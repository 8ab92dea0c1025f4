"""Readers of what a Mamba-2 model with latent experts adds:
``layer_metrics/ssm_decode_roofline.statectx.py`` and
``moe_expert_roofline.statectx.py`` are a line each over these.  Every
reader returns None where it finds nothing to read (a program without the
kernel or the spans' attributes: the parent of the change that brought the
kernel), and the harness leaves the metric out."""
from __future__ import annotations

from typing import Optional

from .arith import roofline_share
from .expert_reads import EXPERT_KERNELS, kernel_time, traced_expert_counts
from .sparse_reads import _traced_counter
from .ssm_arith import latent_expert_cost, ssm_decode_cost

SSM_KERNEL = "ssm_state_decode"


def ssm_decode_roofline(run) -> Optional[float]:
    """The decode kernel's least time over its summed device time in the
    traced stretch, in percent: the larger of the states' and the rows'
    bytes over the HBM peak and the FLOPs over the bf16 peak, the rows
    counted by the program (``ssm_rows`` on the ``engine.step`` spans:
    rows stepped, summed over the Mamba layers)."""
    if run["job"] != "serve" or not run["peaks"]:
        return None
    k = kernel_time(run, SSM_KERNEL)
    shape = run["shape"]
    if k is None or "mamba_heads" not in shape:
        return None
    rows = _traced_counter(run, "ssm_rows")
    if rows is None:
        return None
    flops, moved = ssm_decode_cost(rows, shape["mamba_heads"],
                                   shape["mamba_head_dim"],
                                   shape["ssm_state"], shape["ssm_groups"])
    share, _ = roofline_share(flops, moved, k[1], run["peaks"]["bf16_flops"],
                              run["peaks"]["hbm_bytes_s"])
    return share


def latent_expert_roofline(run) -> Optional[float]:
    """The grouped expert products' least time over their summed device
    time in the traced stretch, in percent, at the latent width the
    experts read and write (``expert_io_width``)."""
    if run["job"] != "serve" or not run["peaks"]:
        return None
    k = kernel_time(run, EXPERT_KERNELS)
    counts = traced_expert_counts(run)
    shape = run["shape"]
    if k is None or counts is None or "expert_io_width" not in shape:
        return None
    flops, moved = latent_expert_cost(counts[0], counts[1],
                                      shape["expert_io_width"],
                                      shape["expert_width"])
    share, _ = roofline_share(flops, moved, k[1], run["peaks"]["bf16_flops"],
                              run["peaks"]["hbm_bytes_s"])
    return share
