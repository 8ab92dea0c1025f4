"""The three flash-attention training kernels' share of their roofline, in
percent: the time the chip needs at least for the attention of the steps
traced (the larger of FLOPs over the bf16 peak and bytes over the HBM peak,
from shapes) over the summed device time of ``flash_fwd``,
``flash_bwd_dkdv`` and ``flash_bwd_dq``.  Returns nothing where the trace has
none of them (a mesh takes XLA attention)."""
from perfbench.harness.arith import flash_needs, roofline_share

KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")


def read(run):
    t = run["trace"]
    if run["job"] != "train" or not t or not run["peaks"]:
        return None
    k = t.get("kernels", {})
    if not all(name in k for name in KERNELS):
        return None
    s, tr = run["shape"], run["train"]
    flops, moved = flash_needs(*(k[name][0] for name in KERNELS),
                               tr["batch"], s["heads"], tr["seq"],
                               s["head_dim"])
    seconds = sum(k[name][1] for name in KERNELS)
    share, _ = roofline_share(flops, moved, seconds,
                              run["peaks"]["bf16_flops"],
                              run["peaks"]["hbm_bytes_s"])
    return share
