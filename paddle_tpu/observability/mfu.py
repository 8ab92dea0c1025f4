"""MFU accounting helpers (ISSUE 3): one definition of "model FLOPs
utilization" for the live per-step telemetry and every harness that
reports one.

Two halves:

- the **denominator**: :func:`peak_flops_per_sec` — bf16 peak matmul
  FLOP/s of the device the process runs on, looked up in
  :data:`DEVICE_SPECS` by the ``device_kind`` string jax reports.  A
  device that is not in the table has **no** peak: the lookup returns
  ``None`` and every MFU derived from it is ``None`` ("not measured"),
  never a number over another device's peak;
- the **numerator**: :func:`flops_per_token` — the standard 6N
  fwd+bwd matmul estimate plus the attention term
  ``12·L·h·S`` per token (halved when causal), exactly the formula the
  benchmark has always used.
"""
from __future__ import annotations

from typing import Any, Optional

__all__ = ["PEAK_TFLOPS", "DEVICE_SPECS", "device_spec",
           "peak_flops_per_sec", "param_count",
           "flops_per_token", "mfu", "readback_sync"]

# Per-chip roofline specs keyed by ``jax.devices()[0].device_kind``:
# bf16 peak matmul TFLOP/s, int8 peak TOP/s, peak HBM bandwidth in GB/s
# (Google Cloud TPU documentation, per-generation system architecture
# pages).  ``gen`` is the short marketing name, for reports.  The v5e row
# is the one a chip of this repository's runs has confirmed
# (``device_kind == "TPU v5 lite"``); the others are keyed by the kind
# strings libtpu reports for those generations.
DEVICE_SPECS = {
    "TPU v2":      {"gen": "v2",  "bf16_tflops": 46.0,  "int8_tops": 46.0,
                    "hbm_gbps": 700.0},
    "TPU v3":      {"gen": "v3",  "bf16_tflops": 123.0, "int8_tops": 123.0,
                    "hbm_gbps": 900.0},
    "TPU v4":      {"gen": "v4",  "bf16_tflops": 275.0, "int8_tops": 275.0,
                    "hbm_gbps": 1228.0},
    "TPU v5 lite": {"gen": "v5e", "bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbps": 819.0},
    "TPU v5p":     {"gen": "v5p", "bf16_tflops": 459.0, "int8_tops": 918.0,
                    "hbm_gbps": 2765.0},
    "TPU v6 lite": {"gen": "v6e", "bf16_tflops": 918.0, "int8_tops": 1836.0,
                    "hbm_gbps": 1640.0},
}

# bf16 peak matmul TFLOPs per chip by device kind — a derived view
PEAK_TFLOPS = {kind: spec["bf16_tflops"]
               for kind, spec in DEVICE_SPECS.items()}


def device_spec(device_kind: Optional[str] = None) -> dict:
    """Resolve a device kind to its roofline spec.

    Returns ``device_kind``, ``gen``, ``known`` plus the ``bf16_tflops`` /
    ``int8_tops`` / ``hbm_gbps`` columns.  An unknown kind (a CPU, a
    generation not in the table) comes back with ``known=False``,
    ``gen=None`` and **no** peak columns — callers that attribute time
    (the roofline) book it under the explicit ``"unknown_device"`` sink,
    callers that want a utilization get ``None``."""
    if device_kind is None:
        import jax
        device_kind = getattr(jax.devices()[0], "device_kind", "")
    spec = DEVICE_SPECS.get(device_kind)
    if spec is None:
        return {"device_kind": device_kind, "gen": None, "known": False}
    return {"device_kind": device_kind, "known": True, **spec}


def peak_flops_per_sec() -> Optional[float]:
    """Peak bf16 FLOP/s of the first visible device, or None when the
    device is not in :data:`DEVICE_SPECS`."""
    spec = device_spec()
    return spec["bf16_tflops"] * 1e12 if spec["known"] else None


def param_count(params: Any) -> int:
    """Total element count of a parameter pytree."""
    import jax
    import numpy as np
    return sum(int(np.prod(v.shape))
               for v in jax.tree_util.tree_leaves(params))


def flops_per_token(n_params: int, num_layers: Optional[int] = None,
                    hidden_size: Optional[int] = None,
                    seq_len: Optional[int] = None,
                    causal: bool = True, fwd_only: bool = False) -> float:
    """Train-step (fwd+bwd) FLOPs per token: 6N for the matmuls, plus the
    attention term ``12·L·h·S`` when the transformer shape is known
    (halved for causal masking).  With no shape info this degrades to
    the plain 6N estimate — still the right order for MLPs/CNNs.

    ``fwd_only=True`` divides by 3 (2N + fwd attention) — the serving /
    decode estimate ``bench_serve`` and the engine MFU line share."""
    total = 6.0 * float(n_params)
    if num_layers and hidden_size and seq_len:
        attn = 12.0 * num_layers * hidden_size * seq_len
        total += attn / 2.0 if causal else attn
    return total / 3.0 if fwd_only else total


def mfu(tokens_per_sec: float, flops_token: float,
        peak: Optional[float] = None) -> Optional[float]:
    """Achieved / peak FLOP throughput; None when the device has no
    known peak (and none was passed)."""
    peak = peak or peak_flops_per_sec()
    if not peak:
        return None
    return tokens_per_sec * flops_token / peak


def readback_sync(x) -> float:
    """Host readback of a scalar: waits for the device and copies the
    value — the step breakdown's "readback" component."""
    return float(x)
