"""The benchmark's arithmetic: percentiles, per-request token gaps, rates,
and the operations and bytes a kernel's call needs, from its shapes.

Pure Python on plain numbers, so the tests check it on the CPU and no PR that
claims a gain can change how a number is made.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by linear interpolation between the
    order statistics (numpy's default, "linear"); None of nothing."""
    v = sorted(float(x) for x in values)
    if not v:
        return None
    if len(v) == 1:
        return v[0]
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def gaps_inside(token_times: Sequence[float], t0: float, t1: float
                ) -> List[float]:
    """Gaps between consecutive output tokens of one request, counting a gap
    only when BOTH of its tokens were emitted inside ``[t0, t1)``."""
    out = []
    for a, b in zip(token_times, token_times[1:]):
        if t0 <= a and b < t1:
            out.append(b - a)
    return out


def tpot_per_request(requests: Iterable[Sequence[float]], t0: float,
                     t1: float, min_gaps: int = 8) -> List[float]:
    """Per request, the mean gap between its output tokens emitted inside the
    window, over requests with at least ``min_gaps`` such gaps.  A mean per
    request on purpose: single gaps cluster at "one decode step" and "one
    decode step plus a prefill", and a percentile between two clusters jumps."""
    out = []
    for times in requests:
        g = gaps_inside(times, t0, t1)
        if len(g) >= min_gaps:
            out.append(sum(g) / len(g))
    return out


def rate(count: float, seconds: float, chips: int = 1) -> float:
    """Work over seconds over chips."""
    if seconds <= 0 or chips <= 0:
        raise ValueError(f"rate over {seconds} s on {chips} chips")
    return count / seconds / chips


# -- operations and bytes --------------------------------------------------
def train_flops_per_token(n_params: int, num_layers: int, hidden: int,
                          seq_len: int, causal: bool = True) -> float:
    """Forward + backward matmul FLOPs a token, recompute not counted: 6N
    for the weights plus the attention term 12*L*h*S (halved when causal).
    The same count as ``paddle_tpu/observability/mfu.py flops_per_token``."""
    attn = 12.0 * num_layers * hidden * seq_len
    return 6.0 * float(n_params) + (attn / 2.0 if causal else attn)


def flash_needs(n_fwd: int, n_dkdv: int, n_dq: int, batch: int, heads: int,
                seq: int, head_dim: int, causal: bool = True,
                itemsize: int = 2) -> Tuple[float, float]:
    """FLOPs and bytes that the attention of ``n_fwd`` forward-kernel calls
    and ``n_dkdv`` / ``n_dq`` backward-kernel calls NEEDS (one call = one
    layer, whole batch).  One matmul is 2*B*H*S*S*D, halved under a causal
    mask.  Forward: QK^T and PV, 2 matmuls.  Backward: S again, dP, dV, dK,
    dQ, 5 matmuls, counted half to each backward kernel; the kernels here
    replay S and dP in both (7 in all), and the extra two are the
    implementation's cost, not work done.  Bytes, each array B*H*S*D moved
    once: forward reads q, k, v and writes o (4); backward reads q, k, v, o,
    do and writes dq, dk, dv (8, half to each kernel)."""
    matmul = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        matmul /= 2.0
    array = float(batch * heads * seq * head_dim * itemsize)
    flops = matmul * (2.0 * n_fwd + 2.5 * (n_dkdv + n_dq))
    moved = array * (4.0 * n_fwd + 4.0 * (n_dkdv + n_dq))
    return flops, moved


def kv_bytes_per_token(num_layers: int, num_heads: int, head_dim: int,
                       itemsize: int = 2) -> int:
    """Bytes of cached keys and values one token holds over all layers."""
    return 2 * num_layers * num_heads * head_dim * itemsize


def roofline_share(flops: float, bytes_moved: float, seconds: float,
                   peak_flops: float, peak_bytes_s: float
                   ) -> Tuple[Optional[float], str]:
    """The least time the chip could take — the larger of operations over
    peak FLOP/s and bytes over peak bytes/s — over the time taken, in
    percent, and which of the two bounds it.  No clamp: a share over 100
    means the count or the time is wrong, and must show."""
    if seconds <= 0:
        return None, "none"
    t_flops, t_bytes = flops / peak_flops, bytes_moved / peak_bytes_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
