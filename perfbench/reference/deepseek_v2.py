"""DeepSeek-V2's decoder, written from the layer equations of the paper
(arXiv:2405.04434, sections 2.1 and 2.2) and the published modelling code,
in plain ``jax.numpy`` float32 at ``highest`` precision: plain (not
absorbed) attention over the whole sequence, no cache, no kernel, a Python
loop over the experts, and no import from ``paddle_tpu``.  It decides
``correct``.

    h      = RMSNorm(x)
    c_q    = RMSNorm(h W_qa);  q = c_q W_qb -> heads of [q_nope | q_rope]
    [c_kv | k_r] = h W_kva;    c_kv = RMSNorm(c_kv)
    q_rope, k_r <- YaRN rotary by position (k_r is shared by the heads)
    [k_nope | v] = c_kv W_kvb  per head
    att    = softmax((q_nope . k_nope + q_rope . k_r) * scale + causal) v
    x      = x + att W_o
    layer 0:   x = x + W_down(silu(h' W_gate) * (h' W_up)),  h' = RMSNorm(x)
    layers 1..: s = softmax(h' W_g) in float32 over all routed experts;
               a group's score is its best expert's; the topk_group best
               groups stay; the num_experts_per_tok best experts in them;
               weights are those s times routed_scaling_factor (not
               renormalised);  x = x + sum_e w_e SwiGLU_e(h') + SwiGLU_shared(h')
    logits = RMSNorm(x_L) W_head                                (untied)

YaRN: inverse frequencies theta^(-2i/d) blended with the same over
``factor`` by the linear ramp between the ``beta_fast`` and ``beta_slow``
correction dimensions, at every position; cos/sin times m(factor, mscale) /
m(factor, mscale_all_dim); scale = (d_nope + d_rope)^-0.5 * m(factor,
mscale_all_dim)^2 with m(s, a) = 0.1 a ln s + 1.

Departures from the published code, each forced by what the program under
test holds or by the cut (``perfbench/configs/deepseek-v2-ep4-l5.json``):

- the share: ``ep_degree`` / ``ep_rank`` name the experts held here,
  ``[held * rank, held * (rank + 1))``; the router keeps its width, and
  what absent experts would add is LEFT OUT of the layer's result, here as
  in the program.  The embedding and the head have the rows of the
  vocabulary's slice;
- rotary turns the pairs (2i, 2i+1) in place, where the published code
  first permutes to (i, i + d/2); queries and keys are permuted alike, so
  every score is the same;
- ``routing`` (optional): the experts the PROGRAM chose for each token.
  A top-k near-tie can fall the other way when the router's input was a
  bf16 residual stream, and one swapped expert moves logits by far more
  than rounding does.  Each handed choice is first checked against this
  reference's own float32 scores (:func:`check_choice`: at most
  ``topk_group`` groups, every group and every expert within ``tie_eps``
  of the cut-off it had to pass), and only then does the layer compute
  under those choices, with THIS reference's scores as the weights.  A
  choice that fails the check fails the comparison: the logits come back
  as NaN.  So do they when more than ``differ_share`` of the checked
  choices are other than this reference's own: a mean over thousands of
  tokens, which tells a lower precision that the two maxima (the widest
  margin, the largest logit difference) let through;
- sequences of a batch run padded to one length (causal attention keeps
  the padding from every earlier position), so each block compiles once.

Parameters arrive in the reference's own names and in whatever type the
program holds them; one block at a time (a layer's attention on one
sequence, one expert, a quarter of the dense feed-forward) is cast to
float32, because on the chip this runs beside 10 GB of weights and the KV
pool.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(w):
    """A weight matrix as the reference computes with it."""
    return w.astype(F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(F32)


def mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: Dict[str, Any]):
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return jnp.asarray(extra / factor * ramp + extra * (1.0 - ramp), F32)


def _rotate(x, cos, sin):
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(h, gate, up, down):
    g = h @ _f32(gate)
    return (g * jax.nn.sigmoid(g) * (h @ _f32(up))) @ _f32(down)


def _attention(x, p, cfg):
    """One layer's attention on ``x (S, hidden)``, plain form."""
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r, eps = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    rs = cfg["rope_scaling"]
    s = x.shape[0]
    h = _rms(x, p["input_norm"], eps)
    q = _rms(h @ _f32(p["q_a"]), p["q_a_norm"], eps) \
        @ _f32(p["q_b"])
    q = q.reshape(s, heads, nope + rope)
    kv = h @ _f32(p["kv_a"])
    c_kv = _rms(kv[:, :r], p["kv_a_norm"], eps)
    angle = jnp.arange(s, dtype=F32)[:, None] * yarn_inv_freq(
        rope, cfg["rope_theta"], rs)
    m = (mscale(rs["factor"], rs["mscale"])
         / mscale(rs["factor"], rs["mscale_all_dim"]))
    cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m
    q_rope = _rotate(q[..., nope:], cos[:, None], sin[:, None])
    k_r = _rotate(kv[:, r:], cos, sin)
    kvb = (c_kv @ _f32(p["kv_b"])).reshape(s, heads, nope + dv)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = (nope + rope) ** -0.5 * mscale(rs["factor"],
                                           rs["mscale_all_dim"]) ** 2
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + att.reshape(s, heads * dv) @ _f32(p["o"])


def _scores(h, router):
    return jax.nn.softmax(h @ _f32(router), axis=-1)


def own_choice(scores: np.ndarray, cfg) -> np.ndarray:
    """The published ``group_limited_greedy`` on ``scores (T, E)``:
    ``(T, k)`` expert ids."""
    t, e = scores.shape
    g, kg, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    group = scores.reshape(t, g, e // g).max(-1)
    kept = np.argsort(-group, axis=1, kind="stable")[:, :kg]
    keep = np.zeros((t, g), bool)
    np.put_along_axis(keep, kept, True, axis=1)
    masked = np.where(np.repeat(keep, e // g, axis=1), scores, 0.0)
    return np.argsort(-masked, axis=1, kind="stable")[:, :k]


def check_choice(scores: np.ndarray, chosen: np.ndarray, cfg,
                 tie_eps: float) -> Dict[str, Any]:
    """Is ``chosen (T, k)`` what ``group_limited_greedy`` gives on scores
    within ``tie_eps`` (relative) of ``scores (T, E)``?  The chosen
    experts may touch at most ``topk_group`` groups; those groups, filled
    up with the best others, must each lie within ``tie_eps`` of the
    group cut-off; and every chosen expert must lie within ``tie_eps`` of
    the expert cut-off under those groups.  Reports how many tokens'
    choices differ from :func:`own_choice` and the widest margin used."""
    t, e = scores.shape
    g, kg, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    per = e // g
    group = scores.reshape(t, g, per).max(-1)
    distinct = np.all(np.diff(np.sort(chosen, axis=1), axis=1) > 0, axis=1)
    touched = np.zeros((t, g), bool)
    np.put_along_axis(touched, chosen // per, True, axis=1)
    few = touched.sum(1) <= kg
    # the program's groups: those it touched, then the best of the rest
    order = np.argsort(-(group + 2.0 * touched), axis=1, kind="stable")
    keep = np.zeros((t, g), bool)
    np.put_along_axis(keep, order[:, :kg], True, axis=1)
    cut_g = -np.sort(-group, axis=1)[:, kg - 1]
    worst_g = np.where(keep, group, np.inf).min(1)
    margin_g = 1.0 - worst_g / cut_g
    masked = np.where(np.repeat(keep, per, axis=1), scores, 0.0)
    cut_e = -np.sort(-masked, axis=1)[:, k - 1]
    worst_e = np.take_along_axis(scores, chosen, axis=1).min(1)
    margin_e = 1.0 - worst_e / cut_e
    ok = distinct & few & (margin_g <= tie_eps) & (margin_e <= tie_eps)
    differs = np.any(np.sort(chosen, axis=1)
                     != np.sort(own_choice(scores, cfg), axis=1), axis=1)
    return {"ok": bool(ok.all()), "tokens": int(t),
            "tokens_failed": int((~ok).sum()),
            "tokens_differ": int(differs.sum()),
            "max_margin": float(max(margin_g.max(), margin_e.max(), 0.0))}


class Reference:
    """The forward pass for one configuration (``cfg``: the configuration
    file's keys plus ``ep_degree`` / ``ep_rank``).  Each block is one
    jitted program at ``highest`` precision, so a run compiles a handful
    of programs whatever the depth; the blocks are small enough (a
    layer's attention on one sequence, one expert, a quarter of the dense
    feed-forward) for their float32 casts to fit beside the program's own
    weights and pool."""

    DENSE_CHUNKS = 4

    def __init__(self, cfg: Dict[str, Any]):
        self.cfg = cfg
        eps = cfg["rms_norm_eps"]
        # fresh callables, so every Reference traces for itself (jit keys
        # its cache on the function: a reading taken with another `_f32`
        # must not be handed an older instance's programs)
        self._attention = jax.jit(lambda x, p: _attention(x, p, cfg))
        self._norm = jax.jit(lambda x, w: _rms(x, w, eps))
        self._scores = jax.jit(lambda h, w: _scores(h, w))
        self._swiglu = jax.jit(lambda h, g, u, d: _swiglu(h, g, u, d))
        self._expert = jax.jit(
            lambda y, h, w, gate, up, down:
            y + w[:, None] * _swiglu(h, gate, up, down))
        self._head = jax.jit(lambda h, w: h @ _f32(w))

    def _expert_layer(self, h, p, chosen, real, tie_eps):
        """``sum_e w_e SwiGLU_e(h)`` over the HELD experts + the shared
        experts for ``h (T, hidden)``: a Python loop over the held
        experts, each cast alone.  ``chosen (T, k)`` are the program's
        choices where ``real (T,)``; elsewhere (padding) and when None the
        reference's own.  Returns ``(y, report or None)``."""
        cfg = self.cfg
        scores = self._scores(h, p["router"])
        host = np.asarray(scores)
        mine = own_choice(host, cfg)
        report = None
        if chosen is not None:
            report = check_choice(host[real], chosen[real], cfg, tie_eps)
            mine[real] = chosen[real]
        weight = np.zeros_like(host)
        np.put_along_axis(weight, mine, 1.0, axis=1)
        w = scores * jnp.asarray(weight)
        if cfg["norm_topk_prob"] and cfg["num_experts_per_tok"] > 1:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        else:
            w = w * cfg["routed_scaling_factor"]
        held = p["w_gate"].shape[0]
        first = held * cfg["ep_rank"]
        y = (self._swiglu(h, *p["shared"]) if p.get("shared")
             else jnp.zeros_like(h))
        for e in range(held):
            y = self._expert(y, h, w[:, first + e], p["w_gate"][e],
                             p["w_up"][e], p["w_down"][e])
        return y, report

    def _dense(self, h, gate, up, down):
        step = -(-gate.shape[1] // self.DENSE_CHUNKS)
        y = jnp.zeros_like(h)
        for a in range(0, gate.shape[1], step):
            y = y + self._swiglu(h, gate[:, a:a + step], up[:, a:a + step],
                                 down[a:a + step])
        return y

    def logits_at(self, params: Dict[str, Any], ids, positions, lengths,
                  routing=None, tie_eps: float = 0.0,
                  differ_share: float = 1.0):
        """Logits ``(B, K, V)`` float32 at ``positions (B, K)`` of the
        sequences ``ids (B, S)`` (``ids[b, lengths[b]:]`` is padding, which
        causal attention keeps from every earlier position), and the
        routing report.  ``routing[b]``: the program's choices, ``(expert
        layers, lengths[b], k)``.  A choice that fails
        :func:`check_choice`, or more than ``differ_share`` of them other
        than the reference's own, turns the logits into NaN."""
        cfg = self.cfg
        ids = np.asarray(ids)
        b, s = ids.shape
        real = (np.arange(s)[None, :] < np.asarray(lengths)[:, None])
        chosen = None
        if routing is not None:
            k = cfg["num_experts_per_tok"]
            layers = len(routing[0])
            chosen = np.zeros((layers, b, s, k), np.int64)
            for i, r in enumerate(routing):
                chosen[:, i, :int(lengths[i])] = np.asarray(r)
        report = {"ok": True, "tokens": 0, "tokens_failed": 0,
                  "tokens_differ": 0, "max_margin": 0.0}
        with jax.default_matmul_precision("highest"):
            x = params["embed"][jnp.asarray(ids)].astype(F32)   # (B, S, H)
            moe = 0
            for p in params["layers"]:
                x = jnp.stack([self._attention(x[i], p) for i in range(b)])
                h = self._norm(x, p["post_attn_norm"]).reshape(b * s, -1)
                if "router" in p:
                    y, rep = self._expert_layer(
                        h, p, None if chosen is None
                        else chosen[moe].reshape(b * s, -1),
                        real.reshape(-1), tie_eps)
                    moe += 1
                    if rep is not None:
                        report["ok"] &= rep["ok"]
                        for key in ("tokens", "tokens_failed",
                                    "tokens_differ"):
                            report[key] += rep[key]
                        report["max_margin"] = max(report["max_margin"],
                                                   rep["max_margin"])
                else:
                    y = self._dense(h, *p["dense"])
                x = x + y.reshape(b, s, -1)
            hidden = self._norm(x, params["norm"])
            picked = hidden[np.arange(b)[:, None], np.asarray(positions)]
            logits = np.asarray(self._head(picked, params["head"]))
        report["ok"] &= (report["tokens_differ"]
                         <= differ_share * report["tokens"])
        if not report["ok"]:
            logits = np.full_like(logits, np.nan)
        return logits, report
