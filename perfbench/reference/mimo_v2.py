"""MiMo-V2-Flash's decoder (``model_type: mimo_v2_flash``), written from the
catalog row's ``config`` and ``described_as`` (huggingface.co/XiaomiMiMo/
MiMo-V2-Flash, config.json: "SWA(128) with learnable sink bias; global GQA;
qk 192 / v 128; 256 experts, top-8, 0 shared"), in plain ``jax.numpy``
float32 at ``highest`` precision: masked attention over the whole sequence,
no cache, no kernel, no skipped block, a Python loop over the experts, and
no import from ``paddle_tpu``.  It decides ``correct``.

For layer ``l`` of kind full (``hybrid_layer_pattern[l] == 0``) or window
(``1``), ``x`` the residual stream, no bias:

    h      = RMSNorm(x, eps layernorm_epsilon)
    q      = h W_q -> 64 heads of 192;  k = h W_k, v = h W_v -> n_kv heads
             of 192 and of 128;  n_kv = num_key_value_heads (full) or
             swa_num_key_value_heads (window)
    rotary on the first R = even(0.334 x 192) = 64 of the 192 dims of q and
             k, pairs (i, i + R/2), angle = position x base^(-2i/R), base
             rope_theta (full) or swa_rope_theta (window)
    v      = v x attention_value_scale
    s_ij   = q_i . k_j / sqrt(192), query head h against key/value head
             h // (64 / n_kv), over j <= i and, in a window layer, i - j <
             sliding_window (the query itself counts)
    p_ij   = exp(s_ij) / (exp(sink_h) + sum_j' exp(s_ij'))   with a sink
             (window layers: add_swa_attention_sink_bias), else softmax
    x      = x + (sum_j p_ij v_j) W_o
    g      = RMSNorm(x)
    moe_layer_freq[l] == 0:  x = x + W_down(silu(g W_gate) * (g W_up))
    else:  s = sigmoid(g W_r) in float32 over all routed experts; chosen =
           top-8 of s + b (e_score_correction_bias; n_group 1); weights =
           s[chosen] / (sum + 1e-20)  (norm_topk_prob; routed_scaling_factor
           null = 1); x = x + sum over the chosen experts HELD here; no
           shared expert
    logits = RMSNorm(x_L) W_head                                    (untied)

Departures and assumptions (``perfbench/configs/mimo-v2-flash-ep16-l7.json``
lists them under ``assumed``):

- the share: ``ep_degree`` / ``ep_rank`` name the experts held here,
  ``[held * rank, held * (rank + 1))``; the router keeps its width, and what
  absent experts would add is LEFT OUT, here as in the program.  The
  embedding and the head have the rows of the vocabulary's slice;
- which dims turn, and the pairing: the first 64, half-split (the family's
  ``rotate_half`` on a leading rotary slice); the sink as one logit a query
  head in the softmax's denominator; the value scale on ``v`` before the
  product; a window that counts the query itself;
- sinks and the correction bias are parameters like any other (the program
  draws them from the seed);
- the three multi-token-prediction layers are not part of the language
  model's logits and are left out;
- ``routing`` (optional): the experts the PROGRAM chose for each token.  A
  top-k near-tie falls the other way when its input was a bf16 stream.  Each
  handed choice is first checked against this reference's own float32 scores
  (``glm5.check_experts``, shared: the router is the same published one) and
  only then does the layer compute under it, with THIS reference's scores as
  the weights.  A choice outside ``routing_tie_eps`` fails the comparison
  (the logits come back as NaN), and so do more than ``routing_differ_share``
  of the choices falling other than this reference's own.

One sequence at a time, padded to a multiple of the query block; attention
runs a key/value head's query heads and a block of queries at a time, the
dense feed-forward in quarters, one expert at a time, each cast to float32
alone, because on the chip this runs beside the program's weights and pools.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.glm5 import check_experts, own_experts

F32 = jnp.float32
NEG = -np.inf


def _f32(w):
    """A weight matrix as the reference computes with it."""
    return w.astype(F32)


def cached(x, what):
    """What a cache would hold of ``what`` (``"k"`` or ``"v"`` rows): the
    reference keeps float32 (a control swaps this for a lower precision)."""
    del what
    return x


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(F32)


def _swiglu(h, gate, up, down):
    g = h @ _f32(gate)
    return (g * jax.nn.sigmoid(g) * (h @ _f32(up))) @ _f32(down)


def rotary_dim(cfg) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"]) // 2 * 2


def _rotate(x, n, r, base):
    """The first ``r`` dims of ``x (n, heads, dim)``, pairs ``(i, i +
    r/2)``, by the positions ``0..n``."""
    inv = 1.0 / float(base) ** (np.arange(0, r, 2, dtype=np.float64) / r)
    angle = jnp.arange(n, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], axis=-1)


def layer_kind(cfg, layer: int) -> Dict[str, Any]:
    """What the configuration says of layer ``layer``'s attention."""
    window = bool(cfg["hybrid_layer_pattern"][layer])
    return {"window": int(cfg["sliding_window"]) if window else None,
            "n_kv": int(cfg["swa_num_key_value_heads"] if window
                        else cfg["num_key_value_heads"]),
            "base": cfg["swa_rope_theta"] if window else cfg["rope_theta"],
            "sink": bool(cfg["add_swa_attention_sink_bias"] if window
                         else cfg["add_full_attention_sink_bias"])}


def _qkv(x, p, cfg, kind):
    """``q (S, heads, 192)``, ``k (S, held kv heads, 192)``, ``v (S, held
    kv heads, 128)`` of one padded sequence ``x (S, hidden)``."""
    s = x.shape[0]
    dk, dv, r = cfg["head_dim"], cfg["v_head_dim"], rotary_dim(cfg)
    h = _rms(x, p["input_norm"], cfg["layernorm_epsilon"])
    q = _rotate((h @ _f32(p["q"])).reshape(s, -1, dk), s, r, kind["base"])
    k = _rotate((h @ _f32(p["k"])).reshape(s, -1, dk), s, r, kind["base"])
    v = (h @ _f32(p["v"])).reshape(s, -1, dv) * cfg["attention_value_scale"]
    return q, cached(k, "k"), cached(v, "v")


def _attend_group(q, k, v, sink, window, scale, block):
    """One key/value head's query heads: ``q (S, per, dk)`` against ``k (S,
    dk)`` / ``v (S, dv)``, a block of queries at a time against all keys
    under the mask; ``sink (per,)`` or None."""
    s = q.shape[0]
    keys = jnp.arange(s)[None, :]

    def one(i):
        at = i * block + jnp.arange(block)[:, None]
        seen = keys <= at
        if window is not None:
            seen &= at - keys < window
        sc = jnp.einsum("qhd,kd->hqk",
                        jax.lax.dynamic_slice_in_dim(q, i * block, block),
                        k) * scale
        sc = jnp.where(seen[None], sc, NEG)
        m = jnp.max(sc, axis=-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink[:, None, None])
        e = jnp.exp(sc - m)
        total = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            total = total + jnp.exp(sink[:, None, None] - m)
        return jnp.einsum("hqk,kd->qhd", e / total, v)

    out = jax.lax.map(one, jnp.arange(s // block))
    return out.reshape(s, q.shape[1], v.shape[-1])


def router_scores(h, router, scoring_func):
    logits = h @ _f32(router)
    return (jax.nn.sigmoid(logits) if scoring_func == "sigmoid"
            else jax.nn.softmax(logits, axis=-1))


def gate_weights(scores, chosen, cfg):
    """The chosen experts' weights ``(T, E)`` (zero elsewhere): the UNBIASED
    scores, normalised over the chosen, times the scaling factor (null: 1).
    ``chosen (T, E)`` is 1 at the chosen experts."""
    w = scores * chosen
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * (cfg.get("routed_scaling_factor") or 1.0)


class Reference:
    """The forward pass for one configuration (``cfg``: the configuration
    file's keys, ``n_routed_experts`` the router's published width, plus
    ``ep_degree`` / ``ep_rank``).  Each block is one jitted program at
    ``highest`` precision."""

    DENSE_CHUNKS = 4

    def __init__(self, cfg: Dict[str, Any], query_block: int = 256):
        self.cfg = cfg
        self.block = int(query_block)
        eps = cfg["layernorm_epsilon"]
        scale = cfg["head_dim"] ** -0.5
        blk = self.block
        # fresh callables, so every Reference traces for itself (a reading
        # taken with another `_f32` must not be handed older programs)
        self._qkv = [jax.jit(lambda x, p, kind=layer_kind(cfg, i):
                             _qkv(x, p, cfg, kind))
                     for i in range(cfg["num_hidden_layers"])]
        self._attend = {
            w: jax.jit(lambda q, k, v, s, w=w: _attend_group(
                q, k, v, s, w, scale, blk))
            for w in {layer_kind(cfg, i)["window"]
                      for i in range(cfg["num_hidden_layers"])}}
        self._out = jax.jit(lambda x, a, o: x + a.reshape(a.shape[0], -1)
                            @ _f32(o))
        self._norm = jax.jit(lambda x, w: _rms(x, w, eps))
        self._scores = jax.jit(
            lambda h, w: router_scores(h, w, cfg["scoring_func"]))
        self._gate = jax.jit(lambda s, c: gate_weights(s, c, cfg))
        self._swiglu = jax.jit(lambda h, g, u, d: _swiglu(h, g, u, d))
        self._expert = jax.jit(
            lambda y, h, w, gate, up, down:
            y + w[:, None] * _swiglu(h, gate, up, down))
        self._head = jax.jit(lambda h, w: h @ _f32(w))

    # -- attention -----------------------------------------------------------
    def _attention(self, x, p, layer: int):
        """``x + att W_o`` for one padded sequence ``x (S, hidden)``."""
        kind = layer_kind(self.cfg, layer)
        q, k, v = self._qkv[layer](x, p)
        n_kv = kind["n_kv"]
        per = q.shape[1] // n_kv
        sink = p["sink"].astype(F32) if kind["sink"] else None
        groups = [self._attend[kind["window"]](
            q[:, g * per:(g + 1) * per], k[:, g], v[:, g],
            None if sink is None else sink[g * per:(g + 1) * per])
            for g in range(n_kv)]
        return self._out(x, jnp.concatenate(groups, axis=1), p["o"])

    # -- feed-forward ----------------------------------------------------------
    def _expert_layer(self, h, p, chosen, real, tie_eps):
        """``sum_e w_e SwiGLU_e(h)`` over the HELD experts for ``h (T,
        hidden)``.  ``chosen (T, k)`` are the program's choices where
        ``real (T,)``; elsewhere (padding) and when None the reference's
        own.  Returns ``(y, report or None)``."""
        cfg = self.cfg
        k = cfg["num_experts_per_tok"]
        scores = self._scores(h, p["router"])
        host = np.asarray(scores)
        bias = np.asarray(p["router_bias"], np.float32)
        mine = own_experts(host, bias, k)
        report = None
        if chosen is not None:
            report = check_experts(host[real], bias, chosen[real], k,
                                   tie_eps)
            mine[real] = chosen[real]
        weight = np.zeros_like(host)
        np.put_along_axis(weight, mine, 1.0, axis=1)
        w = self._gate(self._gate_scores(scores, p), jnp.asarray(weight))
        held = p["w_gate"].shape[0]
        first = held * cfg["ep_rank"]
        y = jnp.zeros_like(h)
        for e in range(held):
            y = self._expert(y, h, w[:, first + e], p["w_gate"][e],
                             p["w_up"][e], p["w_down"][e])
        return y, report

    def _gate_scores(self, scores, p):
        """The scores that weigh the chosen experts: the unbiased ones."""
        return scores

    def _dense(self, h, gate, up, down):
        step = -(-gate.shape[1] // self.DENSE_CHUNKS)
        y = jnp.zeros_like(h)
        for a in range(0, gate.shape[1], step):
            y = y + self._swiglu(h, gate[:, a:a + step], up[:, a:a + step],
                                 down[a:a + step])
        return y

    # -- the whole pass ----------------------------------------------------------
    def logits_at(self, params: Dict[str, Any], ids, positions, lengths,
                  routing=None, limits: Optional[Dict[str, float]] = None):
        """Logits ``(B, K, V)`` float32 at ``positions (B, K)`` of the
        sequences ``ids (B, S)`` (``ids[b, lengths[b]:]`` is padding) and
        the report.  ``routing[b]``: the program's experts, ``(expert
        layers, lengths[b], k)``.  ``limits``: ``routing_tie_eps``,
        ``routing_differ_share``."""
        cfg = self.cfg
        lim = {"routing_tie_eps": 0.0, "routing_differ_share": 1.0}
        lim.update(limits or {})
        ids, positions = np.asarray(ids), np.asarray(positions)
        route = {"ok": True, "tokens": 0, "tokens_failed": 0,
                 "tokens_differ": 0, "max_margin": 0.0}
        out = []
        with jax.default_matmul_precision("highest"):
            for b in range(ids.shape[0]):
                n = int(lengths[b])
                s = -(-n // self.block) * self.block
                row = np.zeros((s,), np.int32)
                row[:n] = ids[b, :n]
                real = np.arange(s) < n
                x = params["embed"][jnp.asarray(row)].astype(F32)
                moe = 0
                for li, p in enumerate(params["layers"]):
                    x = self._attention(x, p, li)
                    h = self._norm(x, p["post_attn_norm"])
                    if "router" in p:
                        chosen = None
                        if routing is not None:
                            chosen = np.zeros(
                                (s, cfg["num_experts_per_tok"]), np.int64)
                            chosen[:n] = np.asarray(routing[b])[moe]
                        y, rep = self._expert_layer(
                            h, p, chosen, real, lim["routing_tie_eps"])
                        moe += 1
                        if rep is not None:
                            route["ok"] &= rep["ok"]
                            for key in ("tokens", "tokens_failed",
                                        "tokens_differ"):
                                route[key] += rep[key]
                            route["max_margin"] = max(route["max_margin"],
                                                      rep["max_margin"])
                    else:
                        y = self._dense(h, *p["dense"])
                    x = x + y
                hidden = self._norm(x, params["norm"])
                out.append(np.asarray(self._head(
                    hidden[jnp.asarray(positions[b])], params["head"])))
        logits = np.stack(out)
        route["ok"] &= (route["tokens_differ"]
                        <= lim["routing_differ_share"] * route["tokens"])
        report = {"ok": bool(route["ok"]), "routing": route}
        if not report["ok"]:
            logits = np.full_like(logits, np.nan)
        return logits, report
