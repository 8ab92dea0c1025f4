"""dots3-note-prev's language model (``model_type: dots3_note``), written
from the catalog row's ``config`` and ``described_as``
(huggingface.co/dots-studio/dots3-note-prev, config.json: "MLA + DSA indexer
(full layers); SWA(513) with its own low-rank (kv_lora 1024) latent
attention + headwise gate -- 46L: 13 full + 33 sliding"), in plain
``jax.numpy`` float32 at ``highest`` precision: plain (not absorbed)
attention over the whole sequence under a mask, no cache, no kernel, no
skipped block, a Python loop over the experts, and no import from
``paddle_tpu``.  It decides ``correct``.

Layer ``l`` is of kind F (``layer_types[l] == "full_attention"``) or S
(``"sliding_attention"``); an S layer reads every attention size under its
``swa_`` key.  ``x`` the residual stream, no bias but the index keys'
LayerNorm:

    h      = RMSNorm(x)
    c_q    = RMSNorm(h W_qa);   q = c_q W_qb -> heads of [q_nope | q_rope]
    [c_kv | k_r] = h W_kva;     c_kv = RMSNorm(c_kv)
    q_rope, k_r <- rotary by position, base^(-2i/d), pairs (2i, 2i+1); base
             rope_theta (F, 8e7) or swa_rope_theta (S, 5e4)
    [k_nope | v] = c_kv W_kvb  per head
    s_ij   = (q_nope . k_nope + q_rope . k_r) * (d_nope + d_rope)^-0.5
             over j <= i; in S layers only i - j < sliding_window_size (513:
             the query and the 512 before it); in F layers only the j the
             indexer selects:
             q^I = c_q W^I_qb -> 64 heads of 128;  k^I = LayerNorm(h W^I_k);
             the layer's rotary on the first d_rope dims of both; w = h W^I_w
             I[i, j] = sum_n w[i, n] ReLU(q^I[i, n] . k^I[j]),  j <= i
             S_i = the min(index_topk, i + 1) positions of largest I[i, .],
                   ties to the lower position
    o_n    = softmax(s) v   for each head n
    g      = sigmoid(h W_g)  (hidden x heads; attention_gate_type headwise)
    x      = x + concat_n(g_n o_n) W_o
    layer 0 (first_k_dense_replace 1): x = x + W_down(silu(u W_gate) *
             (u W_up)),  u = RMSNorm(x)
    others: s = sigmoid(u W_r) in float32 over all 256 routed experts;
             chosen = top-8 of s + b (b the correction bias; n_group 1);
             weights = s[chosen] / (sum + 1e-20) * routed_scaling_factor (1);
             x = x + SwiGLU_shared(u) + sum over the chosen experts HELD here
    logits = RMSNorm(x_L) W_head                                    (untied)

Departures and assumptions (``perfbench/configs/dots3-note-ep16-l5.json``
lists them under ``assumed``):

- the share: ``ep_degree`` / ``ep_rank`` name the experts held here,
  ``[held * rank, held * (rank + 1))``; the router keeps its width, and what
  absent experts would add is LEFT OUT, here as in the program.  The
  embedding and the head have the rows of the vocabulary's slice;
- ``apply_mla_qkv_lora_rescale`` is a constant factor on the low-rank
  projections that learned weights and norm gains absorb: 1 here; the gate's
  input is ``h``, as in the headwise gated attention of Qiu et al.
  (arXiv:2505.06708);
- rotary turns pairs (2i, 2i+1) in place, in the indexer on its FIRST d_rope
  dims; queries and keys are permuted alike, so another placement changes no
  score.  No Hadamard rotation or FP8 quantisation of index queries and keys
  (orthogonal, and the configuration is served in bf16); no constant on the
  index scores (it changes no order);
- the correction bias is a parameter like any other (the program draws it
  from the seed); the multi-token-prediction layer and the vision and audio
  towers are not part of the language model's logits and are left out;
- ``routing`` / ``selections`` (optional): the experts and the positions the
  PROGRAM chose.  A near-tie falls the other way when its input was a bf16
  stream, so each handed choice is checked against this reference's own
  float32 scores (``glm5.check_experts`` / ``glm5.check_selection``: the
  router and the indexer are GLM-5's published ones) and only then does the
  layer compute under it, with THIS reference's scores as the weights.  A
  choice outside its epsilon fails the comparison (the logits come back as
  NaN), and so do more than ``*_differ_share`` of the choices falling other
  than this reference's own.

One sequence at a time, padded to a multiple of the query block; attention
runs a group of heads and a block of queries at a time, the dense
feed-forward in quarters, one expert at a time, each cast to float32 alone,
because on the chip this runs beside the program's weights and pools.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.glm5 import (check_experts, check_selection,
                                      own_experts)

F32 = jnp.float32
NEG = -np.inf


def _f32(w):
    """A weight matrix as the reference computes with it."""
    return w.astype(F32)


def cached(x, what):
    """What a cache would hold of ``what`` (``"latent"`` rows, ``"index"``
    keys): the reference keeps float32 (a control swaps this for a lower
    precision)."""
    del what
    return x


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(F32)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


def _swiglu(h, gate, up, down):
    g = h @ _f32(gate)
    return (g * jax.nn.sigmoid(g) * (h @ _f32(up))) @ _f32(down)


def _cos_sin(n, rope, theta):
    inv = 1.0 / float(theta) ** (np.arange(0, rope, 2, dtype=np.float64)
                                 / rope)
    angle = jnp.arange(n, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    return jnp.cos(angle), jnp.sin(angle)


def _rotate(x, cos, sin):
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def layer_kind(cfg, layer: int) -> Dict[str, Any]:
    """What the configuration says of layer ``layer``'s attention."""
    sliding = cfg["layer_types"][layer] == "sliding_attention"
    p = "swa_" if sliding else ""
    return {"heads": int(cfg[p + "num_attention_heads"]),
            "kv_lora": int(cfg[p + "kv_lora_rank"]),
            "nope": int(cfg[p + "qk_nope_head_dim"]),
            "rope": int(cfg[p + "qk_rope_head_dim"]),
            "v": int(cfg[p + "v_head_dim"]),
            "theta": float(cfg[p + "rope_theta"]),
            "window": int(cfg["sliding_window_size"]) if sliding else None,
            "index": not sliding,
            "gate": cfg[p + "attention_gate_type"] == "headwise"}


# -- one layer's attention, in pieces ----------------------------------------
def _project(x, p, cfg, kind):
    """What attention, the gate and the indexer need of ``x (S, hidden)``:
    ``c_q``, ``c_kv``, ``k_r`` (rotated), the gate ``(S, heads)`` (ones
    without one) and the index queries, keys and head weights (None
    without an indexer)."""
    eps, r, rope = cfg["rms_norm_eps"], kind["kv_lora"], kind["rope"]
    s = x.shape[0]
    cos, sin = _cos_sin(s, rope, kind["theta"])
    h = _rms(x, p["input_norm"], eps)
    c_q = _rms(h @ _f32(p["q_a"]), p["q_a_norm"], eps)
    kv = h @ _f32(p["kv_a"])
    c_kv = cached(_rms(kv[:, :r], p["kv_a_norm"], eps), "latent")
    k_r = cached(_rotate(kv[:, r:], cos, sin), "latent")
    gate = (jax.nn.sigmoid(h @ _f32(p["gate"])) if kind["gate"]
            else jnp.ones((s, kind["heads"]), F32))
    index = None
    if kind["index"]:
        heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
        turn = lambda a, c, sn: jnp.concatenate(            # noqa: E731
            [_rotate(a[..., :rope], c, sn), a[..., rope:]], axis=-1)
        q_i = turn((c_q @ _f32(p["index_q_b"])).reshape(s, heads, dim),
                   cos[:, None], sin[:, None])
        k_i = _layer_norm(h @ _f32(p["index_k"]), p["index_k_norm_w"],
                          p["index_k_norm_b"], 1e-6)
        k_i = cached(turn(k_i, cos, sin), "index")
        index = (q_i, k_i, h @ _f32(p["index_w"]))
    return c_q, c_kv, k_r, gate, index


def index_scores(q_i, k_i, w_i, at):
    """``I[t, .]`` for the queries at the positions ``at (T,)``: minus
    infinity past the query."""
    dots = jnp.einsum("thd,sd->ths", q_i[at], k_i)
    score = jnp.einsum("ths,th->ts", jnp.maximum(dots, 0.0), w_i[at])
    return jnp.where(jnp.arange(k_i.shape[0])[None, :] <= at[:, None],
                     score, NEG)


def _top(score, k):
    """The ``k`` positions of largest ``score (T, S)`` as a mask, ties to
    the lower position; positions at minus infinity never."""
    k = min(int(k), score.shape[1])
    _, idx = jax.lax.top_k(jnp.where(score == 0, 0.0, score), k)
    mask = jnp.zeros(score.shape, bool).at[
        jnp.arange(score.shape[0])[:, None], idx].set(True)
    return mask & (score > NEG)


def _own_mask(index, seen, k, block):
    """The reference's own selection for every query among the keys
    ``seen (S, S)`` lets it see, a block of queries at a time: ``(S, S)``
    bool."""
    q_i, k_i, w_i = index
    s = q_i.shape[0]

    def one(i):
        at = i * block + jnp.arange(block)
        return _top(jnp.where(seen[at], index_scores(q_i, k_i, w_i, at),
                              NEG), k)

    return jax.lax.map(one, jnp.arange(s // block)).reshape(s, s)


def _base_mask(s, window):
    """Keys at or before the query and, with a window, inside it."""
    at = jnp.arange(s)
    seen = at[None, :] <= at[:, None]
    if window is not None:
        seen &= at[:, None] - at[None, :] < window
    return seen


def _attend_group(c_q, c_kv, k_r, mask, q_b, kv_b, kind, scale, block):
    """A group of heads: ``q_b (q_lora, G, nope + rope)``, ``kv_b
    (kv_lora, G, nope + v)``; a block of queries at a time against all keys
    under ``mask (S, S)``.  Returns ``(S, G, v)``."""
    nope, s = kind["nope"], c_q.shape[0]
    cos, sin = _cos_sin(s, kind["rope"], kind["theta"])
    q = jnp.einsum("sr,rgd->sgd", c_q, _f32(q_b))
    q_rope = _rotate(q[..., nope:], cos[:, None], sin[:, None])
    kv = jnp.einsum("sr,rgd->sgd", c_kv, _f32(kv_b))
    k_nope, v = kv[..., :nope], kv[..., nope:]

    def one(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * block, block)
        sc = (jnp.einsum("qgd,kgd->gqk", sl(q[..., :nope]), k_nope)
              + jnp.einsum("qgd,kd->gqk", sl(q_rope), k_r)) * scale
        sc = jnp.where(sl(mask)[None], sc, NEG)
        return jnp.einsum("gqk,kgd->qgd", jax.nn.softmax(sc, axis=-1), v)

    return jax.lax.map(one, jnp.arange(s // block)).reshape(s, q.shape[1],
                                                             -1)


def router_scores(h, router, scoring_func):
    logits = h @ _f32(router)
    return (jax.nn.sigmoid(logits) if scoring_func == "sigmoid"
            else jax.nn.softmax(logits, axis=-1))


def gate_weights(scores, chosen, cfg):
    """The chosen experts' weights ``(T, E)`` (zero elsewhere): the
    UNBIASED scores, normalised over the chosen, times the scaling
    factor.  ``chosen (T, E)`` is 1 at the chosen experts."""
    w = scores * chosen
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


class Reference:
    """The forward pass for one configuration (``cfg``: the configuration
    file's keys, ``n_routed_experts`` the router's published width, plus
    ``ep_degree`` / ``ep_rank``).  Each block is one jitted program at
    ``highest`` precision."""

    DENSE_CHUNKS = 4

    def __init__(self, cfg: Dict[str, Any], query_block: int = 256,
                 head_group: int = 8):
        self.cfg = cfg
        self.block = blk = int(query_block)
        self.group = int(head_group)
        eps, k = cfg["rms_norm_eps"], cfg["index_topk"]
        kinds = [self._kind(i) for i in range(cfg["num_hidden_layers"])]
        self.kinds = kinds
        # fresh callables, so every Reference traces for itself (a reading
        # taken with another `_f32` must not be handed older programs)
        self._project = [jax.jit(lambda x, p, kind=kd: _project(x, p, cfg,
                                                                kind))
                         for kd in kinds]
        self._attend = [jax.jit(
            lambda cq, ckv, kr, m, qb, kvb, kind=kd: _attend_group(
                cq, ckv, kr, m, qb, kvb, kind, self._scale(kind), blk))
            for kd in kinds]
        self._base = jax.jit(_base_mask, static_argnums=(0, 1))
        self._own_mask = jax.jit(lambda ix, m: _own_mask(ix, m, k, blk))
        self._rows = jax.jit(index_scores)
        self._out = jax.jit(lambda x, a, g, o: x + self.gated_output(a, g, o))
        self._norm = jax.jit(lambda x, w: _rms(x, w, eps))
        self._scores = jax.jit(
            lambda h, w: router_scores(h, w, cfg["scoring_func"]))
        self._gate = jax.jit(lambda s, c: gate_weights(s, c, cfg))
        self._swiglu = jax.jit(lambda h, g, u, d: _swiglu(h, g, u, d))
        self._expert = jax.jit(
            lambda y, h, w, gate, up, down:
            y + w[:, None] * _swiglu(h, gate, up, down))
        self._head = jax.jit(lambda h, w: h @ _f32(w))

    # -- what a control may replace -----------------------------------------
    def _kind(self, layer: int) -> Dict[str, Any]:
        return layer_kind(self.cfg, layer)

    @staticmethod
    def _scale(kind) -> float:
        return (kind["nope"] + kind["rope"]) ** -0.5

    @staticmethod
    def gated_output(att, gate, o):
        """``concat_n(g_n o_n) W_o``: ``att (S, heads, v)``, ``gate (S,
        heads)``."""
        return (att * gate[..., None]).reshape(att.shape[0], -1) @ _f32(o)

    # -- attention -----------------------------------------------------------
    def _attention(self, x, p, li, at, chosen, tie_eps, report):
        """``x + att W_o`` for one padded sequence ``x (S, hidden)``.
        ``at (K,)`` are the compared positions and ``chosen (K, topk)`` the
        program's selections there (None: the reference's own)."""
        cfg, kind = self.cfg, self.kinds[li]
        heads, nope, rope, dv = (kind["heads"], kind["nope"], kind["rope"],
                                 kind["v"])
        s = x.shape[0]
        c_q, c_kv, k_r, gate, index = self._project[li](x, p)
        mask = self._base(s, kind["window"])
        if index is not None:
            own = self._own_mask(index, mask)
            if chosen is not None:
                rows = np.where(np.asarray(mask)[np.asarray(at)], np.asarray(
                    self._rows(*index, jnp.asarray(at))), NEG)
                accepted = np.zeros(rows.shape, bool)
                for j, (score, sel) in enumerate(zip(rows, chosen)):
                    rep = check_selection(score, sel, cfg["index_topk"],
                                          tie_eps)
                    report["ok"] &= rep["ok"]
                    report["entries"] += rep["entries"]
                    report["entries_differ"] += rep["entries_differ"]
                    report["max_margin"] = max(report["max_margin"],
                                               rep["max_margin"])
                    sel = np.asarray(sel)
                    if rep["ok"]:
                        accepted[j, sel[sel >= 0]] = True
                    else:
                        accepted[j] = np.asarray(own[int(at[j])])
                own = own.at[jnp.asarray(at)].set(jnp.asarray(accepted))
            mask = mask & own
        q_b = p["q_b"].reshape(-1, heads, nope + rope)
        kv_b = p["kv_b"].reshape(-1, heads, nope + dv)
        g = min(self.group, heads)
        att = jnp.concatenate([
            self._attend[li](c_q, c_kv, k_r, mask, q_b[:, a:a + g],
                             kv_b[:, a:a + g])
            for a in range(0, heads, g)], axis=1)
        return self._out(x, att, gate, p["o"])

    # -- feed-forward ----------------------------------------------------------
    def _expert_layer(self, h, p, chosen, real, tie_eps):
        """``SwiGLU_shared(h) + sum_e w_e SwiGLU_e(h)`` over the HELD
        experts for ``h (T, hidden)``.  ``chosen (T, k)`` are the
        program's choices where ``real (T,)``; elsewhere (padding) and
        when None the reference's own.  Returns ``(y, report or None)``."""
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
        w = self._gate(scores, jnp.asarray(weight))
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

    # -- the whole pass ----------------------------------------------------------
    def logits_at(self, params: Dict[str, Any], ids, positions, lengths,
                  routing=None, selections=None,
                  limits: Optional[Dict[str, float]] = None):
        """Logits ``(B, K, V)`` float32 at ``positions (B, K)`` of the
        sequences ``ids (B, S)`` (``ids[b, lengths[b]:]`` is padding) and
        the report.  ``routing[b]``: the program's experts, ``(expert
        layers, lengths[b], k)``; ``selections[b]``: its selected
        positions at ``positions[b]``, ``(layers with an indexer, K,
        index_topk)`` with -1 for none.  ``limits``: ``routing_tie_eps``,
        ``routing_differ_share``, ``index_tie_eps``,
        ``index_differ_share``."""
        cfg = self.cfg
        lim = {"routing_tie_eps": 0.0, "routing_differ_share": 1.0,
               "index_tie_eps": 0.0, "index_differ_share": 1.0}
        lim.update(limits or {})
        ids, positions = np.asarray(ids), np.asarray(positions)
        route = {"ok": True, "tokens": 0, "tokens_failed": 0,
                 "tokens_differ": 0, "max_margin": 0.0}
        index = {"ok": True, "entries": 0, "entries_differ": 0,
                 "max_margin": 0.0}
        out = []
        with jax.default_matmul_precision("highest"):
            for b in range(ids.shape[0]):
                n = int(lengths[b])
                s = -(-n // self.block) * self.block
                row = np.zeros((s,), np.int32)
                row[:n] = ids[b, :n]
                real = np.arange(s) < n
                x = params["embed"][jnp.asarray(row)].astype(F32)
                moe = indexed = 0
                for li, p in enumerate(params["layers"]):
                    chosen = None
                    if self.kinds[li]["index"]:
                        if selections is not None:
                            chosen = np.asarray(selections[b])[indexed]
                        indexed += 1
                    x = self._attention(x, p, li, positions[b], chosen,
                                        lim["index_tie_eps"], index)
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
        index["ok"] &= (index["entries_differ"]
                        <= lim["index_differ_share"] * index["entries"])
        report = {"ok": bool(route["ok"] and index["ok"]),
                  "routing": route, "index": index}
        if not report["ok"]:
            logits = np.full_like(logits, np.nan)
        return logits, report
