"""GLM-5's decoder (``model_type: glm_moe_dsa``), written from the catalog
row's ``config`` and the DeepSeek-V3.2-Exp report's "DeepSeek Sparse
Attention" (github.com/deepseek-ai/DeepSeek-V3.2-Exp, ``inference/model.py``:
``MLA`` and ``Indexer``), in plain ``jax.numpy`` float32 at ``highest``
precision: plain (not absorbed) attention over the whole sequence, no cache,
no kernel, a Python loop over the experts, and no import from
``paddle_tpu``.  It decides ``correct``.

    h      = RMSNorm(x)
    c_q    = RMSNorm(h W_qa);  q = c_q W_qb -> heads of [q_nope | q_rope]
    [c_kv | k_r] = h W_kva;    c_kv = RMSNorm(c_kv)
    q_rope, k_r <- rotary by position, theta^(-2i/d), pairs (2i, 2i+1)
    [k_nope | v] = c_kv W_kvb  per head
    indexer: q^I = c_q W^I_qb -> index heads;  k^I = LayerNorm(h W^I_k);
             the same rotary on the first qk_rope_head_dim dims of both;
             w = h W^I_w
             I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s]),   s <= t
             S_t = the min(index_topk, t + 1) positions of largest I[t, .],
                   ties to the lower position
    att    = softmax over s in S_t of ((q_nope . k_nope + q_rope . k_r)
             * (d_nope + d_rope)^-0.5) v
    x      = x + att W_o
    dense layers:  x = x + W_down(silu(g W_gate) * (g W_up)),  g = RMSNorm(x)
    expert layers: s = sigmoid(g W_r) in float32 over all routed experts;
             chosen = top-k of s + b (b the correction bias; no group limit);
             weights = s[chosen] / (sum + 1e-20) * routed_scaling_factor;
             x = x + SwiGLU_shared(g) + sum over the chosen experts HELD here
    logits = RMSNorm(x_L) W_head                                    (untied)

Departures from the published code, each forced by what the program under
test holds or by the cut (``perfbench/configs/glm-5-ep16-l5.json``):

- the share: ``ep_degree`` / ``ep_rank`` name the experts held here,
  ``[held * rank, held * (rank + 1))``; the router keeps its width, and what
  absent experts would add is LEFT OUT, here as in the program.  The
  embedding and the head have the rows of the vocabulary's slice;
- rotary turns the pairs (2i, 2i+1) in place (``rope_interleave``), in the
  indexer on its FIRST ``qk_rope_head_dim`` dims (the published indexer
  splits ``[rope | rest]``); queries and keys are permuted alike, so another
  placement changes no score;
- no Hadamard rotation and no FP8 quantisation of index queries and keys:
  the rotation is orthogonal (every score is the same in exact arithmetic)
  and the configuration is served in bf16.  The constant ``heads^-0.5 *
  dim^-0.5`` on the index scores changes no order and is left out;
- the correction bias ``b`` is a parameter like any other (the program
  draws it from the seed);
- the multi-token-prediction layer is not part of the language model's
  logits and is left out;
- ``routing`` (optional): the experts the PROGRAM chose for each token, and
  ``selections`` (optional): the positions it selected at the compared
  positions.  A top-k near-tie falls the other way when its input was a
  bf16 stream.  Each handed choice is first checked against this
  reference's own float32 scores (:func:`check_experts`,
  :func:`check_selection`) and only then does the layer compute under it,
  with THIS reference's scores as the weights.  A choice outside its
  epsilon fails the comparison (the logits come back as NaN), and so do
  more than ``*_differ_share`` of the choices falling other than this
  reference's own.  Everywhere else the reference selects for itself.

One sequence at a time, padded to a multiple of the query block; attention
runs a group of heads and a block of queries at a time, the dense
feed-forward in quarters, one expert at a time, each cast to float32 alone,
because on the chip this runs beside the program's weights and its pool.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

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


def _rotate(x, cos, sin):
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(h, gate, up, down):
    g = h @ _f32(gate)
    return (g * jax.nn.sigmoid(g) * (h @ _f32(up))) @ _f32(down)


def _theta(cfg) -> float:
    return float(cfg["rope_parameters"]["rope_theta"])


def _cos_sin(n, cfg):
    rope = cfg["qk_rope_head_dim"]
    inv = 1.0 / _theta(cfg) ** (np.arange(0, rope, 2, dtype=np.float64)
                                / rope)
    angle = jnp.arange(n, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    return jnp.cos(angle), jnp.sin(angle)


# -- one layer's attention, in pieces ----------------------------------------
def _project(x, p, cfg):
    """What attention and the indexer need of ``x (S, hidden)``: ``c_q``,
    ``c_kv``, ``k_r`` (rotated), the index queries, keys and head
    weights."""
    eps, r = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    rope = cfg["qk_rope_head_dim"]
    s = x.shape[0]
    cos, sin = _cos_sin(s, cfg)
    h = _rms(x, p["input_norm"], eps)
    c_q = _rms(h @ _f32(p["q_a"]), p["q_a_norm"], eps)
    kv = h @ _f32(p["kv_a"])
    c_kv = cached(_rms(kv[:, :r], p["kv_a_norm"], eps), "latent")
    k_r = cached(_rotate(kv[:, r:], cos, sin), "latent")
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    q_i = (c_q @ _f32(p["index_q_b"])).reshape(s, heads, dim)
    q_i = index_rotate(q_i, cos[:, None], sin[:, None], rope)
    k_i = _layer_norm(h @ _f32(p["index_k"]), p["index_k_norm_w"],
                      p["index_k_norm_b"], 1e-6)
    k_i = cached(index_rotate(k_i, cos, sin, rope), "index")
    w_i = h @ _f32(p["index_w"])
    return c_q, c_kv, k_r, q_i, k_i, w_i


def index_rotate(x, cos, sin, rope):
    """The indexer's rotary: the first ``rope`` dims of ``x (..., dim)``."""
    return jnp.concatenate([_rotate(x[..., :rope], cos, sin), x[..., rope:]],
                           axis=-1)


def index_products(q_i, k_i, w_i):
    """``sum_j w[t, j] ReLU(q_i[t, j] . k_i[s])``, ``(T, S)``."""
    dots = jnp.einsum("thd,sd->ths", q_i, k_i)
    return jnp.einsum("ths,th->ts", jnp.maximum(dots, 0.0), w_i)


def may_see(t_positions, s):
    """Which of ``s`` keys each query may look at: those at or before
    it."""
    return jnp.arange(s)[None, :] <= t_positions[:, None]


def index_scores(q_i, k_i, w_i, start=0):
    """``I[t, s]`` for the queries ``q_i (T, heads, dim)`` (positions
    ``start..``) against the keys ``k_i (S, dim)``: minus infinity where
    ``s > t``."""
    t, s = q_i.shape[0], k_i.shape[0]
    return jnp.where(may_see(start + jnp.arange(t), s),
                     index_products(q_i, k_i, w_i), NEG)


def index_rows(q_i, k_i, w_i, at):
    """``I[t, .]`` for the queries at the positions ``at (K,)`` alone."""
    return jnp.where(may_see(at, k_i.shape[0]),
                     index_products(q_i[at], k_i, w_i[at]), NEG)


def top_positions(score, k):
    """The ``k`` positions of largest ``score (T, S)``, ties to the lower
    position, as a mask ``(T, S)``; positions at minus infinity (``s >
    t``) are never selected."""
    k = min(int(k), score.shape[1])
    # equal values: lower index first (and minus zero equals zero)
    _, idx = jax.lax.top_k(jnp.where(score == 0, 0.0, score), k)
    rows = jnp.arange(score.shape[0])[:, None]
    mask = jnp.zeros(score.shape, bool).at[rows, idx].set(True)
    return mask & (score > NEG)


def _own_mask(q_i, k_i, w_i, k, block):
    """The reference's own selection for every query, a block of queries
    at a time: ``(S, S)`` bool."""
    s = q_i.shape[0]

    def one(i):
        start = i * block
        q = jax.lax.dynamic_slice_in_dim(q_i, start, block)
        w = jax.lax.dynamic_slice_in_dim(w_i, start, block)
        return top_positions(index_scores(q, k_i, w, start), k)

    return jax.lax.map(one, jnp.arange(s // block)).reshape(s, s)


def _attend_group(c_q, c_kv, k_r, mask, q_b, kv_b, o, cfg, block):
    """A group of heads' part of ``att W_o``: ``q_b (q_lora, G, nope +
    rope)``, ``kv_b (kv_lora, G, nope + v)``, ``o (G * v, hidden)``; a
    block of queries at a time against all keys under ``mask (S, S)``."""
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    s = c_q.shape[0]
    cos, sin = _cos_sin(s, cfg)
    q = jnp.einsum("sr,rgd->sgd", c_q, _f32(q_b))
    q_rope = _rotate(q[..., nope:], cos[:, None], sin[:, None])
    kv = jnp.einsum("sr,rgd->sgd", c_kv, _f32(kv_b))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + rope) ** -0.5

    def one(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * block, block)
        sc = (jnp.einsum("qgd,kgd->gqk", sl(q[..., :nope]), k_nope)
              + jnp.einsum("qgd,kd->gqk", sl(q_rope), k_r)) * scale
        sc = jnp.where(sl(mask)[None], sc, NEG)
        return jnp.einsum("gqk,kgd->qgd", jax.nn.softmax(sc, axis=-1), v)

    att = jax.lax.map(one, jnp.arange(s // block))
    return att.reshape(s, -1) @ _f32(o)


# -- the router -------------------------------------------------------------------
def router_scores(h, router):
    return jax.nn.sigmoid(h @ _f32(router))


def gate_weights(scores, chosen, cfg):
    """The chosen experts' weights ``(T, E)`` (zero elsewhere): the
    UNBIASED scores, normalised over the chosen, times the scaling
    factor.  ``chosen (T, E)`` is 1 at the chosen experts."""
    w = scores * chosen
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


# -- the checks of what the program chose ------------------------------------
def own_experts(scores: np.ndarray, bias: np.ndarray, k: int) -> np.ndarray:
    """``noaux_tc`` with one group: the ``k`` experts of largest ``scores
    + bias``, ``(T, k)``."""
    return np.argsort(-(scores + bias), axis=1, kind="stable")[:, :k]


def check_experts(scores: np.ndarray, bias: np.ndarray, chosen: np.ndarray,
                  k: int, tie_eps: float) -> Dict[str, Any]:
    """Is ``chosen (T, k)`` the top-k of ``scores + bias`` within
    ``tie_eps`` (relative to the cut-off)?"""
    biased = scores + bias
    distinct = np.all(np.diff(np.sort(chosen, axis=1), axis=1) > 0, axis=1)
    cut = -np.sort(-biased, axis=1)[:, k - 1]
    worst = np.take_along_axis(biased, chosen, axis=1).min(1)
    margin = (cut - worst) / np.maximum(np.abs(cut), 1e-6)
    ok = distinct & (margin <= tie_eps)
    differs = np.any(np.sort(chosen, axis=1)
                     != np.sort(own_experts(scores, bias, k), axis=1),
                     axis=1)
    return {"ok": bool(ok.all()), "tokens": int(len(scores)),
            "tokens_failed": int((~ok).sum()),
            "tokens_differ": int(differs.sum()),
            "max_margin": float(max(margin.max(initial=0.0), 0.0))}


def check_selection(score: np.ndarray, chosen: np.ndarray, k: int,
                    tie_eps: float) -> Dict[str, Any]:
    """Is ``chosen`` (positions, -1 = none) a top-``k`` of one query's
    ``score (S,)`` (minus infinity where it may not look) within
    ``tie_eps``?  Every chosen position must be one the query may see,
    none twice, as many as the reference selects itself, and each at most
    ``tie_eps`` under the reference's last selected score, in units of the
    standard deviation of the query's scores.  Reports how many entries
    are other than the reference's own."""
    seen = np.flatnonzero(score > NEG)
    n = min(int(k), len(seen))
    own = np.argsort(-score, kind="stable")[:n]
    chosen = np.asarray(chosen)
    chosen = chosen[chosen >= 0]
    legal = (len(chosen) == n and len(np.unique(chosen)) == n
             and bool(np.isin(chosen, seen).all()))
    margin = 0.0
    if legal and n:
        spread = float(np.std(score[seen])) or 1.0
        margin = max(0.0, float(score[own[-1]] - score[chosen].min())
                     / spread)
    differ = int(n - np.isin(chosen, own).sum()) if legal else n
    return {"ok": bool(legal and margin <= tie_eps), "entries": n,
            "entries_differ": differ, "max_margin": margin}


class Reference:
    """The forward pass for one configuration (``cfg``: the configuration
    file's keys, ``n_routed_experts`` the router's published width, plus
    ``ep_degree`` / ``ep_rank``).  Each block is one jitted program at
    ``highest`` precision."""

    DENSE_CHUNKS = 4

    def __init__(self, cfg: Dict[str, Any], query_block: int = 256,
                 head_group: int = 8):
        self.cfg = cfg
        self.block = int(query_block)
        self.group = min(int(head_group), cfg["num_attention_heads"])
        eps, k = cfg["rms_norm_eps"], cfg["index_topk"]
        blk = self.block
        # fresh callables, so every Reference traces for itself (a reading
        # taken with another `_f32` must not be handed older programs)
        self._project = jax.jit(lambda x, p: _project(x, p, cfg))
        self._own_mask = jax.jit(
            lambda q, kk, w: _own_mask(q, kk, w, k, blk))
        self._rows = jax.jit(index_rows)
        self._attend = jax.jit(
            lambda cq, ckv, kr, m, qb, kvb, o: _attend_group(
                cq, ckv, kr, m, qb, kvb, o, cfg, blk))
        self._norm = jax.jit(lambda x, w: _rms(x, w, eps))
        self._scores = jax.jit(lambda h, w: router_scores(h, w))
        self._gate = jax.jit(lambda s, c: gate_weights(s, c, cfg))
        self._swiglu = jax.jit(lambda h, g, u, d: _swiglu(h, g, u, d))
        self._expert = jax.jit(
            lambda y, h, w, gate, up, down:
            y + w[:, None] * _swiglu(h, gate, up, down))
        self._head = jax.jit(lambda h, w: h @ _f32(w))

    # -- attention -----------------------------------------------------------
    def _attention(self, x, p, at, chosen, tie_eps, report):
        """``x + att W_o`` for one padded sequence ``x (S, hidden)``.
        ``at (K,)`` are the compared positions and ``chosen (K, topk)``
        the program's selections there (None: the reference's own)."""
        cfg = self.cfg
        heads, g = cfg["num_attention_heads"], self.group
        nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        dv, k = cfg["v_head_dim"], cfg["index_topk"]
        c_q, c_kv, k_r, q_i, k_i, w_i = self._project(x, p)
        mask = self._own_mask(q_i, k_i, w_i)
        if chosen is not None:
            rows = np.asarray(self._rows(q_i, k_i, w_i, jnp.asarray(at)))
            accepted = np.zeros(rows.shape, bool)
            for j, (score, sel) in enumerate(zip(rows, chosen)):
                rep = check_selection(score, sel, k, tie_eps)
                report["ok"] &= rep["ok"]
                report["entries"] += rep["entries"]
                report["entries_differ"] += rep["entries_differ"]
                report["max_margin"] = max(report["max_margin"],
                                           rep["max_margin"])
                if rep["ok"]:
                    accepted[j, np.asarray(sel)[np.asarray(sel) >= 0]] = True
                else:
                    accepted[j] = np.asarray(mask[int(at[j])])
            mask = mask.at[jnp.asarray(at)].set(jnp.asarray(accepted))
        q_b = p["q_b"].reshape(-1, heads, nope + rope)
        kv_b = p["kv_b"].reshape(-1, heads, nope + dv)
        y = x
        for a in range(0, heads, g):
            y = y + self._attend(c_q, c_kv, k_r, mask, q_b[:, a:a + g],
                                 kv_b[:, a:a + g],
                                 p["o"][a * dv:(a + g) * dv])
        return y

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
        positions at ``positions[b]``, ``(layers, K, index_topk)`` with -1
        for none.  ``limits``: ``routing_tie_eps``,
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
                moe = 0
                for li, p in enumerate(params["layers"]):
                    x = self._attention(
                        x, p, positions[b],
                        None if selections is None
                        else np.asarray(selections[b])[li],
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
