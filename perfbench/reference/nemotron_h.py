"""Nemotron-3-Super's decoder (``model_type: nemotron_h``), written from the
catalog row's ``config`` and ``described_as`` (huggingface.co/nvidia/
NVIDIA-Nemotron-3-Super-120B-A12B-BF16, config.json: "Mamba-2; GQA 32Q/2KV;
512 experts, top-22, 1 shared; LatentMoE: experts in 1024-d latent; routed
scaling 5"), in plain ``jax.numpy`` float32 at ``highest`` precision: the
Mamba-2 layers as their SEQUENTIAL recurrence (a ``lax.scan`` over
positions, not the chunked form the program prefills with), attention as a
plain masked softmax over the whole sequence, the experts computed densely
over every row, no cache, no kernel, and no import from ``paddle_tpu``.  It
decides ``correct``.

``hybrid_override_pattern[l]`` names layer ``l``; with ``x`` the residual
stream and ``h = RMSNorm(x, eps layer_norm_epsilon)`` every layer is ``x <- x
+ f(h)``, no bias but the convolution's:

    M  [z | xBC | dt] = h W_in       widths 8192 | 10240 | 128
       xBC = SiLU(b_conv + sum_i w_conv[i] xBC_{t - 3 + i})   depthwise, causal
       [x | B | C] = xBC             x: 128 heads of 64; B, C: 8 groups of 128
       per head n, group g = n // 16:
         dt = softplus(dt_n + dt_bias_n);  A = -exp(A_log_n)
         S_t = exp(dt A) S_{t-1} + dt x_{t,n} (outer) B_{t,g}     S: 64 x 128
         y_{t,n} = S_t C_{t,g} + D_n x_{t,n}
       f(h) = GroupRMSNorm_8(y * SiLU(z)) W_out      the norm after the gate,
                                                     over 8 groups of 1024
    *  q = h W_q (32 heads of 128), k = h W_k, v = h W_v (2 heads of 128);
       s_ij = q_i . k_j / sqrt(128) over j <= i, query head j reading
       key/value head j // 16; f(h) = softmax(s) v W_o        (no rotary)
    E  s = sigmoid(h W_r) in float32 over all 512 experts; chosen = top-22 of
       s + b (e_score_correction_bias; n_group 1); w = s[chosen] / sum x 5
       (norm_topk_prob, routed_scaling_factor); u = h W_latent_down (1024);
       f(h) = (sum over the chosen experts HELD here w_e relu(u W1_e)^2 W2_e)
              W_latent_up + relu(h W1_s)^2 W2_s     (the shared expert, 5376)
    logits = RMSNorm(x_L) W_head                                      (untied)

Assumed readings (``perfbench/configs/nemotron3-super-ep8-l11.json`` lists
them under ``assumed``): no rotary in attention (``rope_theta`` and
``partial_rotary_factor`` are in the config, and the family's modeling code
reads neither); the router and the shared expert read ``h`` at full width,
only the routed experts work in the latent; no norm inside the latent; the
multi-token-prediction layer left out (not part of the language model's
logits); ``dt`` not clamped (the config sets no ``time_step_limit``).

The share: ``ep_degree`` / ``ep_rank`` name the experts held here; the router
keeps its width, and what absent experts would add is LEFT OUT, here as in
the program.  The embedding and the head have the rows of the vocabulary's
slice.  ``routing`` (optional): the experts the PROGRAM chose for each token,
checked against this reference's own float32 scores within
``routing_tie_eps`` and counted against ``routing_differ_share``
(``glm5.check_experts``, shared: the router is the same published one)
before the layer computes under them, with THIS reference's scores as the
weights.

``controls``: the written controls of the tests, each a reading of the
published description that this model is NOT (``rotary``,
``router_reads_latent``, ``shared_reads_latent``, ``swiglu`` for relu2,
``norm_before_gate``, ``one_norm_group``, ``group_by_mod``, ``no_d``,
``no_dt_bias``, ``no_softplus``, ``no_conv_bias``, ``softmax_router``).
The module-level ``_f32`` (a weight as the reference computes with it),
``cached`` (keys and values as a cache would hold them) and ``kept_state``
(the recurrent state as it is kept between the program's steps) are the
precision controls' seams.

One sequence at a time; each block is a jitted program, each weight cast to
float32 alone, because on the chip this runs beside the program's weights
and state.  ``logits_at(..., marks=)`` also hands back each Mamba layer's
state at two positions of each sequence, for the builder's check of the
state the program keeps.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.glm5 import check_experts, own_experts

F32 = jnp.float32
NEG = -np.inf
CONTROLS = ("rotary", "router_reads_latent", "shared_reads_latent", "swiglu",
            "norm_before_gate", "one_norm_group", "group_by_mod", "no_d",
            "no_dt_bias", "no_softplus", "no_conv_bias", "softmax_router")


def _f32(w):
    """A weight matrix as the reference computes with it."""
    return w.astype(F32)


def cached(x, what):
    """What a cache would hold of ``what`` (``"k"`` or ``"v"`` rows): the
    reference keeps float32 (a control swaps this for a lower precision)."""
    del what
    return x


def kept_state(s):
    """The recurrent state as it is kept from one of the program's steps to
    the next: float32 (a control swaps this for a lower precision)."""
    return s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(F32)


def _act(a, swiglu: bool):
    if swiglu:                    # a control: SwiGLU with the one matrix
        return a * jax.nn.sigmoid(a) * a
    r = jnp.maximum(a, 0.0)
    return r * r


def _rotate(x, theta):
    """Rotary over all dims, half-split pairs, positions ``0..n`` (only a
    control: the model applies none)."""
    n, _, d = x.shape
    inv = 1.0 / float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angle = jnp.arange(n, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mamba(x, p, cfg, ctl, keep_from, marks=None):
    """``x + f(RMSNorm(x))`` of a Mamba-2 layer over one padded sequence
    ``x (S, hidden)``; the state is kept (:func:`kept_state`) from position
    ``keep_from`` on.  With ``marks = (t0, t1)`` also ``(S_t0, S_t1,
    kept)``: the state after positions ``t0`` and ``t1``, and what each head
    keeps of the first by the second, ``exp(A sum dt)`` over ``(t0, t1]``."""
    eps = cfg["layer_norm_epsilon"]
    heads, p_dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d_inner = heads * p_dim
    conv_dim = d_inner + 2 * g * n
    s = x.shape[0]
    h = _rms(x, p["norm"], eps)
    zxd = h @ _f32(p["w_in"])
    z, xbc, dt = (zxd[:, :d_inner], zxd[:, d_inner:d_inner + conv_dim],
                  zxd[:, d_inner + conv_dim:])
    xp = jnp.concatenate([jnp.zeros((k - 1, conv_dim), F32), xbc])
    w = _f32(p["conv_w"])
    conv = sum(xp[i:i + s] * w[i] for i in range(k))
    if not ctl["no_conv_bias"]:
        conv = conv + _f32(p["conv_b"])
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(s, heads, p_dim)
    bm = xbc[:, d_inner:d_inner + g * n].reshape(s, g, n)
    cm = xbc[:, d_inner + g * n:].reshape(s, g, n)
    group = (np.arange(heads) % g if ctl["group_by_mod"]
             else np.arange(heads) // (heads // g))
    if not ctl["no_dt_bias"]:
        dt = dt + p["dt_bias"].astype(F32)
    if not ctl["no_softplus"]:
        dt = jax.nn.softplus(dt)
    a = -jnp.exp(p["A_log"].astype(F32))

    t0, t1 = (-1, -1) if marks is None else marks

    def step(carry, inp):
        state, at0, at1 = carry
        t, xt, dtt, bt, ct = inp
        state = (state * jnp.exp(dtt * a)[:, None, None]
                 + (dtt[:, None] * xt)[:, :, None] * bt[group][:, None, :])
        state = jnp.where(t >= keep_from, kept_state(state), state)
        if marks is not None:
            at0 = jnp.where(t == t0, state, at0)
            at1 = jnp.where(t == t1, state, at1)
        return (state, at0, at1), jnp.einsum("hpn,hn->hp", state, ct[group])

    zeros = jnp.zeros((heads, p_dim, n), F32)
    none = () if marks is None else zeros
    (_, at0, at1), y = jax.lax.scan(step, (zeros, none, none),
                                    (jnp.arange(s), xs, dt, bm, cm))
    if not ctl["no_d"]:
        y = y + p["D"].astype(F32)[None, :, None] * xs
    y = y.reshape(s, d_inner)
    groups = 1 if ctl["one_norm_group"] else g
    norm_w = p["gate_norm"].astype(F32)

    def group_norm(v):
        v = v.reshape(s, groups, d_inner // groups)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
        return v.reshape(s, d_inner) * norm_w

    if ctl["norm_before_gate"]:
        y = group_norm(y) * jax.nn.silu(z)
    else:
        y = group_norm(y * jax.nn.silu(z))
    x = x + y @ _f32(p["w_out"])
    if marks is None:
        return x
    between = (jnp.arange(s) > t0) & (jnp.arange(s) <= t1)
    kept = jnp.exp(a * jnp.sum(jnp.where(between[:, None], dt, 0.0), axis=0))
    return x, (at0, at1, kept)


def _qkv(x, p, cfg, ctl):
    s = x.shape[0]
    d = cfg["head_dim"]
    h = _rms(x, p["norm"], cfg["layer_norm_epsilon"])
    q = (h @ _f32(p["q"])).reshape(s, -1, d)
    k = (h @ _f32(p["k"])).reshape(s, -1, d)
    v = (h @ _f32(p["v"])).reshape(s, -1, d)
    if ctl["rotary"]:
        q, k = _rotate(q, cfg["rope_theta"]), _rotate(k, cfg["rope_theta"])
    return q, cached(k, "k"), cached(v, "v")


def _attend_group(q, k, v, scale, block):
    """One key/value head's query heads, ``q (S, per, d)`` against ``k`` /
    ``v (S, d)``, a block of queries at a time under the causal mask."""
    s = q.shape[0]
    keys = jnp.arange(s)[None, :]

    def one(i):
        at = i * block + jnp.arange(block)[:, None]
        sc = jnp.einsum("qhd,kd->hqk",
                        jax.lax.dynamic_slice_in_dim(q, i * block, block),
                        k) * scale
        sc = jnp.where((keys <= at)[None], sc, NEG)
        return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(one, jnp.arange(s // block))
    return out.reshape(s, q.shape[1], v.shape[-1])


def router_scores(h, router, softmax: bool):
    logits = h @ _f32(router)
    return (jax.nn.softmax(logits, axis=-1) if softmax
            else jax.nn.sigmoid(logits))


def gate_weights(scores, chosen, cfg):
    """The chosen experts' weights ``(T, E)`` (zero elsewhere): the
    unbiased scores, normalised over the chosen, times the scaling
    factor."""
    w = scores * chosen
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


class Reference:
    """The forward pass for one configuration (``cfg``: the configuration
    file's keys, ``n_routed_experts`` the router's published width, plus
    ``ep_degree`` / ``ep_rank``)."""

    def __init__(self, cfg: Dict[str, Any], query_block: int = 256,
                 **controls: bool):
        unknown = set(controls) - set(CONTROLS)
        if unknown:
            raise ValueError(f"no control {sorted(unknown)}")
        self.cfg = cfg
        self.block = int(query_block)
        ctl = self.ctl = {c: bool(controls.get(c, False)) for c in CONTROLS}
        eps = cfg["layer_norm_epsilon"]
        scale = cfg["head_dim"] ** -0.5
        blk = self.block
        self._mamba = jax.jit(lambda x, p, keep: _mamba(x, p, cfg, ctl, keep))
        self._mamba_marked = jax.jit(
            lambda x, p, keep, t0, t1: _mamba(x, p, cfg, ctl, keep, (t0, t1)))
        self._qkv = jax.jit(lambda x, p: _qkv(x, p, cfg, ctl))
        self._attend = jax.jit(lambda q, k, v: _attend_group(q, k, v, scale,
                                                             blk))
        self._out = jax.jit(lambda x, a, o: x + a.reshape(a.shape[0], -1)
                            @ _f32(o))
        self._norm = jax.jit(lambda x, w: _rms(x, w, eps))
        self._scores = jax.jit(lambda h, w: router_scores(
            h, w, ctl["softmax_router"]))
        self._gate = jax.jit(lambda s, c: gate_weights(s, c, cfg))
        self._down = jax.jit(lambda h, w: h @ _f32(w))
        self._expert = jax.jit(
            lambda y, u, w, up, down:
            y + w[:, None] * (_act(u @ _f32(up), ctl["swiglu"])
                              @ _f32(down)))
        self._shared = jax.jit(lambda h, up, down: _act(
            h @ _f32(up), ctl["swiglu"]) @ _f32(down))
        self._head = jax.jit(lambda h, w: h @ _f32(w))

    def _attention(self, x, p):
        q, k, v = self._qkv(x, p)
        n_kv = k.shape[1]
        per = q.shape[1] // n_kv
        groups = [self._attend(q[:, i * per:(i + 1) * per], k[:, i], v[:, i])
                  for i in range(n_kv)]
        return self._out(x, jnp.concatenate(groups, axis=1), p["o"])

    def _expert_layer(self, x, p, chosen, real, tie_eps):
        """``x + f(RMSNorm(x))`` of an expert layer.  ``chosen (T, k)`` are
        the program's choices where ``real (T,)``; elsewhere, and when
        None, the reference's own.  Returns ``(x, report or None)``."""
        cfg, ctl = self.cfg, self.ctl
        k = cfg["num_experts_per_tok"]
        h = self._norm(x, p["norm"])
        u = self._down(h, p["latent_down"])
        scores = self._scores(u if ctl["router_reads_latent"] else h,
                              p["router"] if not ctl["router_reads_latent"]
                              else p["router"][:u.shape[1]])
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
        held = p["w_up"].shape[0]
        first = held * cfg["ep_rank"]
        y = jnp.zeros_like(u)
        for e in range(held):
            y = self._expert(y, u, w[:, first + e], p["w_up"][e],
                             p["w_down"][e])
        y = self._down(y, p["latent_up"])
        if ctl["shared_reads_latent"]:
            up = p["shared_up"][:u.shape[1]]
            y = y + self._shared(u, up, p["shared_down"])
        else:
            y = y + self._shared(h, p["shared_up"], p["shared_down"])
        return x + y, report

    def logits_at(self, params: Dict[str, Any], ids, positions, lengths,
                  routing=None, limits: Optional[Dict[str, float]] = None,
                  marks=None):
        """Logits ``(B, K, V)`` float32 at ``positions (B, K)`` of the
        sequences ``ids (B, S)`` (``ids[b, lengths[b]:]`` is padding) and
        the report.  ``routing[b]``: the program's experts, ``(expert
        layers, lengths[b], k)``.  ``limits``: ``routing_tie_eps``,
        ``routing_differ_share``.  The state is kept
        (:func:`kept_state`) from each sequence's first checked position
        on: where the program's prefill ended and its decode steps
        began.  ``marks (B, 2)``: also, third, each Mamba layer's states
        at the two positions of sequence ``b`` and what each head keeps of
        the first by the second (``states[b][layer] = (S_t0, S_t1,
        kept)``, numpy)."""
        cfg = self.cfg
        lim = {"routing_tie_eps": 0.0, "routing_differ_share": 1.0}
        lim.update(limits or {})
        ids, positions = np.asarray(ids), np.asarray(positions)
        route = {"ok": True, "tokens": 0, "tokens_failed": 0,
                 "tokens_differ": 0, "max_margin": 0.0}
        out, states = [], []
        with jax.default_matmul_precision("highest"):
            for b in range(ids.shape[0]):
                n = int(lengths[b])
                s = -(-n // self.block) * self.block
                row = np.zeros((s,), np.int32)
                row[:n] = ids[b, :n]
                real = np.arange(s) < n
                x = params["embed"][jnp.asarray(row)].astype(F32)
                moe = 0
                states.append([])
                for layer in params["layers"]:
                    p = {k: v for k, v in layer.items() if k != "kind"}
                    if layer["kind"] == "M" and marks is not None:
                        x, marked = self._mamba_marked(
                            x, p, int(positions[b, 0]), int(marks[b][0]),
                            int(marks[b][1]))
                        states[-1].append(tuple(np.asarray(m)
                                                for m in marked))
                    elif layer["kind"] == "M":
                        x = self._mamba(x, p, int(positions[b, 0]))
                    elif layer["kind"] == "*":
                        x = self._attention(x, p)
                    else:
                        chosen = None
                        if routing is not None:
                            chosen = np.zeros(
                                (s, cfg["num_experts_per_tok"]), np.int64)
                            chosen[:n] = np.asarray(routing[b])[moe]
                        x, rep = self._expert_layer(
                            x, p, chosen, real, lim["routing_tie_eps"])
                        moe += 1
                        if rep is not None:
                            route["ok"] &= rep["ok"]
                            for key in ("tokens", "tokens_failed",
                                        "tokens_differ"):
                                route[key] += rep[key]
                            route["max_margin"] = max(route["max_margin"],
                                                      rep["max_margin"])
                hidden = self._norm(x, params["norm"])
                out.append(np.asarray(self._head(
                    hidden[jnp.asarray(positions[b])], params["head"])))
        logits = np.stack(out)
        route["ok"] &= (route["tokens_differ"]
                        <= lim["routing_differ_share"] * route["tokens"])
        report = {"ok": bool(route["ok"]), "routing": route}
        if not report["ok"]:
            logits = np.full_like(logits, np.nan)
        if marks is None:
            return logits, report
        return logits, report, states


def expert_layer_whole(params_layer, cfg, h):
    """What the UNCUT layer's experts give for ``h (T, hidden)`` (every
    routed expert of ``params_layer``, which holds all of them, and the
    shared one): the sum the shares' parts must add up to."""
    k = cfg["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(h, F32)
        u = h @ _f32(params_layer["latent_down"])
        scores = router_scores(h, params_layer["router"], False)
        mine = own_experts(np.asarray(scores),
                           np.asarray(params_layer["router_bias"]), k)
        weight = np.zeros(scores.shape, np.float32)
        np.put_along_axis(weight, mine, 1.0, axis=1)
        w = gate_weights(scores, jnp.asarray(weight), cfg)
        y = jnp.zeros_like(u)
        for e in range(params_layer["w_up"].shape[0]):
            y = y + w[:, e:e + 1] * (_act(u @ _f32(params_layer["w_up"][e]),
                                          False)
                                     @ _f32(params_layer["w_down"][e]))
        return np.asarray(y @ _f32(params_layer["latent_up"])
                          + _act(h @ _f32(params_layer["shared_up"]), False)
                          @ _f32(params_layer["shared_down"]))
