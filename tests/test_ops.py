"""OpTest-style parity tests for the fused-op family (reference test model:
unittests/op_test.py — numpy/XLA reference forward + gradient comparison,
dtype sweep)."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
import paddle_tpu.nn.functional as F
from paddle_tpu.framework import flags
from paddle_tpu import ops

pytestmark = pytest.mark.kernels


def _sdpa_ref(q, k, v, causal):
    # straight einsum reference (no pallas routing)
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
    s = s.astype(jnp.float32)
    if causal:
        ql, kl = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _rand_qkv(b=2, h=2, s=128, d=32, dtype=jnp.float32, seed=0):
    r = np.random.RandomState(seed)
    mk = lambda i: jnp.asarray(r.randn(b, h, s, d) * 0.5, dtype)
    return mk(0), mk(1), mk(2)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_xla(self, causal):
        q, k, v = _rand_qkv()
        out = ops.flash_attention(q, k, v, causal=causal)
        ref = _sdpa_ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_forward_multi_block(self):
        # seq > block size exercises the online-softmax recurrence
        q, k, v = _rand_qkv(b=1, h=2, s=256, d=32)
        out = ops.flash_attention(q, k, v, causal=True)
        ref = _sdpa_ref(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_decode_cache_alignment(self):
        # q_len < kv_len: bottom-right causal alignment (decode semantics)
        b, h, d = 1, 2, 32
        r = np.random.RandomState(3)
        q = jnp.asarray(r.randn(b, h, 128, d), jnp.float32)
        k = jnp.asarray(r.randn(b, h, 256, d), jnp.float32)
        v = jnp.asarray(r.randn(b, h, 256, d), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True)
        ref = _sdpa_ref(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_xla(self, causal):
        q, k, v = _rand_qkv(b=1, h=2, s=128, d=32)

        def loss_flash(q, k, v):
            return jnp.sum(ops.flash_attention(q, k, v, causal=causal) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(_sdpa_ref(q, k, v, causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=f"d{name}")

    def test_bf16(self):
        q, k, v = _rand_qkv(s=128, d=32, dtype=jnp.bfloat16)
        out = ops.flash_attention(q, k, v, causal=True)
        ref = _sdpa_ref(q, k, v, True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2)
        assert out.dtype == jnp.bfloat16

    def test_sdpa_routes_to_flash_under_flag(self):
        q, k, v = _rand_qkv(s=128, d=32)
        try:
            # routing is TPU-only by default; force interpret routing on CPU
            flags.set_flags({"use_pallas_kernels": True,
                             "pallas_interpret_routing": True})
            out_flash = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            flags.set_flags({"use_pallas_kernels": False})
            out_xla = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        finally:
            flags.set_flags({"use_pallas_kernels": True,
                             "pallas_interpret_routing": False})
        np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_xla),
                                   rtol=2e-5, atol=2e-5)

    def test_jit_compatible(self):
        q, k, v = _rand_qkv(s=128, d=32)
        f = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True))
        out = f(q, k, v)
        assert out.shape == q.shape


def _masked_prefill_ref(q, k, v, lens):
    """Today's XLA route of a GPT prefill (``_paged_cache_forward``): an
    (s, s) additive bias for the causal and the length mask."""
    s = q.shape[2]
    cols = jnp.arange(s)
    ok = (cols[None, :] <= cols[:, None])[None] & (
        cols[None, None, :] < lens[:, None, None])
    bias = jnp.where(ok, 0.0, -1e9)[:, None].astype(q.dtype)
    return F.scaled_dot_product_attention(q, k, v, attn_mask=bias,
                                          is_causal=False, training=False)


class TestFlashPrefill:
    """ISSUE 38: the serving prefill's kernel against the masked XLA route
    it replaces: real rows agree, padded rows are finite, a q block wholly
    past the length is zero."""

    @pytest.mark.parametrize("block", [None, 128], ids=["picked", "b128"])
    @pytest.mark.parametrize("length", [1, 127, 129, 300, 511, 512])
    def test_real_rows_match_the_masked_route(self, length, block):
        from paddle_tpu.ops.flash_attention import _prefill_call
        s = 512
        q, k, v = _rand_qkv(b=1, h=2, s=s, d=32, dtype=jnp.bfloat16,
                            seed=length)
        lens = jnp.asarray([length], jnp.int32)
        if block is None:          # what _block_sizes picks: one 512 block
            out = ops.flash_prefill(q, k, v, lens)
        else:                      # 4 x 4 pairs: the skips are exercised
            flat = lambda x: x.reshape(2, s, 32)             # noqa: E731
            out = _prefill_call(flat(q), flat(k), flat(v), lens, 32 ** -0.5,
                                block, block, 2).reshape(q.shape)
        out = np.asarray(out, np.float32)
        ref = np.asarray(_masked_prefill_ref(q, k, v, lens), np.float32)
        np.testing.assert_allclose(out[:, :, :length], ref[:, :, :length],
                                   rtol=2e-2, atol=2e-2)
        assert np.isfinite(out).all()
        if block is not None:
            # every q block that starts at or past the length is zero
            dead = -(-length // block) * block
            assert not out[:, :, dead:].any()

    @pytest.mark.parametrize("lengths", [(400,), (400, 1024), (1, 700)])
    def test_a_q_block_wholly_past_the_length_is_zero(self, lengths):
        """Bucket 1024 in blocks of 512 (``_block_sizes``' pick): a row of
        512 tokens or fewer has a second q block of padding alone; rows of
        a batch differ in length."""
        q, k, v = _rand_qkv(b=len(lengths), h=2, s=1024, d=32)
        lens = jnp.asarray(lengths, jnp.int32)
        out = np.asarray(ops.flash_prefill(q, k, v, lens))
        ref = np.asarray(_masked_prefill_ref(q, k, v, lens))
        assert np.isfinite(out).all()
        for row, n in enumerate(lengths):
            np.testing.assert_allclose(out[row, :, :n], ref[row, :, :n],
                                       rtol=2e-5, atol=2e-5)
            assert (n <= 512) == (not out[row, :, 512:].any())

    def test_full_length_is_trainings_causal_forward(self):
        """One block body: at the full length the prefill entry gives
        exactly what training's ``flash_attention`` gives."""
        q, k, v = _rand_qkv(b=1, h=2, s=256, d=32, dtype=jnp.bfloat16)
        got = ops.flash_prefill(q, k, v, jnp.asarray([256], jnp.int32))
        want = ops.flash_attention(q, k, v, causal=True)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


class TestFusedEpilogues:
    def test_bias_dropout_residual_ln_eval(self):
        r = np.random.RandomState(0)
        x = jnp.asarray(r.randn(4, 16), jnp.float32)
        res = jnp.asarray(r.randn(4, 16), jnp.float32)
        b = jnp.asarray(r.randn(16), jnp.float32)
        g = jnp.ones(16); beta = jnp.zeros(16)
        out = ops.fused_bias_dropout_residual_layer_norm(
            x, res, b, g, beta, dropout_rate=0.0, training=False)
        ref = F.layer_norm(res + x + b, (16,), g, beta)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)

    def test_fused_feedforward_matches_unfused(self):
        r = np.random.RandomState(1)
        x = jnp.asarray(r.randn(2, 8, 16), jnp.float32)
        w1 = jnp.asarray(r.randn(16, 32) * 0.1, jnp.float32)
        b1 = jnp.zeros(32)
        w2 = jnp.asarray(r.randn(32, 16) * 0.1, jnp.float32)
        b2 = jnp.zeros(16)
        g = jnp.ones(16); beta = jnp.zeros(16)
        out = ops.fused_feedforward(x, w1, b1, w2, b2, g, beta,
                                    training=False)
        h = F.gelu(F.linear(F.layer_norm(x, (16,), g, beta), w1, b1))
        ref = x + F.linear(h, w2, b2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


class TestRope:
    def test_rotation_preserves_norm(self):
        q, k, _ = _rand_qkv(s=16, d=32)
        qr, kr = ops.rotary_position_embedding(q, k)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(qr), axis=-1),
            np.linalg.norm(np.asarray(q), axis=-1), rtol=1e-5)

    def test_position_zero_identity(self):
        q, k, _ = _rand_qkv(s=4, d=8)
        pos = jnp.zeros((1, 4), jnp.int32)
        qr, kr = ops.rotary_position_embedding(q, k, position_ids=pos)
        np.testing.assert_allclose(np.asarray(qr), np.asarray(q), rtol=1e-6)

    def test_cached_tables_numerics_identical(self):
        """ISSUE 7 satellite: the lru-cached cos/sin tables must be
        numerically IDENTICAL to the from-scratch computation (same f32
        jnp expressions, evaluated once instead of per layer per call)."""
        from paddle_tpu.ops.fused import _rope_tables
        q, k, _ = _rand_qkv(s=48, d=32)
        b, h, s, d = q.shape

        def scratch(q, k, pos):
            # the pre-cache implementation, verbatim
            inv_freq = 1.0 / (10000.0 ** (jnp.arange(0, d, 2,
                                                     jnp.float32) / d))
            ang = pos[..., None].astype(jnp.float32) * inv_freq
            cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

            def rot(x):
                x1, x2 = x[..., :d // 2], x[..., d // 2:]
                f1, f2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
                return jnp.concatenate(
                    [f1 * cos - f2 * sin, f2 * cos + f1 * sin],
                    -1).astype(x.dtype)

            return rot(q), rot(k)

        hits0 = _rope_tables.cache_info().hits
        got_q, got_k = ops.rotary_position_embedding(q, k)
        ref_q, ref_k = scratch(q, k, jnp.arange(s)[None, :])
        np.testing.assert_array_equal(np.asarray(got_q), np.asarray(ref_q))
        np.testing.assert_array_equal(np.asarray(got_k), np.asarray(ref_k))
        # second call is served from the cache (two multiplies, no
        # inv_freq/cos/sin recomputation)
        ops.rotary_position_embedding(q, k)
        assert _rope_tables.cache_info().hits > hits0
        # concrete position_ids gather from the cached table, same numbers
        pos = jnp.arange(s)[None, :] + 3
        got_q2, _ = ops.rotary_position_embedding(q, k, position_ids=pos)
        ref_q2, _ = scratch(q, k, pos)
        np.testing.assert_array_equal(np.asarray(got_q2),
                                      np.asarray(ref_q2))
        # traced ids still work (on-the-fly fallback)
        f = jax.jit(lambda p: ops.rotary_position_embedding(
            q, k, position_ids=p)[0])
        np.testing.assert_allclose(np.asarray(f(pos)), np.asarray(ref_q2),
                                   rtol=1e-6, atol=1e-6)

    def test_relative_phase(self):
        # attention scores depend only on relative positions after rope
        r = np.random.RandomState(5)
        q = jnp.asarray(r.randn(1, 1, 8, 16), jnp.float32)
        k = jnp.asarray(r.randn(1, 1, 8, 16), jnp.float32)
        q1, k1 = ops.rotary_position_embedding(q, k)
        # shift both positions by a constant: scores unchanged
        pos = jnp.arange(8)[None, :] + 5
        q2, k2 = ops.rotary_position_embedding(q, k, position_ids=pos)
        s1 = jnp.einsum("bhqd,bhkd->bhqk", q1, k1)
        s2 = jnp.einsum("bhqd,bhkd->bhqk", q2, k2)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-4, atol=1e-4)


class TestFlashDropout:
    """In-kernel counter-based attention dropout (reference Philox seeds,
    fused_attention_op.cc:292-311): fused path, deterministic per seed."""

    def test_deterministic_given_seed(self):
        q, k, v = _rand_qkv()
        a = ops.flash_attention(q, k, v, dropout_p=0.3, seed=42)
        b = ops.flash_attention(q, k, v, dropout_p=0.3, seed=42)
        c = ops.flash_attention(q, k, v, dropout_p=0.3, seed=43)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.allclose(np.asarray(a), np.asarray(c))

    def test_eval_mode_disables(self):
        q, k, v = _rand_qkv()
        out = ops.flash_attention(q, k, v, dropout_p=0.3, training=False)
        ref = ops.flash_attention(q, k, v, dropout_p=0.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))

    def test_mean_preserved(self):
        # E[dropout(P)] = P: averaging over seeds approaches no-dropout
        q, k, v = _rand_qkv(b=1, h=2, s=64, d=16)
        ref = np.asarray(ops.flash_attention(q, k, v, dropout_p=0.0))
        acc = np.zeros_like(ref)
        n = 24
        for s in range(n):
            acc += np.asarray(ops.flash_attention(q, k, v, dropout_p=0.3,
                                                  seed=s))
        err = np.abs(acc / n - ref).max() / np.abs(ref).max()
        assert err < 0.25, err

    def test_grad_matches_numeric_with_fixed_seed(self):
        # mask is deterministic given seed, so finite differences are valid
        r = np.random.RandomState(0)
        q, k, v = _rand_qkv(b=1, h=1, s=16, d=8)

        def loss(q_, k_, v_):
            return jnp.sum(ops.flash_attention(q_, k_, v_, causal=True,
                                               dropout_p=0.4, seed=7) ** 2)

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        eps = 1e-3
        for argi, g in enumerate(grads):
            g = np.asarray(g)
            for _ in range(4):   # spot-check 4 random coordinates
                idx = tuple(r.randint(0, s) for s in g.shape)
                args_hi = [np.array(a) for a in (q, k, v)]
                args_lo = [np.array(a) for a in (q, k, v)]
                args_hi[argi][idx] += eps
                args_lo[argi][idx] -= eps
                num = (float(loss(*map(jnp.asarray, args_hi)))
                       - float(loss(*map(jnp.asarray, args_lo)))) / (2 * eps)
                np.testing.assert_allclose(g[idx], num, rtol=2e-2,
                                           atol=2e-3)

    def test_dropout_stays_on_fused_path(self, monkeypatch):
        # dropout>0 must NOT fall back to the XLA path anymore
        import importlib
        fa = importlib.import_module("paddle_tpu.ops.flash_attention")
        calls = []
        orig = fa._flash_fwd

        def spy(*args, **kw):
            calls.append(1)
            return orig(*args, **kw)

        monkeypatch.setattr(fa, "_flash_fwd", spy)
        q, k, v = _rand_qkv(b=1, h=1, s=128, d=16)
        out = ops.flash_attention(q, k, v, dropout_p=0.2, seed=3)
        assert calls, "dropout>0 fell off the fused kernel path"
        assert np.isfinite(np.asarray(out)).all()

    def test_jitted_steps_vary_mask_via_key_scope(self):
        # under key_scope the auto-drawn seed is traced, not a constant
        import paddle_tpu as pt
        q, k, v = _rand_qkv(b=1, h=1, s=64, d=16)

        @jax.jit
        def step(key, q_, k_, v_):
            with pt.key_scope(key):
                return ops.flash_attention(q_, k_, v_, dropout_p=0.3)

        o1 = step(jax.random.key(1), q, k, v)
        o2 = step(jax.random.key(2), q, k, v)
        assert not np.allclose(np.asarray(o1), np.asarray(o2))


class TestFlashRagged:
    """Auto-padding for non-block-multiple sequence lengths."""

    @pytest.mark.parametrize("sq,sk", [(100, 100), (37, 37), (60, 200),
                                       (130, 130)])
    def test_ragged_matches_xla(self, sq, sk):
        r = np.random.RandomState(1)
        q = jnp.asarray(r.randn(1, 2, sq, 16) * 0.5, jnp.float32)
        k = jnp.asarray(r.randn(1, 2, sk, 16) * 0.5, jnp.float32)
        v = jnp.asarray(r.randn(1, 2, sk, 16) * 0.5, jnp.float32)
        for causal in (True, False):
            out = ops.flash_attention(q, k, v, causal=causal)
            ref = _sdpa_ref(q, k, v, causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)

    def test_ragged_grads(self):
        r = np.random.RandomState(2)
        q = jnp.asarray(r.randn(1, 1, 50, 8) * 0.5, jnp.float32)
        k = jnp.asarray(r.randn(1, 1, 70, 8) * 0.5, jnp.float32)
        v = jnp.asarray(r.randn(1, 1, 70, 8) * 0.5, jnp.float32)

        def f_flash(q_, k_, v_):
            return jnp.sum(ops.flash_attention(q_, k_, v_, causal=True) ** 2)

        def f_ref(q_, k_, v_):
            return jnp.sum(_sdpa_ref(q_, k_, v_, True) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)



def _pair_ref(q, k, v, causal, dropout_p=0.0, seed=0):
    """``_sdpa_ref`` with the kernels' counter-based dropout mask applied to
    P.V (``_keep_mask`` over the whole (seq, seq) grid of each head)."""
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    b, h, sq, d = q.shape
    sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
    rows = jnp.arange(sq)[:, None] + jnp.zeros((1, sk), jnp.int32)
    cols = jnp.arange(sk)[None, :] + jnp.zeros((sq, 1), jnp.int32)
    if causal:
        s = jnp.where(rows + (sk - sq) >= cols, s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0:
        bh = jnp.arange(b * h).reshape(b, h, 1, 1)
        keep = fa._keep_mask(jnp.uint32(seed), bh, rows, cols, dropout_p)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _dense_classes(nq, nk, bq, bk, offset, kv_len, causal):
    """Each pair's (live, masked) from its elements, one by one."""
    rows = np.arange(nq * bq)[:, None]
    cols = np.arange(nk * bk)[None, :]
    attends = (cols < kv_len) & ((rows + offset >= cols) if causal
                                 else (rows >= 0))
    tiles = attends.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    live = tiles.any(axis=(2, 3))
    return live, live & ~tiles.all(axis=(2, 3))


class TestFlashPairClasses:
    """The training kernels' pairs by where the causal diagonal and the
    real key length fall: skipped (no grid step), interior (no mask),
    diagonal (the mask is built)."""

    @pytest.mark.parametrize("seq_q,seq_k,causal,want", [
        (2048, 2048, True, (6, 6, 4)),      # gpt3-125m.train, blocks of 512
        (1024, 2048, True, (1, 5, 2)),      # bottom-right: offset 1024
        (2000, 2000, True, (6, 6, 4)),      # padded to 2048: the length cut
        (2000, 2000, False, (0, 12, 4)),    # ... is the last kv block's
        (2048, 2048, False, (0, 16, 0)),
    ], ids=["cell", "offset", "ragged", "ragged-noncausal", "noncausal"])
    def test_counts_a_batch_head(self, seq_q, seq_k, causal, want):
        fa = importlib.import_module("paddle_tpu.ops.flash_attention")
        got = fa.flash_pair_counts(seq_q, seq_k, causal)
        assert (got["skipped"], got["interior"], got["diagonal"]) == want

    @pytest.mark.parametrize("nq,nk,bq,bk,offset,kv_len,causal", [
        (4, 4, 128, 128, 0, 512, True),
        (4, 4, 128, 128, 0, 450, True),
        (2, 4, 128, 128, 256, 512, True),
        (3, 4, 128, 128, 70, 450, True),
        (4, 4, 128, 128, 126, 512, True),     # the diagonal one col in
        (4, 4, 128, 128, 127, 512, True),
        (4, 2, 128, 128, -256, 256, True),      # q blocks with nothing
        (4, 4, 64, 128, -100, 400, True),
        (2, 4, 256, 128, 0, 300, True),
        (4, 4, 128, 128, 0, 450, False),
        (3, 5, 128, 128, 0, 257, False),        # kv blocks wholly padding
    ])
    def test_classes_and_clamps_agree_with_the_elements(
            self, nq, nk, bq, bk, offset, kv_len, causal):
        fa = importlib.import_module("paddle_tpu.ops.flash_attention")
        live, masked = _dense_classes(nq, nk, bq, bk, offset, kv_len,
                                      causal)
        for qi in range(nq):
            for kj in range(nk):
                assert fa._pair_class(qi, kj, bq, bk, offset, kv_len,
                                      causal) == (live[qi, kj],
                                                  masked[qi, kj]), (qi, kj)
        # the index maps clamp to the ends of the live runs: a q block's
        # live kv blocks are 0..last, a kv block's live q blocks first..
        for qi in range(nq):
            last = int(fa._last_kv(qi, bq, bk, offset, kv_len, causal))
            assert 0 <= last < nk
            if live[qi].any():
                assert list(live[qi]) == [j <= last for j in range(nk)], qi
        for kj in range(nk):
            first = int(fa._first_q(kj, bq, bk, offset, kv_len, causal, nq))
            assert 0 <= first < nq
            if live[:, kj].any():
                assert list(live[:, kj]) == [i >= first
                                             for i in range(nq)], kj


class TestFlashPairGrid:
    """The training entry at blocks of 128 on 512 tokens, a 4 x 4 pair
    grid, so skipped, interior, diagonal and length-cut pairs all run:
    forward and all three gradients against the dense reference."""

    @pytest.mark.parametrize("sq,sk,causal,dropout_p", [
        (512, 512, True, 0.0),
        (256, 512, True, 0.0),              # bottom-right offset
        (450, 450, True, 0.0),              # ragged: kv_len < padded
        (450, 450, False, 0.0),
        (512, 512, True, 0.3),
        (512, 256, True, 0.0),              # q blocks with nothing to see
    ], ids=["causal", "offset", "ragged", "noncausal-ragged", "dropout",
            "dead-q-blocks"])
    def test_forward_and_grads_match_the_reference(
            self, monkeypatch, sq, sk, causal, dropout_p):
        fa = importlib.import_module("paddle_tpu.ops.flash_attention")
        monkeypatch.setattr(fa, "_block_sizes",
                            lambda a, b: (min(a, 128), min(b, 128)))
        r = np.random.RandomState(sq + sk)
        q = jnp.asarray(r.randn(1, 2, sq, 32) * 0.5, jnp.float32)
        k = jnp.asarray(r.randn(1, 2, sk, 32) * 0.5, jnp.float32)
        v = jnp.asarray(r.randn(1, 2, sk, 32) * 0.5, jnp.float32)
        # rows that see no key at all (sq > sk, causal) come out zero;
        # the reference spreads them over every key: compare the others
        seen = max(sq - sk, 0) if causal else 0

        def loss(attend):
            return lambda q_, k_, v_: jnp.sum(
                attend(q_, k_, v_)[:, :, seen:] ** 2)

        flash = lambda q_, k_, v_: fa.flash_attention(   # noqa: E731
            q_, k_, v_, causal=causal, dropout_p=dropout_p, seed=11)
        ref = lambda q_, k_, v_: _pair_ref(              # noqa: E731
            q_, k_, v_, causal, dropout_p, seed=11)
        out = np.asarray(flash(q, k, v))
        np.testing.assert_allclose(out[:, :, seen:],
                                   np.asarray(ref(q, k, v))[:, :, seen:],
                                   rtol=2e-5, atol=2e-5)
        assert not out[:, :, :seen].any()
        gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for a, b_, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=f"d{name}")

class TestFlashKVCache:
    """Decode kernel vs full attention over the cache prefix (reference
    CacheKV, fused_attention_op.cc:235)."""

    def test_matches_prefix_attention(self):
        r = np.random.RandomState(3)
        smax, used = 128, 77
        q = jnp.asarray(r.randn(2, 2, 1, 16) * 0.5, jnp.float32)
        kc = jnp.asarray(r.randn(2, 2, smax, 16) * 0.5, jnp.float32)
        vc = jnp.asarray(r.randn(2, 2, smax, 16) * 0.5, jnp.float32)
        out = ops.flash_attention_kvcache(q, kc, vc, used)
        ref = _sdpa_ref(q, kc[:, :, :used], vc[:, :, :used], causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_traced_seqlen_one_program(self):
        # one compiled program serves every decode position
        r = np.random.RandomState(4)
        q = jnp.asarray(r.randn(1, 2, 1, 16), jnp.float32)
        kc = jnp.asarray(r.randn(1, 2, 64, 16), jnp.float32)
        vc = jnp.asarray(r.randn(1, 2, 64, 16), jnp.float32)

        @jax.jit
        def step(qq, ln):
            return ops.flash_attention_kvcache(qq, kc, vc, ln)

        for used in (8, 23, 64):
            out = step(q, jnp.asarray(used, jnp.int32))
            ref = _sdpa_ref(q, kc[:, :, :used], vc[:, :, :used], False)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)


class TestFusionEvidence:
    """Recorded compiler evidence for the 'XLA fusion suffices' design
    claim in ops/fused.py (VERDICT r4 weak #2): the whole
    bias+dropout+residual+LayerNorm epilogue must compile to a handful of
    fused kernels, not one HBM round-trip per elementwise op."""

    def test_epilogue_fuses_to_few_kernels(self):
        from paddle_tpu.ops.fused import (
            fused_bias_dropout_residual_layer_norm as fe)
        x = jnp.ones((4, 256, 512), jnp.float32)
        r = jnp.ones((4, 256, 512), jnp.float32)
        b = jnp.ones((512,))
        s = jnp.ones((512,))
        bb = jnp.zeros((512,))
        f = jax.jit(lambda x, r, b, s, bb, k: fe(
            x, r, b, s, bb, dropout_rate=0.1, training=True, key=k))
        hlo = f.lower(x, r, b, s, bb,
                      jax.random.key(0)).compile().as_text()
        entry = hlo.split("ENTRY")[-1]
        producing = [l for l in entry.splitlines()
                     if "f32[4,256,512]" in l and "=" in l
                     and "parameter" not in l]
        # unfused, the chain (bias add, dropout select, residual add,
        # mean-subtract, var-normalize, scale, shift) would write the
        # full tensor 7+ times; fused it is a handful of kernel outputs
        # (4 on current XLA, 5 on the 0.4.x CPU backend which splits the
        # select+add epilogue into its own fusion)
        assert len(producing) <= 5, (len(producing), producing)


class TestLinearCrossEntropy:
    """ops/fused.py linear_softmax_cross_entropy — the memory-efficient LM
    loss (c_softmax_with_cross_entropy objective without materialized
    logits; B=32 at GPT-125M went out of memory without it)."""

    def _ref(self, hid, W, lab, ignore=-100):
        logits = jnp.einsum("bsh,vh->bsv", hid, W).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        v = W.shape[0]
        picked = jnp.take_along_axis(
            logits, jnp.clip(lab, 0, v - 1)[..., None], -1)[..., 0]
        tok = jnp.where(lab != ignore, lse - picked, 0.0)
        return jnp.sum(tok) / jnp.sum((lab != ignore).astype(jnp.float32))

    @pytest.mark.quick
    def test_loss_and_grad_parity(self):
        from paddle_tpu.ops.fused import linear_softmax_cross_entropy
        rng = np.random.RandomState(0)
        hid = jnp.asarray(rng.randn(2, 256, 32) * 0.4, jnp.float32)
        W = jnp.asarray(rng.randn(97, 32) * 0.4, jnp.float32)
        lab = rng.randint(0, 97, (2, 256))
        lab[0, :9] = -100                      # ignore_index tokens
        lab = jnp.asarray(lab, jnp.int32)
        with jax.default_matmul_precision("highest"):
            got = linear_softmax_cross_entropy(hid, W, lab)
            want = self._ref(hid, W, lab)
            assert abs(float(got - want)) < 1e-6
            g = jax.grad(lambda h, w: linear_softmax_cross_entropy(
                h, w, lab), argnums=(0, 1))(hid, W)
            gr = jax.grad(lambda h, w: self._ref(h, w, lab),
                          argnums=(0, 1))(hid, W)
            for a, b in zip(g, gr):
                assert float(jnp.max(jnp.abs(a - b))) < 1e-6

    def test_reductions_and_fallback(self):
        from paddle_tpu.ops.fused import linear_softmax_cross_entropy
        rng = np.random.RandomState(1)
        hid = jnp.asarray(rng.randn(1, 128, 16) * 0.4, jnp.float32)
        W = jnp.asarray(rng.randn(33, 16) * 0.4, jnp.float32)
        lab = jnp.asarray(rng.randint(0, 33, (1, 128)), jnp.int32)
        with jax.default_matmul_precision("highest"):
            tok = linear_softmax_cross_entropy(hid, W, lab, reduction="none")
            assert tok.shape == (1, 128)
            s = linear_softmax_cross_entropy(hid, W, lab, reduction="sum")
            assert abs(float(jnp.sum(tok) - s)) < 1e-5
            # s=100 has no 128-chunking -> unfused fallback, same numbers
            f = linear_softmax_cross_entropy(hid[:, :100], W, lab[:, :100])
            r = self._ref(hid[:, :100], W, lab[:, :100])
            assert abs(float(f - r)) < 1e-6

    def test_gpt_fused_flag_parity(self):
        """Model-level: fused_lm_loss=True must match the unfused path
        (loss AND a parameter gradient) on a tiny config."""
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny
        rng = np.random.RandomState(2)
        ids = jnp.asarray(rng.randint(0, 1024, (2, 128)), jnp.int32)
        losses, grads = {}, {}
        for fused in (True, False):
            pt.seed(0)
            m = GPTForCausalLM(gpt_tiny(max_position_embeddings=128,
                                        hidden_dropout=0.0,
                                        attention_dropout=0.0,
                                        fused_lm_loss=fused))
            m.train()
            params = m.state_dict()

            def lf(p):
                loss, _ = m.apply(p, ids, labels=ids)
                return loss

            with jax.default_matmul_precision("highest"):
                losses[fused] = float(lf(params))
                g = jax.grad(lf)(params)
            grads[fused] = g["gpt.wte.weight"]
        assert abs(losses[True] - losses[False]) < 1e-5, losses
        err = float(jnp.max(jnp.abs(grads[True] - grads[False])))
        assert err < 1e-5, err

    def test_bf16_path_finite_and_close(self):
        from paddle_tpu.ops.fused import linear_softmax_cross_entropy
        rng = np.random.RandomState(3)
        hid = jnp.asarray(rng.randn(2, 256, 32) * 0.4, jnp.bfloat16)
        W = jnp.asarray(rng.randn(97, 32) * 0.4, jnp.bfloat16)
        lab = jnp.asarray(rng.randint(0, 97, (2, 256)), jnp.int32)
        got = linear_softmax_cross_entropy(hid, W, lab)
        want = self._ref(hid.astype(jnp.float32),
                         W.astype(jnp.float32), lab)
        assert bool(jnp.isfinite(got))
        assert abs(float(got - want)) < 5e-2
        g = jax.grad(lambda h: linear_softmax_cross_entropy(h, W, lab))(hid)
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all())
