"""Real-hardware kernel tests.

The suite's conftest forces an 8-device CPU mesh in-process, which routes the
Pallas kernels through interpret mode — so nothing in the main suite proves
the kernels lower through Mosaic.  Each test here spawns ONE fresh subprocess
(default platform = whatever the machine has): the pytest parent is pinned to
the CPU by conftest.py and never touches the chip, so one child at a time
owns it.  Without a TPU every test skips, after at most one cached probe.

Run on the chip with ``python -m pytest tests/test_tpu_hw.py`` (no
``JAX_PLATFORMS`` in the environment).

The tests at the end need no chip: they pin what keeps the chip free for the
one process that should own it (package imports initialise no backend), that
``chip_smoke.py`` cannot pass without one, and — compiled for a v5e ahead of
time, from whatever machine has libtpu — that the serving step of ``gpt3-xl``
updates its KV pool in place.
"""
import functools
import json
import os
import pathlib
import subprocess
import sys

import pytest

_PROBE = "import jax; print(jax.devices()[0].platform)"

# every child places the compile cache by the program's own rule
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache), so TPU
# compiles persist from one child — and one run — to the next
_PREAMBLE = r"""
import numpy as np, jax, jax.numpy as jnp
assert jax.devices()[0].platform == "tpu", jax.devices()
from paddle_tpu.observability.compilecache import enable_persistent_cache
enable_persistent_cache()
"""


def _sub_env() -> dict:
    # keep the parent env intact except for what conftest.py pinned for the
    # CPU session; just make the repo importable
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    return env


@functools.lru_cache(maxsize=1)
def _tpu_available() -> bool:
    # lazy (called from inside the tests, not at collection), cached, and
    # free when the run pinned the CPU itself — tier-1 pays nothing here
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return False
    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE], env=_sub_env(),
            capture_output=True, text=True, timeout=120)
    except subprocess.TimeoutExpired:
        return False
    return out.returncode == 0 and out.stdout.strip().endswith("tpu")


def _require_tpu() -> None:
    if not _tpu_available():
        pytest.skip("no TPU attached")

_FLASH_SCRIPT = r"""
# the XLA reference otherwise runs fp32 matmuls via reduced-precision bf16
# passes on TPU, while the Pallas kernel's fp32 dots are exact
jax.config.update("jax_default_matmul_precision", "highest")
from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.nn import functional as F

rng = np.random.RandomState(0)
for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
    q = jnp.asarray(rng.randn(2, 4, 256, 64), dtype)
    k = jnp.asarray(rng.randn(2, 4, 256, 64), dtype)
    v = jnp.asarray(rng.randn(2, 4, 256, 64), dtype)
    for causal in (True, False):
        out = flash_attention(q, k, v, causal=causal)
        ref = F.scaled_dot_product_attention(
            q, k, v, is_causal=causal, dropout_p=0.0, training=False)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err <= tol, (dtype, causal, err)

        # backward compiles dominate wall-clock: check grads for one causal
        # setting per dtype (fwd numerics already cover both)
        if causal != (dtype is jnp.float32):
            continue

        def lf(q, k, v, _c=causal):
            return jnp.sum(flash_attention(q, k, v, causal=_c)
                           .astype(jnp.float32) ** 2)
        def lr(q, k, v, _c=causal):
            return jnp.sum(F.scaled_dot_product_attention(
                q, k, v, is_causal=_c, dropout_p=0.0, training=False)
                .astype(jnp.float32) ** 2)
        g = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            gerr = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                         - b.astype(jnp.float32))))
            scale = max(1.0, float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
            # grads flow through the recompute-based backward kernels: one
            # extra rounding step vs forward, so give them 5x headroom
            assert gerr / scale <= 5 * tol, (dtype, causal, gerr, scale)
print("flash-hw-ok")
"""

_TRAIN_SCRIPT = r"""
import paddle_tpu as pt
from paddle_tpu.framework import random as fw_random
from paddle_tpu.models import GPTForCausalLM, gpt_tiny

pt.seed(0)
model = GPTForCausalLM(gpt_tiny(max_position_embeddings=256))
model.train()
params = model.state_dict()
opt = pt.optimizer.AdamW(learning_rate=1e-3)
state = opt.init(params)
rng = np.random.RandomState(0)
ids = jnp.asarray(rng.randint(0, 1024, (2, 256)), jnp.int32)

def step(params, state, key):
    def loss_fn(p):
        with fw_random.key_scope(key):
            loss, _ = model.apply(p, ids, labels=ids)
        return loss
    loss, grads = jax.value_and_grad(loss_fn)(params)
    p2, s2 = opt.apply_gradients(grads, params, state)
    return loss, p2, s2

jitted = jax.jit(step)
key = jax.random.key(0)
losses = []
for i in range(5):
    loss, params, state = jitted(params, state, jax.random.fold_in(key, i))
    losses.append(float(loss))
assert all(np.isfinite(l) for l in losses), losses
assert losses[-1] < losses[0], losses
print("train-hw-ok", losses[0], losses[-1])
"""


def _run(script: str, tag: str, timeout: int = 560) -> None:
    out = subprocess.run([sys.executable, "-c", _PREAMBLE + script],
                         env=_sub_env(), capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert tag in out.stdout, out.stdout


def test_flash_attention_on_tpu():
    """The Pallas kernel must lower via Mosaic and match XLA numerics on
    real hardware (regression: an lse BlockSpec Mosaic refused)."""
    _require_tpu()
    _run(_FLASH_SCRIPT, "flash-hw-ok")


def test_gpt_train_step_on_tpu():
    """Five optimizer steps of the flagship model on the chip: finite and
    decreasing loss through the auto-routed fused-attention path."""
    _require_tpu()
    _run(_TRAIN_SCRIPT, "train-hw-ok")


_FLASH_NEW_PATHS_SCRIPT = r"""
jax.config.update("jax_default_matmul_precision", "highest")
from paddle_tpu.ops.flash_attention import (flash_attention,
                                            flash_attention_kvcache)
from paddle_tpu.nn import functional as F

rng = np.random.RandomState(0)

# 1. in-kernel dropout lowers via Mosaic: deterministic per seed, disjoint
#    across seeds, mean preserved within tolerance
q = jnp.asarray(rng.randn(1, 4, 256, 64) * 0.5, jnp.float32)
k = jnp.asarray(rng.randn(1, 4, 256, 64) * 0.5, jnp.float32)
v = jnp.asarray(rng.randn(1, 4, 256, 64) * 0.5, jnp.float32)
a = flash_attention(q, k, v, dropout_p=0.3, seed=7)
b = flash_attention(q, k, v, dropout_p=0.3, seed=7)
c = flash_attention(q, k, v, dropout_p=0.3, seed=8)
assert bool(jnp.array_equal(a, b))
assert not bool(jnp.allclose(a, c))
g = jax.grad(lambda q_: jnp.sum(flash_attention(
    q_, k, v, dropout_p=0.3, seed=7) ** 2))(q)
assert bool(jnp.isfinite(g).all())

# 2. ragged auto-padding on hardware
qr = jnp.asarray(rng.randn(1, 2, 100, 64) * 0.5, jnp.float32)
kr = jnp.asarray(rng.randn(1, 2, 200, 64) * 0.5, jnp.float32)
vr = jnp.asarray(rng.randn(1, 2, 200, 64) * 0.5, jnp.float32)
out = flash_attention(qr, kr, vr, causal=True)
ref = F.scaled_dot_product_attention(qr, kr, vr, is_causal=True,
                                     dropout_p=0.0, training=False)
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 2e-4, err

# 3. kv-cache decode kernel with a traced length
kc = jnp.asarray(rng.randn(1, 2, 256, 64) * 0.5, jnp.float32)
vc = jnp.asarray(rng.randn(1, 2, 256, 64) * 0.5, jnp.float32)
qd = jnp.asarray(rng.randn(1, 2, 1, 64) * 0.5, jnp.float32)
dec = jax.jit(lambda qq, n: flash_attention_kvcache(qq, kc, vc, n))
for used in (64, 131, 256):
    got = dec(qd, jnp.asarray(used, jnp.int32))
    want = F.scaled_dot_product_attention(
        qd, kc[:, :, :used], vc[:, :, :used], is_causal=False,
        dropout_p=0.0, training=False)
    derr = float(jnp.max(jnp.abs(got - want)))
    assert derr < 2e-4, (used, derr)
print("flash-newpaths-hw-ok")
"""


def test_flash_new_paths_on_tpu():
    """Round-5 kernel additions (in-kernel dropout, ragged auto-pad,
    kv-cache decode) must lower via Mosaic on real hardware — the CPU mesh
    only exercises interpret mode."""
    _require_tpu()
    _run(_FLASH_NEW_PATHS_SCRIPT, "flash-newpaths-hw-ok")


_FLASH_MATRIX_SCRIPT = r"""
jax.config.update("jax_default_matmul_precision", "highest")
from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.nn import functional as F

# the four flash kernels (fwd, dkdv, dq; decode is covered above) at the
# head widths of gpt-125M (64) and gpt-1.3B (128), both dtypes, both
# sides of the block-size switch at S=4096
rng = np.random.RandomState(0)
for d in (64, 128):
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        for S in (2048, 8192):
            q, k, v = (jnp.asarray(rng.randn(1, 2, S, d) * 0.5, dtype)
                       for _ in range(3))

            def lf(q, k, v):
                o = flash_attention(q, k, v, causal=True)
                return jnp.sum(o.astype(jnp.float32) ** 2), o
            def lr(q, k, v):
                o = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, dropout_p=0.0, training=False)
                return jnp.sum(o.astype(jnp.float32) ** 2), o
            (_, o), g = jax.jit(jax.value_and_grad(
                lf, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            (_, orf), gr = jax.jit(jax.value_and_grad(
                lr, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                        - orf.astype(jnp.float32))))
            assert err <= tol, ("fwd", d, dtype, S, err)
            for a, b in zip(g, gr):
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                scale = max(1.0, float(jnp.max(jnp.abs(b))))
                gerr = float(jnp.max(jnp.abs(a - b))) / scale
                # recompute-based backward: one more rounding than fwd
                assert gerr <= 5 * tol, ("bwd", d, dtype, S, gerr)
            print("flash", d, jnp.dtype(dtype).name, S, err, flush=True)
print("flash-matrix-hw-ok")
"""


def test_flash_matrix_on_tpu():
    """All flash train kernels, head_dim 64/128 x f32/bf16 x S 2048/8192,
    forward and backward against XLA attention."""
    _require_tpu()
    _run(_FLASH_MATRIX_SCRIPT, "flash-matrix-hw-ok", timeout=1500)


_PAGED_SCRIPT = r"""
from paddle_tpu.inference.paged_attention import (page_token_shape,
                                                  paged_attention_pallas,
                                                  paged_attention_reference)

# the decode kernel at the head shapes of gpt-125M and gpt-1.3B, default
# block size, token-major pages (blocks, block_size, heads, head_dim),
# ragged lengths: an empty row, one token, a non-multiple of
# the block, an exact multiple, and a full table
bs, nb, T = 16, 96, 12
lens = jnp.asarray([0, 1, 37, 64, T * bs, 5, 100, 17], jnp.int32)
B = lens.shape[0]
rng = np.random.RandomState(0)
for h, d in ((12, 64), (16, 128)):
    # f32: exact products and f32 sums on both sides, only the summation
    # order differs.  bf16: the same arithmetic on bf16 pages, then the
    # output is cast to bf16 — two f32 results that straddle a rounding
    # boundary land one bf16 ulp apart, 2**-6 for the largest |x| < 4 here.
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2.0 ** -6)):
        q = jnp.asarray(rng.randn(B, h, d), dtype)
        # the pool as a model allocates it here: whole tiles, zeros in
        # the heads and dims that 12 x 64 does not fill
        hp, dp = page_token_shape(h, d, dtype)
        assert hp % 8 == 0 and dp % 128 == 0, (hp, dp)
        room = [(0, 0), (0, 0), (0, hp - h), (0, dp - d)]
        kp = jnp.asarray(np.pad(rng.randn(nb, bs, h, d), room), dtype)
        vp = jnp.asarray(np.pad(rng.randn(nb, bs, h, d), room), dtype)
        tbl = jnp.asarray(rng.randint(0, nb, (B, T)), jnp.int32)
        run = jax.jit(paged_attention_pallas, static_argnames=("block_size",))
        assert "tpu_custom_call" in run.lower(
            q, kp, vp, tbl, lens, block_size=bs).compile().as_text()
        out = run(q, kp, vp, tbl, lens, block_size=bs).astype(jnp.float32)
        ref = paged_attention_reference(q, kp, vp, tbl, lens, bs).astype(
            jnp.float32)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert bool(jnp.isfinite(out).all()) and err <= tol, (h, d, dtype, err)
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0      # the empty row
        print("paged", h, d, jnp.dtype(dtype).name, err, flush=True)

# ISSUE 29: the serving cells' table (2048 positions wide) at every block
# size: a full-table row, a wave (128 tokens) to the token and one more,
# empty rows between the live ones, and a NaN page under every table
# entry past a row's length, which the kernel must never read
h, d, nb = 16, 128, 160
for bs in (4, 8, 16, 32):
    T = 2048 // bs
    lens = np.asarray([0, 2048, 0, 128, 129, 0, 1, 700], np.int32)
    B = lens.shape[0]
    q = jnp.asarray(rng.randn(B, h, d), jnp.bfloat16)
    kp = rng.randn(nb, bs, h, d).astype(np.float32)
    vp = rng.randn(nb, bs, h, d).astype(np.float32)
    kp[nb - 1] = vp[nb - 1] = np.nan
    tbl = rng.randint(0, nb - 1, (B, T)).astype(np.int32)
    clean = tbl.copy()
    for b in range(B):
        tbl[b, -(-lens[b] // bs):] = nb - 1
    kp, vp = jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16)
    out = run(q, kp, vp, jnp.asarray(tbl), jnp.asarray(lens),
              block_size=bs).astype(jnp.float32)
    ref = paged_attention_reference(
        q, kp, vp, jnp.asarray(clean), jnp.asarray(lens), bs).astype(
        jnp.float32)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert bool(jnp.isfinite(out).all()) and err <= 2.0 ** -6, (bs, err)
    assert not np.asarray(out)[lens == 0].any()
    print("paged-table", bs, err, flush=True)

# a row whose own pages went NaN (three waves: both halves of the double
# buffer) keeps it to itself: the shorter rows after it fill part of a
# wave's buffer, and the tail of their last page is NaN as well
bs, lens = 16, np.asarray([5, 300, 1, 17, 0, 129], np.int32)
B, used = lens.shape[0], -(-lens // bs)
kp = rng.randn(nb, bs, h, d).astype(np.float32)
vp = rng.randn(nb, bs, h, d).astype(np.float32)
tbl, ids = np.zeros((B, 2048 // bs), np.int32), rng.permutation(nb)
for b in range(B):
    tbl[b, :used[b]], ids = ids[:used[b]], ids[used[b]:]
q = jnp.asarray(rng.randn(B, h, d), jnp.bfloat16)
args = (jnp.asarray(tbl), jnp.asarray(lens))
ref = np.asarray(paged_attention_reference(
    q, jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16), *args,
    bs).astype(jnp.float32))
for b in range(B):
    if b == 1:
        kp[tbl[b, :used[b]]] = vp[tbl[b, :used[b]]] = np.nan
    elif lens[b] % bs:
        kp[tbl[b, used[b] - 1], lens[b] % bs:] = np.nan
        vp[tbl[b, used[b] - 1], lens[b] % bs:] = np.nan
out = np.asarray(run(q, jnp.asarray(kp, jnp.bfloat16),
                     jnp.asarray(vp, jnp.bfloat16), *args,
                     block_size=bs).astype(jnp.float32))
well = np.arange(B) != 1
assert np.isnan(out[1]).all() and np.isfinite(out[well]).all()
assert np.abs(out[well] - ref[well]).max() <= 2.0 ** -6
print("paged-nan-row-ok", flush=True)
print("paged-hw-ok")
"""


def test_paged_attention_on_tpu():
    """The paged decode kernel lowers via Mosaic and matches the gather
    reference on ragged tables."""
    _require_tpu()
    _run(_PAGED_SCRIPT, "paged-hw-ok")


_FUSED_BLOCK_SCRIPT = r"""
import os
# the reference route's f32 GEMMs otherwise run as bf16 passes on a TPU,
# while the kernels' f32 dots are exact
jax.config.update("jax_default_matmul_precision", "highest")
from paddle_tpu.ops.fused_block import (fused_ffn_block, fused_linear_residual,
                                        fused_ln_linear)

# K1-K3 at the widths of gpt-125M and gpt-1.3B (ffn = 4x), Mosaic route
# against the jnp reference route.  The route is read when a call traces.
def both(fn, *args, **kw):
    outs = []
    for route in ("pallas", "reference"):
        os.environ["PTPU_FUSED_BLOCK"] = route
        jitted = jax.jit(lambda *a: fn(*a, **kw))
        text = jitted.lower(*args).compile().as_text()
        assert ("tpu_custom_call" in text) == (route == "pallas"), route
        outs.append(jitted(*args).astype(jnp.float32))
    return outs

rng = np.random.RandomState(0)
n = 1024
for hid in (768, 2048):
    # bf16 GEMMs with f32 accumulation on both routes; the reference rounds
    # the GEMM to bf16 before the bias/residual, the kernel after — a bf16
    # ulp (2**-8 relative) of the largest value
    for dtype, rel in ((jnp.bfloat16, 2.0 ** -7), (jnp.float32, 1e-4)):
        x = jnp.asarray(rng.randn(n, hid), dtype)
        w = lambda i, o: jnp.asarray(rng.randn(i, o) * i ** -0.5, dtype)
        b = lambda o: jnp.asarray(rng.randn(o) * 0.1, jnp.float32)
        g, beta = b(hid) + 1.0, b(hid)
        cases = {
            "K1": both(fused_ln_linear, x, w(hid, 3 * hid), b(3 * hid),
                       g, beta),
            "K2": both(fused_linear_residual, x, w(hid, hid), b(hid), x,
                       dropout_p=0.1, seed=7),
            "K3": both(fused_ffn_block, x, w(hid, 4 * hid), b(4 * hid),
                       w(4 * hid, hid), b(hid), g, beta, dropout1=0.1,
                       dropout2=0.1, seed=7),
        }
        for name, (got, want) in cases.items():
            scale = float(jnp.max(jnp.abs(want)))
            err = float(jnp.max(jnp.abs(got - want))) / scale
            assert bool(jnp.isfinite(got).all()) and err <= rel, (
                name, hid, dtype, err)
            print(name, hid, jnp.dtype(dtype).name, err, flush=True)
print("fused-block-hw-ok")
"""


def test_fused_block_kernels_on_tpu():
    """Fused-block K1 (LN+GEMM), K2 (GEMM+dropout+residual) and K3 (the
    FFN half) lower via Mosaic at hidden 768 and 2048 and match their
    reference route."""
    _require_tpu()
    _run(_FUSED_BLOCK_SCRIPT, "fused-block-hw-ok", timeout=900)


# ---------------------------------------------------------------------------
# no chip needed: who may hold the chip, and what counts as having run on it
# ---------------------------------------------------------------------------
REPO = pathlib.Path(__file__).resolve().parent.parent

_LAUNCH_AHEAD_SCRIPT = r"""
# ISSUE 36: a launch issued while the previous program runs does not wait
# for the device.  The benchmark's own gpt3-xl engine (its builder, its
# configuration, the backlog cell's engine arguments, so the programs are
# the ones the cells compile), 32 rows of 512-token prompts: a decode unit
# keeps the device busy for several milliseconds, the bare jitted call
# takes about one.
import json
from perfbench.harness.manifest import Manifest
m = Manifest(None, [])
config = m.load_config("gpt3-xl")
traffic = m.load_traffic("doc-backlog")
system = m.load_entry(config["entry"])(config, 36)
eng = system.build_for_serving(traffic["engine"])
rng = np.random.default_rng(36)
vocab = system.shape["vocab"]
for _ in range(32):
    eng.submit(rng.integers(0, vocab, 500).tolist(), max_new_tokens=96)
for _ in range(32 + 8):                     # the prefills, a few decodes
    eng.step()

def phases():
    ph = eng.stats()["phases"]
    return {k: (ph[k]["count"], ph[k]["total_ms"])
            for k in ("dispatch", "device_wait", "h2d", "tables")}

a0, p0 = dict(eng.stats()["ahead"]), phases()
calls = 64
for _ in range(calls):
    assert len(eng.step()) == 32
a1, p1 = eng.stats()["ahead"], phases()
mean = {k: (p1[k][1] - p0[k][1]) / (p1[k][0] - p0[k][0]) for k in p0}
launched = a1["units_launched"] - a0["units_launched"]
ahead = a1["units_ahead"] - a0["units_ahead"]
print("launch-ahead", json.dumps({"mean_ms": mean, "launched": launched,
                                  "ahead": ahead}))
assert launched == ahead == calls, (launched, ahead)
# every one of these launches found the previous program still running
# (the landing after it waited for that program longer than the launch
# took), and returned in about the bare call's time
assert mean["device_wait"] > 2.0 * mean["dispatch"], mean
assert mean["dispatch"] < 3.0, mean
eng.stop()
print("launch-ahead-ok")
"""


def test_a_launch_ahead_does_not_wait_for_the_device_on_tpu():
    _require_tpu()
    _run(_LAUNCH_AHEAD_SCRIPT, "launch-ahead-ok", timeout=900)


_IMPORTS = r"""
import paddle_tpu, paddle_tpu.distributed.launch, paddle_tpu.inference.fleet
import paddle_tpu.bench
from jax._src import xla_bridge
assert not xla_bridge._backends, sorted(xla_bridge._backends)
print("no-backend-ok")
"""


def _python(args, **env):
    return subprocess.run([sys.executable, *args], cwd=str(REPO),
                          env=dict(_sub_env(), **env), capture_output=True,
                          text=True, timeout=300)


def test_imports_initialise_no_backend():
    """A parent that has touched a backend holds the chip and its child
    fails or hangs — so importing the package must touch none."""
    out = _python(["-c", _IMPORTS], JAX_PLATFORMS="cpu")
    assert out.returncode == 0 and "no-backend-ok" in out.stdout, out.stderr


def test_chip_smoke_fails_without_a_tpu_and_rehearses_with_tiny():
    out = _python(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip().endswith("}")     # no result line

    out = _python(["chip_smoke.py", "--tiny"], JAX_PLATFORMS="cpu",
                  JAX_ENABLE_COMPILATION_CACHE="0")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "REHEARSAL" in out.stdout
    *_, summary_line, last = out.stdout.strip().splitlines()
    # the driver's contract: exactly these keys on the last line
    verdict = json.loads(last)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["count"], int)
    assert summary_line.startswith("summary: ")
    summary = json.loads(summary_line[len("summary: "):])
    assert summary["rehearsal"]
    assert summary["claim"] is None and summary_line.endswith('"claim": null}')
    # a rehearsal can never print a time, a rate or any other device number
    text = json.dumps(summary)
    assert not any(k in text for k in ("_ms", "_s\"", "tpot", "ttft"))
    assert summary["serve"]["positions_compared"] > 0
    assert summary["serve"]["leaked_blocks"] == 0


# ---------------------------------------------------------------------------
# no chip needed: the serving step of gpt3-xl, compiled for a v5e ahead of time
# ---------------------------------------------------------------------------
_AOT_SERVE_SCRIPT = r"""
import importlib, json, re
import numpy as np, jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
except Exception as e:                 # no libtpu here, or its lock is held
    print("aot-skip:", repr(e)[:300])
    raise SystemExit(0)
print("aot-topology-ok", flush=True)

# off the chip the Pallas entry points default to interpret mode
import paddle_tpu.ops.flash_attention as fa
fa._interpret = lambda: False
importlib.import_module(
    "paddle_tpu.inference.paged_attention")._interpret = lambda: False
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference.paged_attention import (page_token_shape,
                                                  paged_attention_pallas)
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability.registry import MetricsRegistry

sh = SingleDeviceSharding(dev)
S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
# the paged kernel alone (ISSUE 29): both head shapes, block sizes 4-32,
# the cells' table of 2048 positions and one no wave divides
for heads, dim in ((12, 64), (16, 128)):
    for bs, width in ((4, 512), (8, 256), (16, 128), (32, 64), (16, 13)):
        page = (64, bs) + page_token_shape(heads, dim, jnp.bfloat16)
        text = jax.jit(paged_attention_pallas,
                       static_argnames=("block_size",)).lower(
            S((128, heads, dim), jnp.bfloat16), S(page, jnp.bfloat16),
            S(page, jnp.bfloat16), S((128, width), jnp.int32),
            S((128,), jnp.int32), block_size=bs).compile().as_text()
        print("aot-kernel", json.dumps({
            "name": "%dx%d-bs%d-w%d" % (heads, dim, bs, width),
            "paged_decode_calls": len(re.findall(
                r"custom_call_target=\"tpu_custom_call\"", text))}),
            flush=True)

# perfbench/configs/gpt3-xl.json and the engine of both serving cells,
# then GPT-125M's head shape, whose pool is kept in whole tiles
abstract = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
for tag, LAYERS, HEADS, DIM, BLOCKS, BS, SEQS, LEN in (
        ("", 24, 16, 128, 1856, 16, 128, 2048),
        ("_125m", 12, 12, 64, 1024, 16, 8, 2048)):
    model = GPTForCausalLM(GPTConfig(
        hidden_size=HEADS * DIM, num_layers=LAYERS, num_heads=HEADS,
        ffn_hidden_size=4 * HEADS * DIM, max_position_embeddings=LEN,
        vocab_size=50304, hidden_dropout=0.0, attention_dropout=0.0,
        dtype="bfloat16"))
    model.astype("bfloat16")
    # the pool is only ever abstract here: the engine's own stays tiny
    eng = ServingEngine(model, max_seqs=SEQS, max_model_len=LEN,
                        kv_block_size=BS, num_kv_blocks=8,
                        registry=MetricsRegistry())
    pool = (BLOCKS,) + eng.cache.pages[0][0].shape[1:]
    pages = [(S(pool, jnp.bfloat16), S(pool, jnp.bfloat16))] * LAYERS
    width = LEN // BS
    for name, rows, chunk in (("serve_decode", SEQS, 1),
                              ("serve_prefill_b512", 1, 512)):
        # ids, positions, last index, tables, lengths, slots, where each
        # row's id comes from, the step's number: one buffer; and the
        # previous program's tokens
        packed = S((2 * rows * chunk + 3 * rows + 2 + rows * width,),
                   jnp.int32)
        c = eng._build_step_fn().lower(
            abstract(eng._params), packed, pages,
            abstract(jax.random.PRNGKey(0)), S((SEQS,), jnp.int32),
            rows=rows, chunk=chunk).compile()
        text, ma = c.as_text(), c.memory_analysis()
        # an operation whose result is a whole page array, other than the
        # in-place write and what it is fused into
        whole = r"= bf16\[%d,%d,\d+,\d+\]\S* (copy|copy-start|pad)\(" % (
            BLOCKS, BS)
        header = text.split("input_output_alias={", 1)[1].split(
            "entry_computation_layout", 1)[0]
        print("aot-program", json.dumps({
            "name": name + tag, "pool": pool,
            "pool_copies": len(re.findall(whole, text)),
            "aliased": len(re.findall(r"\(\d+, \{\}", header)),
            "alias_bytes": ma.alias_size_in_bytes,
            "pool_bytes": 2 * LAYERS * int(np.prod(pool)) * 2,
            "beside_args_bytes": ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes,
            "paged_decode_calls": len(re.findall(
                r"custom_call_target=\"tpu_custom_call\"[^\n]*paged_decode|"
                r"paged_decode[^\n]*custom_call_target=\"tpu_custom_call\"",
                text))}), flush=True)
print("aot-serve-ok")
"""


@functools.lru_cache(maxsize=1)
def _aot_serve_programs():
    """One child compiles the paged kernel's shapes and both programs
    (building the 1.3B model on the CPU is most of its minute); None when
    libtpu cannot give a topology."""
    out = subprocess.run(
        [sys.executable, "-c", _AOT_SERVE_SCRIPT], cwd=str(REPO),
        env=dict(_sub_env(), JAX_PLATFORMS="cpu", PTPU_PAGED_KERNEL="pallas",
                 JAX_ENABLE_COMPILATION_CACHE="0"),
        capture_output=True, text=True, timeout=1200)
    if "aot-topology-ok" not in out.stdout:
        return None, (out.stdout + out.stderr)[-600:]
    assert out.returncode == 0 and "aot-serve-ok" in out.stdout, \
        f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    rows = [json.loads(line.split(" ", 1)[1])
            for line in out.stdout.splitlines()
            if line.startswith(("aot-program ", "aot-kernel "))]
    return {r["name"]: r for r in rows}, ""


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_b512",
                                     "serve_decode_125m",
                                     "serve_prefill_b512_125m"])
def test_serving_step_compiled_for_v5e_updates_the_pool_in_place(program):
    """ISSUE 27: in the real program of ``gpt3-xl`` (24 layers, 1,856
    blocks, 128 rows) no operation copies a pool-shaped array, every page
    array's output aliases its input, and the plan holds one pool.  The
    same at GPT-125M's 12 x 64 heads, whose pool is kept as 16 x 128
    tiles: no copy between XLA's layout and the kernel's, and no pad."""
    programs, why = _aot_serve_programs()
    if programs is None:
        pytest.skip(f"no v5e topology from libtpu here: {why}")
    p = programs[program]
    layers = 12 if program.endswith("_125m") else 24
    assert p["pool"][2:] == [16, 128], p
    assert p["pool_copies"] == 0, p
    assert p["aliased"] == 2 * layers, p
    assert p["alias_bytes"] == p["pool_bytes"], p
    # what the program needs beside its arguments (logits, activations) is
    # nowhere near a second pool
    assert p["beside_args_bytes"] < p["pool_bytes"] // 10, p
    assert (p["paged_decode_calls"] == layers) == ("decode" in program), p


@pytest.mark.parametrize("heads,dim", [(12, 64), (16, 128)])
@pytest.mark.parametrize("bs,width", [(4, 512), (8, 256), (16, 128),
                                      (32, 64), (16, 13)])
def test_paged_kernel_compiled_for_v5e(heads, dim, bs, width):
    """ISSUE 29: the kernel whose loop follows the live pages lowers
    through Mosaic at both GPT head shapes, block sizes 4 to 32 and a
    table that no wave divides, as one custom call."""
    programs, why = _aot_serve_programs()
    if programs is None:
        pytest.skip(f"no v5e topology from libtpu here: {why}")
    p = programs["%dx%d-bs%d-w%d" % (heads, dim, bs, width)]
    assert p["paged_decode_calls"] == 1, p


# ---------------------------------------------------------------------------
# no chip needed: the serving step of deepseek-v2-ep4-l5 (ISSUE 28), compiled
# for a v5e ahead of time: the latent kernel and the grouped products lower
# through Mosaic at the published widths, and the latent pool stays in place
# ---------------------------------------------------------------------------
_AOT_DEEPSEEK_SCRIPT = r"""
import json, re
import numpy as np, jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
except Exception as e:                 # no libtpu here, or its lock is held
    print("aot-skip:", repr(e)[:300])
    raise SystemExit(0)
print("aot-topology-ok", flush=True)

# a 5-billion-parameter model is only ever abstract here
from paddle_tpu.nn import initializer as I
I.NormalInDtype.__call__ = lambda self, key, shape, dtype=jnp.float32: \
    jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
# off the chip the kernels' entry points take their CPU routes
import paddle_tpu.inference.latent_attention as la
import paddle_tpu.ops.grouped_matmul as gm
la._interpret = gm._interpret = lambda: False
jax.default_backend = lambda: "tpu"
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                           DeepseekV2ForCausalLM)
from paddle_tpu.observability.registry import MetricsRegistry

# perfbench/configs/deepseek-v2-ep4-l5.json + traffic/reason-backlog.json
traffic = json.load(open("perfbench/traffic/reason-backlog.json"))["engine"]
SEQS, LEN = traffic["max_seqs"], traffic["max_model_len"]
BS, BLOCKS = traffic["kv_block_size"], traffic["num_kv_blocks"]
model = DeepseekV2ForCausalLM(DeepseekV2Config(
    vocab_size=25600, num_layers=5, dtype="bfloat16", ep_degree=4,
    ep_rank=0))
eng = ServingEngine(model, max_seqs=SEQS, max_model_len=LEN,
                    kv_block_size=BS, num_kv_blocks=8,
                    registry=MetricsRegistry())
sh = SingleDeviceSharding(dev)
S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
abstract = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
pool = (BLOCKS, BS, 640)
pages = [(S(pool, jnp.bfloat16),)] * 5
params = abstract(eng._params)
n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
for name, rows, chunk in (("serve_decode", SEQS, 1),
                          ("serve_prefill_b1024", 1, 1024)):
    # ids, positions, last index, tables, lengths, slots, the step's
    # number: one buffer
    packed = S((2 * rows * chunk + 3 * rows + 2 + rows * (LEN // BS),),
               jnp.int32)
    c = eng._build_step_fn().lower(
        params, packed, pages, abstract(jax.random.PRNGKey(0)),
        S((SEQS,), jnp.int32), rows=rows, chunk=chunk).compile()
    text, ma = c.as_text(), c.memory_analysis()
    header = text.split("input_output_alias={", 1)[1].split(
        "entry_computation_layout", 1)[0]
    calls = lambda k: len(re.findall(
        r"custom_call_target=\"tpu_custom_call\"[^\n]*" + k + "|" + k
        + r"[^\n]*custom_call_target=\"tpu_custom_call\"", text))
    print("aot-program", json.dumps({
        "name": name, "n_params": n_params,
        "pool_copies": len(re.findall(
            r"= bf16\[%d,%d,%d\]\S* copy(-start)?\(" % pool, text)),
        "aliased": len(re.findall(r"\(\d+, \{\}", header)),
        "alias_bytes": ma.alias_size_in_bytes,
        "pool_bytes": 5 * int(np.prod(pool)) * 2,
        "plan_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes,
        "latent_calls": calls("mla_latent_attn"),
        "grouped_calls": calls("moe_grouped")}), flush=True)
print("aot-serve-ok")
"""


@functools.lru_cache(maxsize=1)
def _aot_deepseek_programs():
    out = subprocess.run(
        [sys.executable, "-c", _AOT_DEEPSEEK_SCRIPT], cwd=str(REPO),
        env=dict(_sub_env(), JAX_PLATFORMS="cpu",
                 JAX_ENABLE_COMPILATION_CACHE="0"),
        capture_output=True, text=True, timeout=1200)
    if "aot-topology-ok" not in out.stdout:
        return None, (out.stdout + out.stderr)[-600:]
    assert out.returncode == 0 and "aot-serve-ok" in out.stdout, \
        f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    rows = [json.loads(line.split(" ", 1)[1])
            for line in out.stdout.splitlines()
            if line.startswith("aot-program ")]
    return {r["name"]: r for r in rows}, ""


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_b1024"])
def test_deepseek_v2_step_compiled_for_v5e_fits_and_stays_in_place(program):
    """ISSUE 28: the cell's own step programs (published widths, 40 held
    experts, 256 rows, the traffic file's pool) compile for a v5e: the
    kernels lower, the 5 latent page arrays alias their inputs with no
    pool-shaped copy, and weights + pool + temporaries fit one chip."""
    programs, why = _aot_deepseek_programs()
    if programs is None:
        pytest.skip(f"no v5e topology from libtpu here: {why}")
    p = programs[program]
    assert p["n_params"] == 5_163_975_680, p          # 10.33 GB in bf16
    assert p["pool_copies"] == 0, p
    assert p["aliased"] == 5 and p["alias_bytes"] == p["pool_bytes"], p
    assert p["plan_bytes"] < 15.75e9, p
    decode = program == "serve_decode"
    assert p["latent_calls"] == (5 if decode else 0), p
    assert p["grouped_calls"] == 8, p                 # 4 layers x (up, down)


# ---------------------------------------------------------------------------
# no chip needed: the decode step of glm-5-ep16-l5 (ISSUE 32), compiled for a
# v5e ahead of time: the index and sparse-attention kernels lower through
# Mosaic at the published widths, and both page arrays of a layer stay in place
# ---------------------------------------------------------------------------
_AOT_GLM5_SCRIPT = _AOT_DEEPSEEK_SCRIPT.split("from paddle_tpu.inference import ServingEngine")[0] + r"""
import paddle_tpu.inference.sparse_attention as sa
sa._interpret = lambda: False
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models.glm5 import Glm5Config, Glm5ForCausalLM
from paddle_tpu.observability.registry import MetricsRegistry

# perfbench/configs/glm-5-ep16-l5.json + traffic/longctx-backlog.json
traffic = json.load(open("perfbench/traffic/longctx-backlog.json"))["engine"]
SEQS, LEN = traffic["max_seqs"], traffic["max_model_len"]
BS, BLOCKS = traffic["kv_block_size"], traffic["num_kv_blocks"]
model = Glm5ForCausalLM(Glm5Config(
    vocab_size=19360, num_layers=5, first_k_dense_replace=1,
    dtype="bfloat16", ep_degree=16, ep_rank=0))
eng = ServingEngine(model, max_seqs=SEQS, max_model_len=LEN,
                    kv_block_size=BS, num_kv_blocks=8,
                    registry=MetricsRegistry())
sh = SingleDeviceSharding(dev)
S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
abstract = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
pages = [(S((BLOCKS, BS, 640), jnp.bfloat16),
          S((BLOCKS, BS, 128), jnp.bfloat16))] * 5
params = abstract(eng._params)
packed = S((2 * SEQS + 3 * SEQS + 2 + SEQS * (LEN // BS),), jnp.int32)
c = eng._build_step_fn().lower(
    params, packed, pages, abstract(jax.random.PRNGKey(0)),
    S((SEQS,), jnp.int32), rows=SEQS, chunk=1).compile()
text, ma = c.as_text(), c.memory_analysis()
header = text.split("input_output_alias={", 1)[1].split(
    "entry_computation_layout", 1)[0]
calls = lambda k: len(re.findall(
    r"custom_call_target=\"tpu_custom_call\"[^\n]*" + k + "|" + k
    + r"[^\n]*custom_call_target=\"tpu_custom_call\"", text))
print("aot-program", json.dumps({
    "n_params": sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)),
    "pool_copies": len(re.findall(
        r"= bf16\[%d,%d,(640|128)\]\S* copy(-start)?\(" % (BLOCKS, BS),
        text)),
    "aliased": len(re.findall(r"\(\d+, \{\}", header)),
    "alias_bytes": ma.alias_size_in_bytes,
    "pool_bytes": 5 * BLOCKS * BS * (640 + 128) * 2,
    "plan_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes
    + ma.output_size_in_bytes - ma.alias_size_in_bytes,
    "index_calls": calls("dsa_index_scores"),
    "sparse_calls": calls("dsa_sparse_attn"),
    "latent_calls": calls("mla_latent_attn"),
    "grouped_calls": calls("moe_grouped")}), flush=True)
print("aot-serve-ok")
"""


def test_glm5_decode_step_compiled_for_v5e_selects_and_stays_in_place():
    """ISSUE 32: the cell's decode program (published widths, 16 held
    experts, 16 rows, the traffic file's pool) compiles for a v5e: both
    new kernels lower, a layer, and the dense latent kernel is not there;
    the 10 page arrays alias their inputs with no pool-shaped copy."""
    out = subprocess.run(
        [sys.executable, "-c", _AOT_GLM5_SCRIPT], cwd=str(REPO),
        env=dict(_sub_env(), JAX_PLATFORMS="cpu",
                 JAX_ENABLE_COMPILATION_CACHE="0"),
        capture_output=True, text=True, timeout=1200)
    if "aot-topology-ok" not in out.stdout:
        pytest.skip("no v5e topology from libtpu here: "
                    + (out.stdout + out.stderr)[-600:])
    assert out.returncode == 0 and "aot-serve-ok" in out.stdout, \
        f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    p = json.loads(next(line for line in out.stdout.splitlines()
                        if line.startswith("aot-program ")).split(" ", 1)[1])
    assert p["n_params"] == 3_909_632_768, p           # 7.82 GB in bf16
    assert p["pool_copies"] == 0, p
    assert p["aliased"] == 10 and p["alias_bytes"] == p["pool_bytes"], p
    assert p["plan_bytes"] < 15.75e9, p
    assert (p["index_calls"], p["sparse_calls"], p["latent_calls"]) \
        == (5, 5, 0), p
    assert p["grouped_calls"] == 8, p                  # 4 layers x (up, down)


# ---------------------------------------------------------------------------
# no chip needed: the step programs of mimo-v2-flash-ep16-l7 (ISSUE 35),
# compiled for a v5e ahead of time: both instantiations of the grouped-query
# kernel lower through Mosaic at the published widths, and the page arrays of
# both pools stay in place
# ---------------------------------------------------------------------------
_AOT_MIMO_SCRIPT = _AOT_DEEPSEEK_SCRIPT.split("from paddle_tpu.inference import ServingEngine")[0] + r"""
import paddle_tpu.inference.gqa_attention as ga
ga._interpret = lambda: False
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models.mimo_v2 import MimoV2Config, MimoV2ForCausalLM
from paddle_tpu.observability.registry import MetricsRegistry

# perfbench/configs/mimo-v2-flash-ep16-l7.json + traffic/mixedctx-backlog.json
traffic = json.load(open("perfbench/traffic/mixedctx-backlog.json"))["engine"]
SEQS, LEN = traffic["max_seqs"], traffic["max_model_len"]
BS, BLOCKS = traffic["kv_block_size"], traffic["num_kv_blocks"]
cfg = MimoV2Config(vocab_size=19072, num_layers=7,
                   hybrid_layer_pattern=(0, 1, 1, 1, 1, 1, 0),
                   moe_layer_freq=(0, 1, 1, 1, 1, 1, 1), dtype="bfloat16",
                   ep_degree=16, ep_rank=0)
model = MimoV2ForCausalLM(cfg)
eng = ServingEngine(model, max_seqs=SEQS, max_model_len=LEN,
                    kv_block_size=BS, num_kv_blocks={"full": 8, "window": 8},
                    registry=MetricsRegistry())
sh = SingleDeviceSharding(dev)
S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
abstract = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
pages, pool_bytes = [], 0
for i in range(7):
    blocks = BLOCKS["window" if cfg.is_window(i) else "full"]
    n = cfg.kv_heads(i)
    pages.append((S((blocks, BS, n * 192), jnp.bfloat16),
                  S((blocks, BS, n * 128), jnp.bfloat16)))
    pool_bytes += blocks * BS * n * 320 * 2
params = abstract(eng._params)
widths = eng.cache.table_widths(eng.sched.max_blocks_per_seq)
for name, rows, chunk in (("serve_decode", SEQS, 1),
                          ("serve_prefill_b2048", 1, 2048)):
    # ids, positions, last index, a table a kind, lengths, slots a kind,
    # the step's number: one buffer
    packed = S((rows * chunk + rows + 1 + rows * sum(widths) + rows
                + 2 * rows * chunk + rows + 1,), jnp.int32)
    c = eng._build_step_fn().lower(
        params, packed, pages, abstract(jax.random.PRNGKey(0)),
        S((SEQS,), jnp.int32), rows=rows, chunk=chunk).compile()
    text, ma = c.as_text(), c.memory_analysis()
    header = text.split("input_output_alias={", 1)[1].split(
        "entry_computation_layout", 1)[0]
    calls = lambda k: len(re.findall(
        r"custom_call_target=\"tpu_custom_call\"[^\n]*" + k + "|" + k
        + r"[^\n]*custom_call_target=\"tpu_custom_call\"", text))
    print("aot-program", json.dumps({
        "name": name, "widths": list(widths),
        "n_params": sum(int(np.prod(a.shape))
                        for a in jax.tree.leaves(params)),
        "pool_copies": len(re.findall(
            r"= bf16\[(%d|%d),%d,\d+\]\S* copy(-start)?\(" % (
                BLOCKS["full"], BLOCKS["window"], BS), text)),
        "aliased": len(re.findall(r"\(\d+, \{\}", header)),
        "alias_bytes": ma.alias_size_in_bytes, "pool_bytes": pool_bytes,
        "plan_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes,
        "full_calls": calls("gqa_full_decode"),
        "window_calls": calls("gqa_window_decode"),
        "grouped_calls": calls("moe_grouped")}), flush=True)
print("aot-serve-ok")
"""


@functools.lru_cache(maxsize=1)
def _aot_mimo_programs():
    out = subprocess.run(
        [sys.executable, "-c", _AOT_MIMO_SCRIPT], cwd=str(REPO),
        env=dict(_sub_env(), JAX_PLATFORMS="cpu",
                 JAX_ENABLE_COMPILATION_CACHE="0"),
        capture_output=True, text=True, timeout=1200)
    if "aot-topology-ok" not in out.stdout:
        return None, (out.stdout + out.stderr)[-600:]
    assert out.returncode == 0 and "aot-serve-ok" in out.stdout, \
        f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    rows = [json.loads(line.split(" ", 1)[1])
            for line in out.stdout.splitlines()
            if line.startswith("aot-program ")]
    return {r["name"]: r for r in rows}, ""


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_b2048"])
def test_mimo_v2_step_compiled_for_v5e_keeps_both_pools_in_place(program):
    """ISSUE 35: the cell's programs (published widths, 16 held experts,
    128 rows, the traffic file's two pools) compile for a v5e: a decode
    step holds one `gqa_full_decode` a full layer and one
    `gqa_window_decode` a window layer, a prefill neither (XLA in blocks);
    the 14 page arrays of both pools alias their inputs with no
    pool-shaped copy; the tables are 80 and 2 wide."""
    programs, why = _aot_mimo_programs()
    if programs is None:
        pytest.skip(f"no v5e topology from libtpu here: {why}")
    p = programs[program]
    assert p["n_params"] == 3_429_955_392, p           # 6.86 GB in bf16
    assert p["widths"] == [80, 2], p
    assert p["pool_copies"] == 0, p
    assert p["aliased"] == 14 and p["alias_bytes"] == p["pool_bytes"], p
    assert p["plan_bytes"] < 15.75e9, p
    decode = program == "serve_decode"
    assert (p["full_calls"], p["window_calls"]) == (
        (2, 5) if decode else (0, 0)), p
    assert p["grouped_calls"] == 12, p                 # 6 layers x (up, down)
