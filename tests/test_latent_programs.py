"""DeepSeek-V2's and GLM-5's attention does not move when the latent
decoder gains what another model needs (a shape a layer kind, a window
kind, an output gate): the jaxprs of one ``LatentAttention`` forward of
each model, in decode and in prefill over the page pool, and of the
kernels ``mla_latent_attn`` and ``dsa_sparse_attn`` are compared as text
with a copy taken before that change
(``fixtures/latent_programs.json.gz``).

Nor do the whole serving step programs of DeepSeek-V2, GLM-5, dots3 and
MiMo-V2 (``<model>.step_decode`` / ``<model>.step_prefill``: the engine's
packing, the stack, the expert layers, the head) move when the stack, the
cache and the expert layer gain what a model of another shape needs (a
block that is one branch, state held a sequence, latent relu2 experts).

To take the copy anew on purpose (after a change that is MEANT to move
these programs): ``PYTHONPATH=. JAX_PLATFORMS=cpu python
tests/test_latent_programs.py --write``."""
import difflib
import gzip
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "latent_programs.json.gz")
BLOCKS, BLOCK = 32, 8
# (name, rows, tokens a row): a decode step; a prefill chunk in the plain
# form (DeepSeek-V2) or the indexer's blocked form (GLM-5); and DeepSeek-V2's
# blocked form, which a chunk takes once its scores pass _SCORE_BUDGET
PHASES = {"deepseek_v2": (("decode", 2, 1), ("prefill", 1, 64),
                          ("prefill_blocked", 1, 6144)),
          "glm5": (("decode", 2, 1), ("prefill", 1, 64))}
# the models whose whole engine step program is pinned, decode and a
# prefill bucket
STEP_MODELS = ("deepseek_v2", "glm5", "dots3", "mimo_v2")
STEP_PREFILL = 32


def program_texts():
    """``{name: jaxpr text}`` of layer 0's attention of the tiny DeepSeek-V2
    and GLM-5 over a page pool, and of ``mla_latent_attn`` /
    ``dsa_sparse_attn`` (the walk over the live pages) in interpret mode,
    all traced under one matmul precision (a jaxpr writes down the
    precision in force)."""
    with jax.default_matmul_precision("highest"):
        return _program_texts()


def _attention_text(model, rows, chunk):
    from paddle_tpu.inference.kv_cache import PagedLayerCache
    c = model.config
    attn = model.layers[0].attn
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    pages = tuple(jax.ShapeDtypeStruct((BLOCKS, BLOCK) + per, jnp.float32)
                  for per in model.kv_cache_layout()[0])
    width = -(-(chunk + 64) // BLOCK)

    def forward(params, h, positions, pages, tables, lens, slots, last):
        cache = PagedLayerCache(pages, tables, lens, slots, BLOCK)
        return attn.apply(params, h, positions, cache,
                          last_index=None if chunk == 1 else last)

    return str(jax.make_jaxpr(forward)(
        attn.state_dict(),
        jax.ShapeDtypeStruct((rows, chunk, c.hidden_size), jnp.float32),
        i32(rows, chunk), pages, i32(rows, width), i32(rows),
        i32(rows, chunk), i32(rows)))


def _step_models():
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                               deepseek_v2_tiny)
    from paddle_tpu.models.dots3 import Dots3ForCausalLM, dots3_tiny
    from paddle_tpu.models.glm5 import Glm5ForCausalLM, glm5_tiny
    from paddle_tpu.models.mimo_v2 import MimoV2ForCausalLM, mimo_v2_tiny
    return {"deepseek_v2": (DeepseekV2ForCausalLM,
                            deepseek_v2_tiny(ep_degree=4)),
            "glm5": (Glm5ForCausalLM, glm5_tiny(ep_degree=4)),
            "dots3": (Dots3ForCausalLM, dots3_tiny(ep_degree=4)),
            "mimo_v2": (MimoV2ForCausalLM, mimo_v2_tiny(ep_degree=4))}


def _step_texts(name, cls, cfg):
    """The engine's step program of a tiny engine over ``cls(cfg)``, a
    decode step and a prefill of ``STEP_PREFILL``, as jaxpr text."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.inference.engine import pack_step_inputs
    pt.seed(0)
    eng = ServingEngine(cls(cfg), max_seqs=2, kv_block_size=8,
                        max_model_len=64)
    fn = eng._build_step_fn()
    out = {}
    for phase, rows, chunk in (("step_decode", 2, 1),
                               ("step_prefill", 1, STEP_PREFILL)):
        sids = [f"none-{i}" for i in range(rows)]
        tables = eng.cache.step_tables(sids, eng.sched.max_blocks_per_seq)
        slots = eng.cache.step_slots(sids, [-1] * rows, chunk)
        if len(tables) == 1:
            tables, slots = tables[0], slots[0]
        packed = pack_step_inputs(
            np.zeros((rows, chunk), np.int32), np.zeros((rows,), np.int32),
            0, tables, np.zeros((rows,), np.int32), slots)
        out[f"{name}.{phase}"] = str(jax.make_jaxpr(
            lambda *a: fn(*a, rows=rows, chunk=chunk))(
            eng._params, packed, eng.cache.pages, eng._key, eng._no_prev))
    eng.stop()
    return out


def _program_texts():
    import paddle_tpu as pt
    from paddle_tpu.inference.latent_attention import latent_attention_pallas
    from paddle_tpu.inference.sparse_attention import dsa_sparse_attn_pallas
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                               deepseek_v2_tiny)
    from paddle_tpu.models.glm5 import Glm5ForCausalLM, glm5_tiny

    out = {}
    for name, cls, cfg in (
            ("deepseek_v2", DeepseekV2ForCausalLM, deepseek_v2_tiny()),
            ("glm5", Glm5ForCausalLM, glm5_tiny())):
        pt.seed(0)
        model = cls(cfg)
        for phase, rows, chunk in PHASES[name]:
            out[f"{name}.{phase}"] = _attention_text(model, rows, chunk)
    q = jax.ShapeDtypeStruct((2, 4, 128), jnp.float32)
    pages = jax.ShapeDtypeStruct((6, 8, 128), jnp.float32)
    table = jax.ShapeDtypeStruct((2, 3), jnp.int32)
    lens = jax.ShapeDtypeStruct((2,), jnp.int32)
    out["mla_latent_attn"] = str(jax.make_jaxpr(
        lambda *a: latent_attention_pallas(*a, 64, 0.1, interpret=True))(
        q, pages, table, lens))
    scores = jax.ShapeDtypeStruct((2, 24), jnp.float32)
    threshold = jax.ShapeDtypeStruct((2,), jnp.float32)
    out["dsa_sparse_attn"] = str(jax.make_jaxpr(
        lambda *a: dsa_sparse_attn_pallas(*a, value_width=64, scale=0.1,
                                          interpret=True))(
        q, pages, table, lens, scores, threshold, lens))
    for name, (cls, cfg) in _step_models().items():
        out.update(_step_texts(name, cls, cfg))
    return out


@pytest.fixture(scope="module")
def programs():
    return program_texts()


@pytest.fixture(scope="module")
def before():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("name", [
    f"{model}.{phase}" for model, phases in PHASES.items()
    for phase, _, _ in phases] + ["mla_latent_attn", "dsa_sparse_attn"]
    + [f"{model}.{phase}" for model in STEP_MODELS
       for phase in ("step_decode", "step_prefill")])
def test_the_program_is_the_one_before_the_change(programs, before, name):
    now, then = programs[name], before[name]
    if now != then:
        diff = "\n".join(list(difflib.unified_diff(
            then.splitlines(), now.splitlines(), "before", "now",
            lineterm=""))[:60])
        pytest.fail(f"{name} moved:\n{diff}")


if __name__ == "__main__" and "--write" in sys.argv:
    os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
    with gzip.open(FIXTURE, "wt") as f:
        json.dump(program_texts(), f)
    print("wrote", FIXTURE)
