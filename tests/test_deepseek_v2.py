"""DeepSeek-V2 on the serving path (ISSUE 28), at a small size on the CPU:
the engine against the benchmark's plain reference
(``perfbench/reference/deepseek_v2.py``: the tests import it, no second copy
of the equations is kept), absorbed against plain attention, the latent
kernel and the grouped product against their ``jax.numpy`` twins, YaRN and
the router against hand-computed cases, and the share test: the four
ranks' parts add up to the uncut layer."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference.engine import pack_step_inputs
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.inference.latent_attention import (
    latent_attention_pallas, latent_attention_reference)
from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                           deepseek_v2_tiny, yarn_inv_freq,
                                           yarn_mscale)
from paddle_tpu.nn.dropless_moe import DroplessMoE, group_limited_topk
from paddle_tpu.observability.registry import MetricsRegistry
from perfbench.reference import deepseek_v2 as ref

ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 32,
        "type": "yarn"}


def reference_cfg(c, **over):
    """The reference's view (the configuration file's keys) of a model
    config."""
    cfg = {"num_attention_heads": c.num_heads,
           "qk_nope_head_dim": c.qk_nope_head_dim,
           "qk_rope_head_dim": c.qk_rope_head_dim,
           "v_head_dim": c.v_head_dim, "kv_lora_rank": c.kv_lora_rank,
           "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
           "rope_scaling": dict(
               ROPE, original_max_position_embeddings=c.
               rope_original_max_position_embeddings),
           "n_group": c.n_group, "topk_group": c.topk_group,
           "num_experts_per_tok": c.num_experts_per_tok,
           "norm_topk_prob": c.norm_topk_prob,
           "routed_scaling_factor": c.routed_scaling_factor,
           "ep_degree": c.ep_degree, "ep_rank": c.ep_rank}
    cfg.update(over)
    return cfg


def reference_params(params, layers):
    """The program's parameters under the reference's names: the
    benchmark builder's own mapping."""
    from perfbench.builders.deepseek_v2 import DeepseekV2System
    return DeepseekV2System({"num_hidden_layers": layers},
                            0).reference_params(params)


@pytest.fixture(scope="module", params=[0, 1], ids=["rank0", "rank1"])
def served(request):
    """A tiny model (one rank of 4, a sliced vocabulary) served through
    the engine: prompts, the collected results and the engine.  Rank 0 is
    the rank the benchmark's cell runs."""
    pt.seed(28)
    cfg = deepseek_v2_tiny(ep_degree=4, ep_rank=request.param,
                           initializer_range=0.2)
    model = DeepseekV2ForCausalLM(cfg)
    eng = ServingEngine(model, max_seqs=4, kv_block_size=8, max_model_len=64,
                        capture_logits=True, registry=MetricsRegistry())
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (9, 17, 3, 24)]       # 24: a page boundary (3 x 8)
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    return cfg, model, eng, prompts, [eng.collect(r) for r in rids]


def _routing(result):
    """``(expert layers, cached tokens, top_k)``, as the reference takes
    the program's choices."""
    return np.swapaxes(result["per_token"]["moe_topk"], 0, 1)


def _reference_logits(cfg, eng, prompts, results, routing,
                      limits=(1e-3, 1.0), **over):
    new = len(results[0]["tokens"])
    width = max(len(p) for p in prompts) + new
    ids = np.zeros((len(prompts), width), np.int32)
    pos = np.zeros((len(prompts), new), np.int32)
    for i, (p, r) in enumerate(zip(prompts, results)):
        seq = p + r["tokens"][:new - 1]
        ids[i, :len(seq)] = seq
        pos[i] = np.arange(len(p) - 1, len(p) - 1 + new)
    return ref.Reference(reference_cfg(cfg, **over)).logits_at(
        reference_params(eng._params, cfg.num_layers), ids, pos,
        pos[:, -1] + 1, routing, *limits)


def test_engine_prefill_and_decode_agree_with_the_plain_reference(served):
    cfg, _, eng, prompts, results = served
    got = np.stack([np.stack(r["logits"]) for r in results])
    want, report = _reference_logits(cfg, eng, prompts, results,
                                     [_routing(r) for r in results])
    assert report["ok"] and report["tokens_differ"] == 0
    assert report["tokens"] == 2 * sum(len(p) + 5 for p in prompts)
    assert np.abs(want).max() > 0.5            # logits worth comparing
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and under the reference's OWN routing: float32 has no near-ties
    own, _ = _reference_logits(cfg, eng, prompts, results, None)
    np.testing.assert_allclose(got, own, atol=2e-5)


def test_too_many_choices_other_than_the_references_own_fail(served):
    """The third limit of the comparison: one token's choice swapped for
    another expert of a group it had already touched passes the margin
    check at ``tie_eps`` 1, and fails once the share of such choices may
    be none."""
    cfg, _, eng, prompts, results = served
    routing = [_routing(r).copy() for r in results]
    chosen = routing[0][0, 0]                     # first layer, first token
    group = chosen[0] // (cfg.n_routed_experts // cfg.n_group)
    per = cfg.n_routed_experts // cfg.n_group
    spare = next(e for e in range(group * per, (group + 1) * per)
                 if e not in chosen)
    routing[0][0, 0, -1] = spare
    logits, report = _reference_logits(cfg, eng, prompts, results, routing,
                                       limits=(1.0, 0.5))
    assert report["ok"] and report["tokens_differ"] == 1
    assert np.isfinite(logits).all()
    logits, report = _reference_logits(cfg, eng, prompts, results, routing,
                                       limits=(1.0, 0.0))
    assert not report["ok"] and np.isnan(logits).all()


def test_absorbed_decode_agrees_with_plain_attention(served):
    """Decode runs the absorbed form over latent pages; the model's own
    cache-free forward runs the plain form over the whole sequence."""
    cfg, model, eng, prompts, results = served
    for p, r in zip(prompts, results):
        seq = p + r["tokens"][:-1]
        plain = np.asarray(model.apply(eng._params, jnp.asarray([seq])))[0]
        np.testing.assert_allclose(np.stack(r["logits"]),
                                   plain[len(p) - 1:], atol=2e-5)


# what each broken piece of the mathematics does to the logits: the
# comparison that decides `correct` has to see every one of them
BROKEN = {
    "no_routed_scaling_factor": {"routed_scaling_factor": 1.0},
    "renormalised_gate_weights": {"norm_topk_prob": True},
    "wrong_group_limit": {"topk_group": 4},
    "missing_mscale_squared": {"rope_scaling": dict(
        ROPE, mscale_all_dim=0.0)},
    "wrong_rank": {"ep_rank": 2},              # the fixture's is 0 or 1
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_reference_disagrees_by_far_more_than_rounding(served,
                                                                fault):
    cfg, _, eng, prompts, results = served
    got = np.stack([np.stack(r["logits"]) for r in results])
    bad, _ = _reference_logits(cfg, eng, prompts, results, None,
                               **BROKEN[fault])
    assert np.abs(got - bad).max() > 0.02      # 1000 x the agreement above


def test_engine_reads_the_cache_layout_from_the_model(served):
    cfg, model, eng, _, _ = served
    assert model.kv_cache_layout() == [((128,),)] * 3      # 24 -> 128 lanes
    assert [tuple(a.shape for a in layer) for layer in eng.cache.pages] \
        == [((eng.cache.num_blocks, 8, 128),)] * 3
    stats = eng.stats()
    assert stats["kv_bytes_per_token"] == 3 * 128 * 4
    assert stats["model_gauges"] == {
        "serve.kv_latent_bytes_per_token": 3 * 24 * 4.0}
    snap = eng._reg().snapshot()
    assert snap["serve.kv_latent_bytes_per_token"]["value"] == 288.0


def test_expert_counters_ride_out_with_the_steps(served):
    cfg, _, eng, prompts, results = served
    booked, snap = eng.stats()["model_counts"], eng._reg().snapshot()
    counters = booked["counters"]
    assert set(counters) == {"serve.moe_pairs", "serve.moe_pairs_dropped",
                             "serve.moe_experts_touched"}
    assert counters["serve.moe_pairs_dropped"] == 0
    for name, total in counters.items():
        assert snap[name]["value"] == total
    assert counters["serve.moe_experts_touched"] > 0
    # every pair computed here is a (token, held expert) pair the router
    # chose: count them from the captured choices (rank r holds 4r..4r+3)
    chosen = [r["per_token"]["moe_topk"] for r in results]
    lo = 4 * cfg.ep_rank
    assert counters["serve.moe_pairs"] == sum(
        int(((c >= lo) & (c < lo + 4)).sum()) for c in chosen)
    load = booked["gauges"]["serve.moe_load_max_over_mean"]
    assert load["steps"] == 5 and load["last"] >= 1.0
    assert load["sum"] >= load["steps"]
    assert snap["serve.moe_load_max_over_mean"]["value"] \
        == pytest.approx(load["last"])
    for c, p in zip(chosen, prompts):
        assert c.shape == (len(p) + 5, 2, 3)       # (tokens, layers, top_k)


def test_the_engine_names_no_expert_layer():
    """The counts' names and meaning are the model's (``serving_counts``):
    the shared engine, scheduler and pool know no model family."""
    import inspect

    from paddle_tpu.inference import engine, kv_cache, scheduler
    for module in (engine, kv_cache, scheduler):
        assert "moe" not in inspect.getsource(module).lower()


def test_named_scopes_of_the_new_device_parts(served):
    _, _, eng, _, _ = served
    tables = np.zeros((4, eng.sched.max_blocks_per_seq), np.int32)

    def names(rows, chunk):
        packed = pack_step_inputs(
            np.zeros((rows, chunk)), np.zeros((rows,)), 0, tables[:rows],
            np.ones((rows,)), np.zeros((rows, chunk)))
        text = eng._build_step_fn().lower(
            eng._params, packed, eng.cache.pages, jax.random.PRNGKey(0),
            eng._no_prev, rows=rows, chunk=chunk).as_text(debug_info=True)
        return text
    decode, prefill = names(4, 1), names(1, 8)
    for scope in ("mla.q", "mla.kv_write", "mla.decode", "moe.route",
                  "moe.experts", "moe.shared"):
        assert scope in decode, scope
    assert "mla.prefill" in prefill and "mla.decode" not in prefill


# -- the latent kernel against its jax.numpy twin ---------------------------
@pytest.mark.parametrize("lens", [
    [5, 13, 30, 1],          # ragged, and a row of length 1
    [8, 16, 32, 24],         # every length on a page boundary
    [32, 0, 9, 17],          # a padding row among full ones
], ids=["ragged", "page_boundaries", "padding_row"])
def test_latent_kernel_matches_its_reference(lens):
    rng = np.random.default_rng(len(lens) + sum(lens))
    b, h, w, v, bs, blocks, t = len(lens), 4, 128, 16, 8, 24, 4
    q = jnp.asarray(rng.normal(size=(b, h, w)), jnp.float32)
    pages = jnp.asarray(rng.normal(size=(blocks, bs, w)), jnp.float32)
    tables = jnp.asarray(rng.permutation(blocks)[:b * t].reshape(b, t),
                         jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    got = latent_attention_pallas(q, pages, tables, lens, v, 0.25,
                                  interpret=True)
    want = latent_attention_reference(q, pages, tables, lens, v, 0.25)
    assert got.shape == (b, h, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert not np.asarray(got)[np.asarray(lens) == 0].any()


# -- YaRN ---------------------------------------------------------------------
def test_yarn_frequencies_and_mscale_against_hand_computed_values():
    # DeepSeek-V2: 64 rotary dims, theta 1e4, factor 40, 4096 original
    # positions, beta 32 / 1: correction dims floor(10.26) = 10 and
    # ceil(22.30) = 23, so pairs 0..10 keep theta^(-2i/64), pairs 23.. are
    # divided by 40, and pair 16 sits at (16 - 10) / 13 of the ramp
    f = np.asarray(yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0))
    extra = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], extra[23:] / 40.0, rtol=1e-6)
    ramp = 6.0 / 13.0
    assert f[16] == pytest.approx(extra[16] * (1 - ramp)
                                  + extra[16] / 40.0 * ramp, rel=1e-6)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(
        0.1 * 0.707 * math.log(40.0) + 1.0)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert yarn_mscale(1.0, 0.707) == 1.0
    # the softmax scale: 192^-0.5 x 1.2608^2
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config
    assert DeepseekV2Config().softmax_scale == pytest.approx(
        192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
    # the reference computes the same frequencies on its own
    np.testing.assert_allclose(
        np.asarray(ref.yarn_inv_freq(64, 10000.0, dict(
            ROPE, original_max_position_embeddings=4096))), f, rtol=1e-6)


# -- the router ---------------------------------------------------------------
def test_group_limit_keeps_a_strong_expert_of_a_losing_group_out():
    # 4 groups of 4, 2 groups kept, top 3.  A group's score is its best
    # expert's, so the overall best expert's group always stays; the case
    # that matters is the THIRD best expert overall (0.20, expert 9) in a
    # group that loses the cut: plain top-3 would take it, the group limit
    # takes expert 1 (0.09) instead.
    s = np.full((1, 16), 0.01, np.float32)
    s[0, [0, 1]] = 0.30, 0.09          # group 0
    s[0, 5] = 0.25                     # group 1
    s[0, 9] = 0.20                     # group 2: loses to groups 0 and 1
    w, idx = group_limited_topk(jnp.asarray(s), 4, 2, 3)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 1, 5]
    np.testing.assert_allclose(np.sort(np.asarray(w)[0]),
                               [0.09, 0.25, 0.30])
    assert sorted(np.argsort(-s[0])[:3].tolist()) == [0, 5, 9]
    cfg = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 3}
    assert sorted(ref.own_choice(s, cfg)[0].tolist()) == [0, 1, 5]
    # the reference's check of a handed choice: a near-tie passes, the
    # expert of the losing group and a fourth group do not
    s[0, 2] = 0.0899                   # within 0.2% of expert 1
    ok = ref.check_choice(s, np.asarray([[0, 2, 5]]), cfg, 0.01)
    assert ok["ok"] and ok["tokens_differ"] == 1
    assert ok["max_margin"] == pytest.approx(1 - 0.0899 / 0.09, rel=1e-3)
    assert not ref.check_choice(s, np.asarray([[0, 5, 9]]), cfg, 0.01)["ok"]
    assert not ref.check_choice(s, np.asarray([[0, 5, 5]]), cfg, 0.01)["ok"]
    s3 = s.copy()
    s3[0, 13] = 0.2499                 # group 3 ties group 1 for the cut
    assert ref.check_choice(s3, np.asarray([[0, 1, 13]]), cfg, 0.01)["ok"]
    assert not ref.check_choice(s3, np.asarray([[0, 5, 13]]), cfg,
                                0.01)["ok"]      # three groups touched


# -- the expert layer ---------------------------------------------------------
def _moe(rank, degree=4, **kw):
    pt.seed(11)                        # every rank draws the same tensors
    full = DroplessMoE(32, 16, 16, 3, 4, 2, 2, 16.0, False, 1, 0, std=0.3)
    if degree == 1:
        return full
    part = DroplessMoE(32, 16, 16, 3, 4, 2, 2, 16.0, False, degree, rank,
                       std=0.3, **kw)
    held = part.held
    state = full.state_dict()
    sl = slice(rank * held, (rank + 1) * held)
    state.update({k: state[k][sl] for k in ("w_gate", "w_up", "w_down")})
    part.set_state_dict(state)
    return part


def test_the_four_ranks_parts_add_up_to_the_uncut_layer():
    """The guide's share test: each rank computes its own experts' part
    (and the shared experts, which every chip computes alike and which
    count once); the sum is the uncut reference layer."""
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(29, 32)), jnp.float32)
    full = _moe(0, degree=1)
    state = full.state_dict()
    shared = np.asarray(full.shared(h))
    total = np.zeros((29, 32), np.float32)
    loads = []
    for rank in range(4):
        y, aux = _moe(rank)(h)
        total += np.asarray(y) - shared
        loads.append(np.asarray(aux["load"]))
        assert int(aux["dropped"]) == 0
    total += shared
    # the uncut layer by the plain reference (one "rank" holding all 16)
    cfg = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
           "norm_topk_prob": False, "routed_scaling_factor": 16.0,
           "ep_rank": 0, "rms_norm_eps": 1e-6}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.Reference(cfg)._expert_layer(
            h, {"router": state["router"], "w_gate": state["w_gate"],
                "w_up": state["w_up"], "w_down": state["w_down"],
                "shared": (state["shared.w_gate"], state["shared.w_up"],
                           state["shared.w_down"])}, None, None, 0.0)
    np.testing.assert_allclose(total, np.asarray(want), atol=2e-4)
    np.testing.assert_allclose(np.asarray(full(h)[0]), np.asarray(want),
                               atol=2e-4)
    # every (token, expert) pair the router chose was computed somewhere
    assert int(np.concatenate(loads).sum()) == 29 * 3


@pytest.mark.parametrize("tile", [4, 8])
def test_pallas_grouped_product_matches_ragged_dot(tile):
    rng = np.random.default_rng(tile)
    moe = _moe(2)
    h = jnp.asarray(rng.normal(size=(37, 32)), jnp.float32)
    valid = jnp.arange(37) < 30                      # 7 padding tokens
    y1, a1 = moe(h, valid, tile=1)                   # jax.lax.ragged_dot
    y2, a2 = moe(h, valid, tile=tile)                # the kernels, interpreted
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(a1["load"]),
                                  np.asarray(a2["load"]))
    assert int(a2["dropped"]) == 0
    # padding tokens reach no expert: the load counts 30 tokens' pairs
    idx = np.asarray(a1["topk"])[:30]
    assert int(np.asarray(a1["load"]).sum()) == int(
        ((idx >= 8) & (idx < 12)).sum())


def test_an_expert_nobody_chose_costs_nothing_and_breaks_nothing():
    moe = _moe(3)
    h = jnp.zeros((5, 32), jnp.float32).at[:, 0].set(1.0)   # one token, 5x
    y, aux = moe(h, tile=4)
    load = np.asarray(aux["load"])
    assert (load == 0).any() and int(aux["dropped"]) == 0
    y1, _ = moe(h, tile=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1), atol=1e-6)
    # no held expert chosen at all: the shared experts alone
    y0, aux0 = moe(h, jnp.zeros((5,), bool), tile=4)
    assert int(np.asarray(aux0["load"]).sum()) == 0
    np.testing.assert_allclose(np.asarray(y0), np.asarray(moe.shared(h)),
                               atol=1e-6)


# -- the pool -----------------------------------------------------------------
def test_pool_takes_any_declared_layout():
    c = PagedKVCache([((128,),), ((2, 4), (2, 4))], num_blocks=4,
                     block_size=8)
    assert [tuple(a.shape for a in layer) for layer in c.pages] == [
        ((4, 8, 128),), ((4, 8, 2, 4), (4, 8, 2, 4))]
    assert c.bytes_per_token() == (128 + 16) * 4
    assert c.pool_bytes() == 4 * 8 * (128 + 16) * 4
    assert c.ensure_capacity("a", 9)
    slots = c.slot_array(["a"], [0], 2)
    latent, pair = c.layer_caches(c.table_array(["a"], 2),
                                  np.asarray([2], np.int32), slots)
    row = jnp.ones((2, 128))
    new = latent.write(row)
    assert float(np.asarray(new.pages[0]).sum()) == 2 * 128
    with pytest.raises(Exception):
        pair.write(row)                 # two page arrays want two arrays
    c.update_pages([new.pages, pair.pages])
    c.scrub_seq("a")
    assert not np.asarray(c.pages[0][0]).any()
    with pytest.raises(Exception):
        c.update_pages([pair.pages, pair.pages])
