"""GLM-5 on the serving path (ISSUE 32), at a small size on the CPU, float32,
``index_topk`` 8 against sequences of 48 and more: the engine against the
benchmark's plain reference (``perfbench/reference/glm5.py``: imported, no
second copy of the equations), each new kernel against its ``jax.numpy``
twin, the selection's ties, contexts of at most ``index_topk`` against dense
latent attention (the tie to DeepSeek-V2's path), the share test of the
sigmoid router, and written controls: each broken piece of the mathematics
moves the logits by far more than the agreement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference import sparse_attention as sa
from paddle_tpu.inference.engine import pack_step_inputs
from paddle_tpu.inference.latent_attention import latent_attention_reference
from paddle_tpu.models import decoder_stack, latent_decoder
from paddle_tpu.models.glm5 import Glm5Config, Glm5ForCausalLM, glm5_tiny
from paddle_tpu.nn.dropless_moe import DroplessMoE
from paddle_tpu.observability.registry import MetricsRegistry
from perfbench.reference import glm5 as ref

NEW = 6


def reference_cfg(c, **over):
    """The reference's view (the configuration file's keys) of a model
    config."""
    cfg = {"num_attention_heads": c.num_heads,
           "qk_nope_head_dim": c.qk_nope_head_dim,
           "qk_rope_head_dim": c.qk_rope_head_dim,
           "v_head_dim": c.v_head_dim, "kv_lora_rank": c.kv_lora_rank,
           "rms_norm_eps": c.rms_norm_eps,
           "rope_parameters": {"rope_theta": c.rope_theta},
           "index_n_heads": c.index_n_heads,
           "index_head_dim": c.index_head_dim, "index_topk": c.index_topk,
           "num_experts_per_tok": c.num_experts_per_tok,
           "norm_topk_prob": c.norm_topk_prob,
           "routed_scaling_factor": c.routed_scaling_factor,
           "ep_degree": c.ep_degree, "ep_rank": c.ep_rank}
    cfg.update(over)
    return cfg


def reference_params(params, layers):
    """The program's parameters under the reference's names: the
    benchmark builder's own mapping."""
    from perfbench.builders.glm5 import Glm5System
    return Glm5System({"num_hidden_layers": layers},
                      0).reference_params(params)


@pytest.fixture(scope="module", params=[0, 1], ids=["rank0", "rank1"])
def served(request):
    """A tiny model (one rank of 4, a sliced vocabulary) served through
    the engine with selection active: contexts of 48 to 70 tokens against
    ``index_topk`` 8, pages of 8.  Rank 0 is the rank the cell runs."""
    pt.seed(32)
    cfg = glm5_tiny(ep_degree=4, ep_rank=request.param,
                    initializer_range=0.2)
    model = Glm5ForCausalLM(cfg)
    eng = ServingEngine(model, max_seqs=4, kv_block_size=8, max_model_len=96,
                        capture_logits=True, registry=MetricsRegistry())
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (50, 17, 3, 64)]      # 64: a page boundary (8 x 8)
    rids = [eng.submit(p, max_new_tokens=NEW) for p in prompts]
    eng.run()
    return cfg, model, eng, prompts, [eng.collect(r) for r in rids]


def _handed(results):
    routing = [np.swapaxes(r["per_token"]["moe_topk"], 0, 1)
               for r in results]
    selections = [np.swapaxes(r["per_logit"]["dsa_selected"], 0, 1)
                  for r in results]
    return routing, selections


def _reference_logits(cfg, eng, prompts, results, handed=(None, None),
                      limits=None, **over):
    width = max(len(p) for p in prompts) + NEW
    ids = np.zeros((len(prompts), width), np.int32)
    pos = np.zeros((len(prompts), NEW), np.int32)
    for i, (p, r) in enumerate(zip(prompts, results)):
        seq = p + r["tokens"][:NEW - 1]
        ids[i, :len(seq)] = seq
        pos[i] = np.arange(len(p) - 1, len(p) - 1 + NEW)
    return ref.Reference(reference_cfg(cfg, **over), query_block=8,
                         head_group=2).logits_at(
        reference_params(eng._params, cfg.num_layers), ids, pos,
        pos[:, -1] + 1, *handed, limits)


# -- the engine against the plain reference ---------------------------------
def test_engine_prefill_and_decode_agree_with_the_plain_reference(served):
    cfg, _, eng, prompts, results = served
    got = np.stack([np.stack(r["logits"]) for r in results])
    want, report = _reference_logits(cfg, eng, prompts, results,
                                     _handed(results))
    assert report["ok"]
    assert report["routing"]["tokens_differ"] == 0
    assert report["index"]["entries_differ"] == 0
    assert report["routing"]["tokens"] == 2 * sum(len(p) + NEW - 1
                                                  for p in prompts)
    # 3 layers x 6 compared positions x min(8, t + 1) entries a sequence
    assert report["index"]["entries"] == 3 * sum(
        min(8, t + 1) for p in prompts
        for t in range(len(p) - 1, len(p) - 1 + NEW))
    assert np.abs(want).max() > 0.5            # logits worth comparing
    np.testing.assert_allclose(got, want, atol=3e-5)
    # and under the reference's OWN choices: float32 has no near-ties
    own, _ = _reference_logits(cfg, eng, prompts, results)
    np.testing.assert_allclose(got, own, atol=3e-5)


def test_whole_model_logits_agree_with_the_plain_reference(served):
    """The cache-free forward (blocked prefill on the whole sequence)
    against the reference at every position of one sequence."""
    cfg, model, eng, prompts, results = served
    seq = prompts[0] + results[0]["tokens"][:NEW - 1]
    got = np.asarray(model.apply(eng._params, jnp.asarray([seq])))[0]
    pos = np.arange(len(seq))[None]
    want, _ = ref.Reference(reference_cfg(cfg), query_block=8,
                            head_group=2).logits_at(
        reference_params(eng._params, cfg.num_layers),
        np.asarray([seq]), pos, [len(seq)])
    np.testing.assert_allclose(got, want[0], atol=3e-5)


def test_paged_decode_agrees_with_the_models_own_plain_forward(served):
    cfg, model, eng, prompts, results = served
    for p, r in zip(prompts, results):
        seq = p + r["tokens"][:-1]
        plain = np.asarray(model.apply(eng._params, jnp.asarray([seq])))[0]
        np.testing.assert_allclose(np.stack(r["logits"]),
                                   plain[len(p) - 1:], atol=3e-5)


def test_selections_are_handed_out_a_logits_row_only(served):
    """A chunk's every selection would be gigabytes at the cell's size:
    ``per_logit`` has one entry a captured logits row."""
    cfg, _, eng, prompts, results = served
    for p, r in zip(prompts, results):
        sel = r["per_logit"]["dsa_selected"]
        assert sel.shape == (NEW, cfg.num_layers, cfg.index_topk)
        assert len(r["logits"]) == NEW
        for j in range(NEW):
            t = len(p) - 1 + j                 # the query's position
            row = sel[j]
            assert ((row >= -1) & (row <= t)).all()
            assert ((row >= 0).sum(axis=1) == min(8, t + 1)).all()
        # experts are handed out for every cached token, as DeepSeek-V2's
        assert r["per_token"]["moe_topk"].shape == (len(p) + NEW - 1, 2, 3)


def test_a_handed_selection_outside_the_epsilon_fails(served):
    cfg, _, eng, prompts, results = served
    routing, selections = _handed(results)
    selections = [s.copy() for s in selections]
    # the first sequence's last compared query, layer 0: swap its best
    # entry for a position it did not select
    row = selections[0][0, -1]
    spare = next(i for i in range(len(prompts[0])) if i not in row)
    row[0] = spare
    # (a changed selection moves what follows it: let routing pass)
    free = {"routing_tie_eps": 100.0, "routing_differ_share": 1.0}
    loose = dict(free, index_tie_eps=100.0, index_differ_share=0.5)
    logits, report = _reference_logits(cfg, eng, prompts, results,
                                       (routing, selections), loose)
    # (the swapped entry, and what it moved in the layers above)
    assert report["ok"] and report["index"]["entries_differ"] >= 1
    assert report["index"]["max_margin"] > 0.1 and np.isfinite(logits).all()
    tight = dict(free, index_tie_eps=1e-3, index_differ_share=0.5)
    logits, report = _reference_logits(cfg, eng, prompts, results,
                                       (routing, selections), tight)
    assert not report["ok"] and np.isnan(logits).all()
    none = dict(free, index_tie_eps=100.0, index_differ_share=0.0)
    _, report = _reference_logits(cfg, eng, prompts, results,
                                  (routing, selections), none)
    assert not report["ok"]


def test_check_selection_by_hand():
    score = np.asarray([5.0, 1.0, 4.0, 3.9, -np.inf, -np.inf], np.float32)
    ok = ref.check_selection(score, np.asarray([0, 2, -1]), 2, 0.0)
    assert ok == {"ok": True, "entries": 2, "entries_differ": 0,
                  "max_margin": 0.0}
    spread = float(np.std(score[:4]))
    near = ref.check_selection(score, np.asarray([0, 3]), 2, 0.1)
    assert near["ok"] and near["entries_differ"] == 1
    assert near["max_margin"] == pytest.approx(0.1 / spread, rel=1e-4)
    assert not ref.check_selection(score, np.asarray([0, 1]), 2, 0.1)["ok"]
    assert not ref.check_selection(score, np.asarray([0, 0]), 2, 9.0)["ok"]
    assert not ref.check_selection(score, np.asarray([0, 4]), 2, 9.0)["ok"]
    assert not ref.check_selection(score, np.asarray([0, -1]), 2, 9.0)["ok"]
    # fewer positions than top_k: all of them, and nothing else
    assert ref.check_selection(score, np.asarray([3, 1, 0, 2, -1]), 5,
                               0.0)["ok"]


# -- counters, gauges, layout, scopes ------------------------------------------
def test_two_page_arrays_of_unlike_widths_under_one_block_table(served):
    cfg, model, eng, _, _ = served
    assert model.kv_cache_layout() == [((128,), (16,))] * 3
    assert [tuple(a.shape for a in layer) for layer in eng.cache.pages] \
        == [((eng.cache.num_blocks, 8, 128), (eng.cache.num_blocks, 8, 16))
            ] * 3
    stats = eng.stats()
    assert stats["kv_bytes_per_token"] == 3 * (128 + 16) * 4
    assert stats["model_gauges"] == {
        "serve.kv_latent_bytes_per_token": 3 * 24 * 4.0,
        "serve.kv_index_bytes_per_token": 3 * 16 * 4.0}
    assert stats["kv_blocks"]["balanced"]


def test_selection_counters_ride_out_with_the_decode_steps(served):
    cfg, _, eng, prompts, results = served
    counters = eng.stats()["model_counts"]["counters"]
    # a decode step adds, per layer, the entries attended and scored
    contexts = [len(p) + j for p in prompts for j in range(1, NEW)]
    assert counters["serve.dsa_context_tokens"] == 3 * sum(contexts)
    assert counters["serve.dsa_selected_tokens"] == 3 * sum(
        min(8, c) for c in contexts)
    snap = eng._reg().snapshot()
    for name in ("serve.dsa_context_tokens", "serve.dsa_selected_tokens",
                 "serve.moe_pairs"):
        assert snap[name]["value"] == counters[name]
    assert counters["serve.moe_pairs_dropped"] == 0


def test_named_scopes_of_the_new_device_parts(served):
    _, _, eng, _, _ = served
    tables = np.zeros((4, eng.sched.max_blocks_per_seq), np.int32)

    def text(rows, chunk):
        packed = pack_step_inputs(
            np.zeros((rows, chunk)), np.zeros((rows,)), 0, tables[:rows],
            np.ones((rows,)), np.zeros((rows, chunk)))
        return eng._build_step_fn().lower(
            eng._params, packed, eng.cache.pages, jax.random.PRNGKey(0),
            eng._no_prev, rows=rows, chunk=chunk).as_text(debug_info=True)
    decode, prefill = text(4, 1), text(1, 16)
    for scope in ("dsa.index_q", "dsa.index_k_write", "dsa.index_scores",
                  "dsa.select", "dsa.attend", "mla.q", "mla.kv_write",
                  "moe.route", "moe.experts", "moe.shared", "glm5.head"):
        assert scope in decode, scope
        assert scope in prefill, scope
    assert "mla.decode" in decode and "mla.decode" not in prefill


# -- the kernels against their jax.numpy twins --------------------------------
LENS = {"ragged": [5, 13, 30, 1], "page_boundaries": [8, 16, 32, 24],
        "padding_row": [32, 0, 9, 17]}


def _pool(lens, width, seed):
    rng = np.random.default_rng(seed)
    b, bs, blocks, t = len(lens), 8, 24, 4
    pages = jnp.asarray(rng.normal(size=(blocks, bs, width)), jnp.float32)
    tables = jnp.asarray(rng.permutation(blocks)[:b * t].reshape(b, t),
                         jnp.int32)
    return rng, pages, tables, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("waves", [1, 3, 4], ids=lambda p: f"{p}_a_step")
@pytest.mark.parametrize("case", sorted(LENS))
def test_index_kernel_matches_its_reference(case, waves):
    """Also where the table's width is no multiple of the pages a step
    (4 entries, 3 a step): the tail is clamped and cut."""
    lens = LENS[case]
    rng, pages, tables, lens = _pool(lens, 16, sum(lens))
    q = jnp.asarray(rng.normal(size=(4, 4, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    got = sa.dsa_index_scores_pallas(q, w, pages, tables, lens,
                                     pages_per_step=waves, interpret=True)
    want = sa.dsa_index_scores_reference(q, w, pages, tables, lens)
    assert got.shape == want.shape == (4, 32)
    live = np.arange(32)[None, :] < np.asarray(lens)[:, None]
    assert (np.asarray(got)[~live] == -np.inf).all()
    assert (np.asarray(want)[~live] == -np.inf).all()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-5)


@pytest.mark.parametrize("case", sorted(LENS))
def test_sparse_attention_kernel_matches_its_reference(case):
    lens = LENS[case]
    rng, pages, tables, lens = _pool(lens, 128, 1 + sum(lens))
    q = jnp.asarray(rng.normal(size=(4, 4, 128)), jnp.float32)
    scores = jnp.where(jnp.arange(32)[None, :] < lens[:, None],
                       jnp.asarray(rng.normal(size=(4, 32)), jnp.float32),
                       -jnp.inf)
    sel = sa.dsa_select(scores, 8)
    got = sa.dsa_sparse_attn_pallas(q, pages, tables, lens, scores,
                                    sel.threshold, sel.last_tied, 16, 0.25,
                                    interpret=True)
    want = sa.dsa_sparse_attn_reference(
        q, pages, sa.dsa_slots(sel.positions, tables, 8), sel.counts, 16,
        0.25)
    assert got.shape == (4, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert not np.asarray(got)[np.asarray(lens) == 0].any()
    assert np.asarray(sel.counts).tolist() == [min(8, n) for n in LENS[case]]


def _tied_scores(rng, lens, ctx):
    """Row 0: six 2s and fifteen 1s scattered, the rest below: the twelfth
    largest falls inside the 1s.  Row 1: every score equal."""
    s = rng.normal(size=(len(lens), ctx)).astype(np.float32) - 3.0
    at = rng.permutation(lens[0])
    s[0, at[:6]], s[0, at[6:21]] = 2.0, 1.0
    s[1] = 0.5
    return s


def _signed_zeros(rng, lens, ctx):
    """Four positive scores, then zeros of both signs across the boundary
    of the top 8, the rest negative."""
    s = -1.0 - rng.random((len(lens), ctx)).astype(np.float32)
    for i, n in enumerate(lens):
        at = rng.permutation(n)
        s[i, at[:4]] = 1.0 + rng.random(4)
        s[i, at[4:14]] = np.where(rng.random(10) < 0.5, 0.0, -0.0)
    return s


# (page size, row width, lengths, table width, top_k, pages a grid step,
#  scores or None, pool blocks over the blocks the rows need)
WALK_CASES = {
    "ties_at_the_boundary": (8, 128, [40, 33], 5, 12, None, _tied_scores, 1),
    "minus_zero_as_zero": (8, 128, [30, 24, 17], 4, 8, None, _signed_zeros,
                           1),
    "rows_shorter_than_top_k": (8, 128, [5, 12, 3, 16], 3, 16, None, None,
                                1),
    "table_narrower_than_top_k": (8, 128, [5, 8, 1], 1, 16, None, None, 1),
    "padding_rows": (8, 128, [0, 30, 0, 17], 4, 8, None, None, 1),
    "partial_last_page": (8, 128, [19, 27, 33], 5, 8, None, None, 1),
    "scattered_tables": (8, 128, [40, 23, 31], 5, 8, None, None, 4),
    "wave_not_dividing_table": (8, 128, [40, 9, 33, 17], 5, 8, 2, None, 1),
    "three_pages_a_wave": (8, 128, [40, 9, 33, 17], 5, 8, 3, None, 1),
    "pages_of_16": (16, 128, [70, 16, 45], 5, 12, None, None, 1),
    "pages_of_16_rows_of_640": (16, 640, [70, 0, 45], 5, 12, 2, None, 1),
    "rows_of_640": (8, 640, [35, 12, 26], 5, 8, None, None, 2),
}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_the_walk_matches_the_gather_oracle(case):
    """The kernel walks every live page and keeps an entry by the
    threshold test; the oracle gathers the selected rows by their slots.
    Same entries, same attention, and the threshold test alone gives the
    positions ``dsa_select`` hands out."""
    bs, width, lens, t, top_k, per_step, make, spare = WALK_CASES[case]
    rng = np.random.default_rng(sum(lens) + bs + width)
    b, ctx = len(lens), t * bs
    blocks = spare * b * t
    pages = jnp.asarray(rng.normal(size=(blocks, bs, width)), jnp.float32)
    tables = jnp.asarray(rng.permutation(blocks)[:b * t].reshape(b, t),
                         jnp.int32)
    raw = (rng.normal(size=(b, ctx)).astype(np.float32) if make is None
           else make(rng, lens, ctx))
    live = np.arange(ctx)[None, :] < np.asarray(lens)[:, None]
    scores = jnp.asarray(np.where(live, raw, -np.inf), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 4, width)), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    sel = sa.dsa_select(scores, top_k)
    kept = (np.asarray(scores) > np.asarray(sel.threshold)[:, None]) | (
        (np.asarray(scores) == np.asarray(sel.threshold)[:, None])
        & (np.arange(ctx)[None, :] <= np.asarray(sel.last_tied)[:, None]))
    for row, pos in zip(kept & live, np.asarray(sel.positions)):
        assert sorted(np.flatnonzero(row)) == sorted(p for p in pos if p >= 0)
    got = sa.dsa_sparse_attn_pallas(q, pages, tables, lens, scores,
                                    sel.threshold, sel.last_tied, 64, 0.1,
                                    pages_per_step=per_step, interpret=True)
    want = sa.dsa_sparse_attn_reference(
        q, pages, sa.dsa_slots(sel.positions, tables, bs), sel.counts, 64,
        0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got)[np.asarray(lens) == 0].any()
    assert np.asarray(sel.counts).tolist() == [
        min(top_k, int(n)) for n in lens]


def test_contexts_of_at_most_top_k_give_exactly_dense_latent_attention():
    """The tie to DeepSeek-V2's path: where a row's context is no longer
    than ``index_topk``, selecting changes nothing."""
    rng, pages, tables, lens = _pool([5, 8, 1, 7], 128, 3)
    q = jnp.asarray(rng.normal(size=(4, 4, 128)), jnp.float32)
    scores = jnp.where(jnp.arange(32)[None, :] < lens[:, None],
                       jnp.asarray(rng.normal(size=(4, 32)), jnp.float32),
                       -jnp.inf)
    sel = sa.dsa_select(scores, 8)
    assert np.asarray(sel.counts).tolist() == [5, 8, 1, 7]
    sparse = sa.dsa_sparse_attn_reference(
        q, pages, sa.dsa_slots(sel.positions, tables, 8), sel.counts, 16,
        0.25)
    dense = latent_attention_reference(q, pages, tables, lens, 16, 0.25)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               atol=1e-6)


def test_short_sequences_read_the_same_with_and_without_an_indexer():
    """At the model's level: sequences of at most ``index_topk`` tokens
    through GLM-5's layer are plain causal latent attention."""
    pt.seed(3)
    cfg = glm5_tiny(index_topk=16, initializer_range=0.2)
    model = Glm5ForCausalLM(cfg)
    params = model.state_dict()
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16))
    got = np.asarray(model.apply(params, jnp.asarray(ids)))
    want, _ = ref.Reference(reference_cfg(cfg, index_topk=10 ** 6),
                            query_block=8, head_group=2).logits_at(
        reference_params(params, cfg.num_layers), ids,
        np.tile(np.arange(16), (2, 1)), [16, 16])
    np.testing.assert_allclose(got, want, atol=3e-5)


# -- the selection: ties go to the lower position -----------------------------
def test_ties_go_to_the_lower_position():
    s = np.full((3, 12), -np.inf, np.float32)
    s[0, :10] = [1, 3, 3, 3, 0, 3, 2, 3, 3, 0]       # five 3s, top 4
    s[1, :6] = 7.0                                    # all tied, top 4
    s[2, :3] = [0.0, -0.0, 0.0]                       # fewer than top_k
    pos, kept, threshold, last_tied = sa.dsa_select(jnp.asarray(s), 4)
    assert np.asarray(pos).tolist() == [[1, 2, 3, 5], [0, 1, 2, 3],
                                        [0, 1, 2, -1]]
    assert np.asarray(kept).tolist() == [4, 4, 3]
    # the same sets as a threshold and the last tied position selected
    assert np.asarray(threshold).tolist() == [3.0, 7.0, -np.inf]
    assert np.asarray(last_tied).tolist() == [5, 3, -1]
    # the prefill's threshold form selects the same sets (keys lead)
    mask = np.asarray(sa.select_mask(jnp.asarray(s.T), 4)).T
    for row, want in zip(mask, pos):
        assert sorted(np.flatnonzero(row).tolist()) \
            == sorted(int(p) for p in np.asarray(want) if p >= 0)
    # and the reference's own
    own = np.asarray(ref.top_positions(jnp.asarray(s), 4))
    np.testing.assert_array_equal(own, mask)


def test_threshold_selection_is_exact_on_random_scores():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(300, 5)).astype(np.float32)
    x[rng.random(x.shape) < 0.2] = 0.5               # many equal scores
    x[250:, 0] = -np.inf
    got = np.asarray(sa.select_mask(jnp.asarray(x), 33))
    order = np.argsort(-x, axis=0, kind="stable")[:33]
    want = np.zeros_like(got)
    np.put_along_axis(want, order, True, axis=0)
    np.testing.assert_array_equal(got, want & np.isfinite(x))


# -- the sigmoid router --------------------------------------------------------
def _moe(rank, degree=4):
    pt.seed(11)                        # every rank draws the same tensors
    full = DroplessMoE(32, 16, 16, 3, 1, 1, 1, 2.5, True, 1, 0, std=0.3,
                       scoring_func="sigmoid")
    if degree == 1:
        return full
    part = DroplessMoE(32, 16, 16, 3, 1, 1, 1, 2.5, True, degree, rank,
                       std=0.3, scoring_func="sigmoid")
    held = part.held
    state = full.state_dict()
    sl = slice(rank * held, (rank + 1) * held)
    state.update({k: state[k][sl] for k in ("w_gate", "w_up", "w_down")})
    part.set_state_dict(state)
    return part


def test_the_ranks_parts_add_up_to_the_uncut_sigmoid_layer():
    """The guide's share test: each rank computes its own experts' part
    and the shared expert, which counts once; the sum is the uncut
    reference layer."""
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
    cfg = {"num_experts_per_tok": 3, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "ep_rank": 0, "rms_norm_eps": 1e-5,
           "index_topk": 8, "num_attention_heads": 4}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.Reference(cfg)._expert_layer(
            h, {"router": state["router"],
                "router_bias": state["router_bias"],
                "w_gate": state["w_gate"], "w_up": state["w_up"],
                "w_down": state["w_down"],
                "shared": (state["shared.w_gate"], state["shared.w_up"],
                           state["shared.w_down"])}, None, None, 0.0)
    np.testing.assert_allclose(total, np.asarray(want), atol=2e-4)
    np.testing.assert_allclose(np.asarray(full(h)[0]), np.asarray(want),
                               atol=2e-4)
    assert int(np.concatenate(loads).sum()) == 29 * 3


def test_sigmoid_router_by_hand():
    """The biased score chooses, the unbiased one weighs, normalised and
    scaled: expert 2 wins its place through the bias alone and is then
    weighed by its own (smaller) sigmoid."""
    moe = DroplessMoE(4, 4, 4, 2, 1, 1, 0, 2.5, True, 1, 0,
                      scoring_func="sigmoid")
    state = moe.state_dict()
    state["router"] = jnp.eye(4)
    state["router_bias"] = jnp.asarray([0.0, 0.0, 0.3, 0.0])
    moe.set_state_dict(state)
    logits = np.asarray([[2.0, 0.5, 0.0, -1.0]], np.float32)
    w, idx = moe.route(jnp.asarray(logits))
    sig = 1 / (1 + np.exp(-logits[0]))
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 2]   # 0.5 + 0.3 > 0.62
    chosen = sig[[0, 2]]
    want = dict(zip([0, 2], chosen / chosen.sum() * 2.5))
    for e, got in zip(np.asarray(idx)[0], np.asarray(w)[0]):
        assert got == pytest.approx(want[int(e)], rel=1e-5)
    with pytest.raises(Exception):
        DroplessMoE(4, 4, 4, 2, 2, 1, 0, scoring_func="sigmoid")
    with pytest.raises(Exception):
        DroplessMoE(4, 4, 4, 2, scoring_func="tanh")


def test_check_experts_by_hand():
    s = np.asarray([[0.9, 0.5, 0.45, 0.1]], np.float32)
    b = np.asarray([0.0, 0.0, 0.1, 0.0], np.float32)    # 2 overtakes 1
    assert ref.own_experts(s, b, 2)[0].tolist() == [0, 2]
    assert ref.check_experts(s, b, np.asarray([[0, 2]]), 2, 0.0)["ok"]
    near = ref.check_experts(s, b, np.asarray([[0, 1]]), 2, 0.1)
    assert near["ok"] and near["tokens_differ"] == 1
    assert near["max_margin"] == pytest.approx(0.05 / 0.55, rel=1e-4)
    assert not ref.check_experts(s, b, np.asarray([[0, 3]]), 2, 0.1)["ok"]
    assert not ref.check_experts(s, b, np.asarray([[0, 0]]), 2, 0.9)["ok"]


# -- written controls: each must move the logits by far more than rounding ------
def _no_relu(q_i, k_i, w_i):
    return jnp.einsum("ths,th->ts", jnp.einsum("thd,sd->ths", q_i, k_i), w_i)


def _no_head_weights(q_i, k_i, w_i):
    return jnp.sum(jnp.maximum(jnp.einsum("thd,sd->ths", q_i, k_i), 0.0), 1)


def _bias_in_the_weights(bias):
    def gate(scores, chosen, cfg):
        w = (scores + bias) * chosen
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * cfg["routed_scaling_factor"]
    return gate


CONTROLS = {
    "no_selection_dense": {"over": {"index_topk": 10 ** 6}},
    "top_k_minus_one": {"over": {"index_topk": 7}},
    "no_relu": {"patch": ("index_products", _no_relu)},
    "no_head_weights": {"patch": ("index_products", _no_head_weights)},
    "no_rotary_in_the_indexer": {
        "patch": ("index_rotate", lambda x, cos, sin, rope: x)},
    "selection_sees_the_future": {
        "patch": ("may_see", lambda t, s: jnp.ones((t.shape[0], s), bool))},
    "bias_added_into_the_gate_weights": {"patch": ("gate_weights", None)},
    "no_normalisation": {"over": {"norm_topk_prob": False}},
    "no_scaling_factor": {"over": {"routed_scaling_factor": 1.0}},
    "softmax_for_sigmoid": {
        "patch": ("router_scores",
                  lambda h, w: jax.nn.softmax(h @ w.astype(jnp.float32),
                                              axis=-1))},
}


@pytest.mark.parametrize("fault", sorted(CONTROLS))
def test_a_broken_reference_disagrees_by_far_more_than_rounding(
        served, fault, monkeypatch):
    cfg, _, eng, prompts, results = served
    got = np.stack([np.stack(r["logits"]) for r in results])
    control = CONTROLS[fault]
    if "patch" in control:
        name, fn = control["patch"]
        if fn is None:                 # needs the model's own bias
            bias = eng._params["layers.1.mlp.router_bias"]
            # one bias for both expert layers is fault enough
            fn = _bias_in_the_weights(jnp.asarray(bias))
        monkeypatch.setattr(ref, name, fn)
    bad, _ = _reference_logits(cfg, eng, prompts, results,
                               **control.get("over", {}))
    assert np.abs(got - bad).max() > 0.02      # 1000 x the agreement above


# -- the lift: long chunks, chunked feed-forwards -------------------------------
def test_a_long_chunk_takes_the_blocked_prefill_and_reads_the_same(
        monkeypatch):
    """Without an indexer (DeepSeek-V2's route): past the score budget a
    chunk is prefilled in blocks of queries, where ``_causal_attention``
    would have fallen to one head at a time; one result."""
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                               deepseek_v2_tiny)
    pt.seed(4)
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny(initializer_range=0.2))
    params = model.state_dict()
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 96, (2, 32)))
    plain = np.asarray(model.apply(params, ids))
    monkeypatch.setattr(latent_decoder, "_SCORE_BUDGET", 32 * 32 - 1)
    monkeypatch.setattr(latent_decoder, "_PREFILL_HEADS", 2)
    blocked = np.asarray(model.apply(params, ids))
    np.testing.assert_allclose(blocked, plain, atol=3e-5)
    with pytest.raises(Exception, match="blocked prefill"):
        latent_decoder._causal_attention(
            jnp.zeros((1, 32, 2, 4)), jnp.zeros((1, 32, 2, 4)),
            jnp.zeros((1, 32, 2, 4)), jnp.asarray([32]), 1.0)


def test_feed_forwards_in_chunks_of_tokens_read_the_same(monkeypatch):
    pt.seed(5)
    cfg = glm5_tiny(initializer_range=0.2)
    model = Glm5ForCausalLM(cfg)
    params = model.state_dict()
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 96, (1, 64)))
    whole = np.asarray(model.apply(params, ids))
    monkeypatch.setattr(decoder_stack, "_FFN_TOKENS", 16)
    parts = np.asarray(model.apply(params, ids))
    np.testing.assert_allclose(parts, whole, atol=3e-5)


def test_padding_blocks_and_parts_of_a_bucket_are_skipped_and_change_nothing(
        monkeypatch):
    """A prompt of 19 tokens in a bucket of 64: the blocked prefill visits
    the 3 blocks of 8 queries that hold a real token and the feed-forwards
    the 2 parts of 16, and the logits are the whole-sequence ones."""
    monkeypatch.setattr(decoder_stack, "_FFN_TOKENS", 16)
    pt.seed(6)
    cfg = glm5_tiny(initializer_range=0.2)
    model = Glm5ForCausalLM(cfg)
    eng = ServingEngine(model, max_seqs=2, kv_block_size=8, max_model_len=96,
                        capture_logits=True, registry=MetricsRegistry())
    prompt = np.random.default_rng(3).integers(0, 96, 35).tolist()
    rid = eng.submit(prompt, max_new_tokens=3)
    eng.run()
    got = eng.collect(rid)
    seq = prompt + got["tokens"][:-1]
    plain = np.asarray(model.apply(eng._params, jnp.asarray([seq])))[0]
    np.testing.assert_allclose(np.stack(got["logits"]),
                               plain[len(prompt) - 1:], atol=3e-5)
    assert int(sa._live_blocks(jnp.asarray(19), 8, 0, 4)) == 3
    assert int(sa._live_blocks(jnp.asarray(19), 8, 4, 4)) == 0
    assert int(sa._live_blocks(jnp.asarray(64), 8, 4, 4)) == 4


def test_published_sizes_are_the_defaults():
    c = Glm5Config()
    assert (c.hidden_size, c.num_heads, c.q_lora_rank, c.kv_lora_rank) \
        == (6144, 64, 2048, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) \
        == (192, 64, 256)
    assert (c.index_n_heads, c.index_head_dim, c.index_topk) \
        == (32, 128, 2048)
    assert c.latent_width == 576 and c.latent_row == 640
    assert c.softmax_scale == pytest.approx(256 ** -0.5)
    cos, sin = c.rotary(jnp.asarray([[0, 1]]))
    assert cos.shape == (1, 2, 32)
    assert float(sin[0, 1, 0]) == pytest.approx(np.sin(1.0))
    assert float(sin[0, 1, 31]) == pytest.approx(
        np.sin(1e6 ** (-62 / 64)), rel=1e-4)
