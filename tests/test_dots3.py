"""dots3-note-prev on the serving path, at a small size on the
CPU, float32: one full layer, then the published period (three sliding
layers, one full), a window of 12 in pages of 4 and ``index_topk`` 8, so
sequences of up to 56 tokens both slide and select.  The engine (prefill,
then decode through a page pool a layer kind) against the benchmark's plain
reference (``perfbench/reference/dots3.py``: imported, no second copy of the
equations), the share test, the window kernel against its oracle, and
written controls: each broken piece of the mathematics moves the logits by
far more than the agreement."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference import sparse_attention as sa
from paddle_tpu.inference.kv_cache import WindowLayer, window_table_width
from paddle_tpu.models.dots3 import (FULL, SLIDING, Dots3Config,
                                     Dots3ForCausalLM, dots3_tiny)
from paddle_tpu.models.latent_decoder import LatentShape
from paddle_tpu.nn.dropless_moe import DroplessMoE
from paddle_tpu.observability.registry import MetricsRegistry
from perfbench.builders.dots3 import Dots3System
from perfbench.reference import dots3 as ref

# the module (the package exports its function under the same name)
la = importlib.import_module("paddle_tpu.inference.latent_attention")

NEW = 6
# float32 through five layers to logits of up to 6: the engine read 3e-6 to
# 6e-6 against its own whole-sequence forward; every written control moves
# them by over 1e-2
ATOL = 2e-5
LENGTHS = (50, 5, 12, 30)      # past the window and index_topk, inside, equal


def reference_cfg(c, **over):
    """The reference's view (the configuration file's keys) of a model
    config."""
    cfg = {"num_hidden_layers": c.num_layers,
           "layer_types": list(c.layer_types),
           "sliding_window_size": c.sliding_window,
           "num_attention_heads": c.num_heads, "q_lora_rank": c.q_lora_rank,
           "kv_lora_rank": c.kv_lora_rank,
           "qk_nope_head_dim": c.qk_nope_head_dim,
           "qk_rope_head_dim": c.qk_rope_head_dim,
           "v_head_dim": c.v_head_dim, "rope_theta": c.rope_theta,
           "index_n_heads": c.index_n_heads,
           "index_head_dim": c.index_head_dim, "index_topk": c.index_topk,
           "attention_gate_type": "headwise",
           "swa_num_attention_heads": c.swa_num_heads,
           "swa_q_lora_rank": c.swa_q_lora_rank,
           "swa_kv_lora_rank": c.swa_kv_lora_rank,
           "swa_qk_nope_head_dim": c.swa_qk_nope_head_dim,
           "swa_qk_rope_head_dim": c.swa_qk_rope_head_dim,
           "swa_v_head_dim": c.swa_v_head_dim,
           "swa_rope_theta": c.swa_rope_theta,
           "swa_attention_gate_type": "headwise",
           "rms_norm_eps": c.rms_norm_eps,
           "num_experts_per_tok": c.num_experts_per_tok,
           "norm_topk_prob": c.norm_topk_prob,
           "scoring_func": c.scoring_func,
           "routed_scaling_factor": c.routed_scaling_factor,
           "ep_degree": c.ep_degree, "ep_rank": c.ep_rank}
    cfg.update(over)
    return cfg


def reference_params(params, layers):
    """The program's parameters under the reference's names: the
    benchmark builder's own mapping."""
    return Dots3System({"num_hidden_layers": layers},
                       0).reference_params(params)


def _engine(cfg, **kw):
    pt.seed(39)
    model = Dots3ForCausalLM(cfg)
    args = dict(max_seqs=4, kv_block_size=4, max_model_len=96,
                capture_logits=True, registry=MetricsRegistry())
    args.update(kw)
    return model, ServingEngine(model, **args)


@functools.lru_cache(maxsize=None)
def _served(rank):
    """A tiny model (one rank of 4, a sliced vocabulary) served through the
    engine.  Rank 0 is the rank the cell runs."""
    cfg = dots3_tiny(ep_degree=4, ep_rank=rank, initializer_range=0.2)
    model, eng = _engine(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in LENGTHS]
    rids = [eng.submit(p, max_new_tokens=NEW) for p in prompts]
    eng.run()
    return cfg, model, eng, prompts, [eng.collect(r) for r in rids]


@pytest.fixture(scope="module", params=[0, 1], ids=["rank0", "rank1"])
def served(request):
    return _served(request.param)


@pytest.fixture(scope="module")
def served0():
    """Rank 0 alone: the written controls need one rank to show that each
    piece moves the logits."""
    return _served(0)


def _choices(results):
    routing = [np.swapaxes(r["per_token"]["moe_topk"], 0, 1)
               for r in results]
    selections = [np.swapaxes(r["per_logit"]["dsa_selected"], 0, 1)
                  for r in results]
    return routing, selections


def _reference_logits(cfg, eng, prompts, results, routing=None,
                      selections=None, limits=None, reference=ref.Reference,
                      params=None, **over):
    width = max(len(p) for p in prompts) + NEW
    ids = np.zeros((len(prompts), width), np.int32)
    pos = np.zeros((len(prompts), NEW), np.int32)
    for i, (p, r) in enumerate(zip(prompts, results)):
        seq = p + r["tokens"][:NEW - 1]
        ids[i, :len(seq)] = seq
        pos[i] = np.arange(len(p) - 1, len(p) - 1 + NEW)
    if params is None:
        params = reference_params(eng._params, cfg.num_layers)
    return reference(reference_cfg(cfg, **over), query_block=8,
                     head_group=2).logits_at(
        params, ids, pos, pos[:, -1] + 1, routing, selections, limits)


def _got(results):
    return np.stack([np.stack(r["logits"]) for r in results])


# -- the engine against the plain reference ---------------------------------
def test_engine_prefill_and_decode_agree_with_the_plain_reference(served):
    cfg, _, eng, prompts, results = served
    routing, selections = _choices(results)
    assert selections[0].shape == (2, NEW, cfg.index_topk)  # 2 full layers
    want, report = _reference_logits(cfg, eng, prompts, results, routing,
                                     selections)
    assert report["ok"] and report["routing"]["tokens_differ"] == 0
    assert report["index"]["entries_differ"] == 0
    assert report["routing"]["tokens"] == 4 * sum(len(p) + NEW - 1
                                                  for p in prompts)
    assert np.abs(want).max() > 0.5            # logits worth comparing
    np.testing.assert_allclose(_got(results), want, atol=ATOL)
    # and under the reference's OWN choices: float32 has no near-ties
    own, _ = _reference_logits(cfg, eng, prompts, results)
    np.testing.assert_allclose(_got(results), own, atol=ATOL)


def test_whole_model_logits_agree_with_the_plain_reference(served):
    """The cache-free forward (the indexer's blocked prefill and the
    banded one on the whole sequence) against the reference at every
    position of one sequence."""
    cfg, model, eng, prompts, results = served
    seq = prompts[0] + results[0]["tokens"][:NEW - 1]
    got = np.asarray(model.apply(eng._params, jnp.asarray([seq])))[0]
    want, _ = ref.Reference(reference_cfg(cfg), query_block=8).logits_at(
        reference_params(eng._params, cfg.num_layers), np.asarray([seq]),
        np.arange(len(seq))[None], [len(seq)])
    np.testing.assert_allclose(got, want[0], atol=ATOL)


def test_both_pools_were_used_and_the_counts_are_booked(served):
    cfg, _, eng, prompts, _ = served
    pools = eng.stats()["kv_pools"]
    assert set(pools) == {"full", "window"}
    width = window_table_width(cfg.sliding_window, 4)
    assert width == 4 and pools["window"]["high_water"] <= 4 * width
    assert pools["window"]["freed_behind"] > 0
    assert pools["full"]["used"] == pools["window"]["used"] == 0
    assert eng.cache.leak_report()["leaked_blocks"] == 0
    # 2 full layers of a 128-value row and a 16-value index key, 3 window
    # layers of one 128-value row, float32
    assert pools["full"]["block_bytes"] == 2 * 4 * (128 + 16) * 4
    assert pools["window"]["block_bytes"] == 3 * 4 * 128 * 4
    counts = eng.stats()["model_counts"]["counters"]
    assert counts["serve.dsa_context_tokens"] > counts[
        "serve.dsa_selected_tokens"] > 0
    assert counts["serve.attn_window_tokens"] > 0
    assert counts["serve.moe_pairs_dropped"] == 0
    assert eng.stats()["model_gauges"] == {
        "serve.kv_full_bytes_per_token": 2 * (128 + 16) * 4.0,
        "serve.kv_window_bytes_per_seq": 3 * 128 * 4.0 * 12}


def test_the_window_tokens_of_a_step_are_min_context_window_a_row():
    cfg = dots3_tiny(initializer_range=0.2)
    model, eng = _engine(cfg, max_seqs=2)
    eng.submit(list(range(20)), max_new_tokens=3)
    eng.submit(list(range(7)), max_new_tokens=3)
    eng.run()
    # decode steps at contexts (21, 8), (22, 9): min(., 12) x 3 layers
    assert eng.stats()["model_counts"]["counters"][
        "serve.attn_window_tokens"] == 3 * ((12 + 8) + (12 + 9))


def test_the_layout_declares_each_layers_kind():
    model = Dots3ForCausalLM(dots3_tiny())
    full, s1, s2, s3, last = model.kv_cache_layout()
    assert full == last == ((128,), (16,))
    assert s1 == s2 == s3 == WindowLayer(((128,),), 12)
    c = Dots3Config(num_layers=5, layer_types=(FULL, SLIDING, SLIDING,
                                               SLIDING, FULL))
    assert (c.full.latent_width, c.full.latent_row) == (576, 640)
    assert (c.sliding.latent_width, c.sliding.latent_row) == (1088, 1152)
    assert c.full.softmax_scale == 192 ** -0.5
    assert c.sliding.softmax_scale == 256 ** -0.5
    assert (c.full.index_topk, c.sliding.index_topk) == (2048, 0)
    assert (c.full.window, c.sliding.window) == (None, 513)
    assert c.full.attention_gate and c.sliding.attention_gate
    layout = Dots3ForCausalLM.kv_cache_layout(
        type("M", (), {"config": c, "_shapes":
                       Dots3ForCausalLM._shapes})())
    assert layout[0] == ((640,), (128,))
    assert layout[1] == WindowLayer(((1152,),), 513)
    whole = Dots3Config()
    assert whole.layer_types.count(FULL) == 13
    assert whole.layer_types.count(SLIDING) == 33
    assert whole.layer_types[:6] == (FULL, FULL, SLIDING, SLIDING, SLIDING,
                                     FULL)


def test_a_model_of_one_kind_keeps_its_own_shape():
    """DeepSeek-V2's and GLM-5's configurations are their own layers'
    shape: no window, no gate (their programs: test_latent_programs.py)."""
    from paddle_tpu.models.glm5 import glm5_tiny
    c = glm5_tiny()
    assert isinstance(c, LatentShape) and c.layer_shape(2) is c
    assert c.window is None and c.attention_gate is False


# -- written controls: each piece of the mathematics moves the logits --------
CONTROLS = {
    "no_gate": dict(attention_gate_type=None, swa_attention_gate_type=None),
    "window_one_shorter": dict(sliding_window_size=11),
    "window_one_longer": dict(sliding_window_size=13),
    "one_rotary_base": dict(swa_rope_theta=dots3_tiny().rope_theta),
    "no_selection_in_full_layers": dict(index_topk=10 ** 6),
    "no_topk_normalisation": dict(norm_topk_prob=False),
    "softmax_for_sigmoid": dict(scoring_func="softmax"),
}


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_a_reference_with_one_piece_broken_disagrees(served0, name):
    cfg, _, eng, prompts, results = served0
    broken, _ = _reference_logits(cfg, eng, prompts, results,
                                  **CONTROLS[name])
    assert np.abs(_got(results) - broken).max() > 1e-2, name


class GateAfterO(ref.Reference):
    """The headwise gate on ``W_o``'s output, a head's share of the
    stream's width each, not on the heads' outputs."""

    @staticmethod
    def gated_output(att, gate, o):
        y = att.reshape(att.shape[0], -1) @ ref._f32(o)
        return (y.reshape(y.shape[0], gate.shape[1], -1)
                * gate[..., None]).reshape(y.shape)


class FullWidthsInSliding(ref.Reference):
    """A sliding layer computed with the full kind's query width in its
    scale (the only width that fits the sliding layer's own weights)."""

    def _scale(self, kind):
        if kind["window"] is None:
            return super()._scale(kind)
        return (self.cfg["qk_nope_head_dim"]
                + self.cfg["qk_rope_head_dim"]) ** -0.5


class SelectionInSliding(ref.Reference):
    """An indexer in the sliding layers too (layer 0's weights: the query
    rank and the stream are the same widths): 8 of a window of 12."""

    def _kind(self, layer):
        return dict(super()._kind(layer), index=True)


@pytest.mark.parametrize("name,reference", [
    ("gate_after_o", GateAfterO),
    ("full_kind_widths_in_a_sliding_layer", FullWidthsInSliding),
    ("selection_in_sliding_layers", SelectionInSliding)])
def test_a_reference_with_a_piece_moved_disagrees(served0, name, reference):
    cfg, _, eng, prompts, results = served0
    params = reference_params(eng._params, cfg.num_layers)
    if reference is SelectionInSliding:
        index = {k: v for k, v in params["layers"][0].items()
                 if k.startswith("index_")}
        for layer in params["layers"]:
            layer.update({k: v for k, v in index.items() if k not in layer})
    broken, _ = _reference_logits(cfg, eng, prompts, results,
                                  reference=reference, params=params)
    assert np.abs(_got(results) - broken).max() > 1e-2, name


def test_a_handed_choice_outside_the_epsilon_fails(served0):
    cfg, _, eng, prompts, results = served0
    routing, selections = _choices(results)
    selections = [s.copy() for s in selections]
    row = selections[0][0, 0]                  # 50-token prompt's last query
    row[row == row.max()] = -1                 # one selected entry dropped
    logits, report = _reference_logits(
        cfg, eng, prompts, results, routing, selections,
        {"index_tie_eps": 100.0, "index_differ_share": 1.0})
    assert not report["ok"] and np.isnan(logits).all()


# -- the share -----------------------------------------------------------------
def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """What each of 16 chips computes of an expert layer (its 1 of 16
    held experts, and the shared expert, which every chip computes alike
    and which is counted once) adds up to the uncut reference's layer."""
    pt.seed(7)
    args = dict(hidden_size=32, expert_width=16, num_experts=16, top_k=3,
                n_shared_experts=1, norm_topk_prob=True,
                scoring_func="sigmoid", std=0.3)
    whole = DroplessMoE(**args)
    sd = whole.state_dict()
    h = jnp.asarray(np.random.default_rng(0).normal(size=(11, 32)),
                    jnp.float32)
    total = 0.0
    for rank in range(16):
        part = DroplessMoE(ep_degree=16, ep_rank=rank, **args)
        own = dict(sd, **{k: sd[k][rank:rank + 1]
                          for k in ("w_gate", "w_up", "w_down")})
        if rank:                        # the shared expert once, not 16x
            own.update({k: jnp.zeros_like(v) for k, v in sd.items()
                        if k.startswith("shared.")})
        total = total + part.apply(own, h)[0]
    cfg = {"num_experts_per_tok": 3, "norm_topk_prob": True,
           "routed_scaling_factor": 1.0, "scoring_func": "sigmoid",
           "ep_rank": 0, "rms_norm_eps": 1e-5, "num_hidden_layers": 0,
           "layer_types": [], "index_topk": 8}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.Reference(cfg)._expert_layer(
            h, {"router": sd["router"], "router_bias": sd["router_bias"],
                "w_gate": sd["w_gate"], "w_up": sd["w_up"],
                "w_down": sd["w_down"],
                "shared": (sd["shared.w_gate"], sd["shared.w_up"],
                           sd["shared.w_down"])}, None, None, 0.0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-6)


# -- the window kernel against its oracle --------------------------------------
def _ring_case(rng, lens, window, bs=4, heads=4, width_vals=128, blocks=48):
    """Pages and ring tables for rows of the lengths ``lens``: block ``b``
    of a row at column ``b % width``, only the blocks its window reaches."""
    width = window_table_width(window, bs)
    pages = rng.normal(size=(blocks, bs, width_vals)).astype(np.float32)
    tables = np.zeros((len(lens), width), np.int32)
    free = list(rng.permutation(blocks))
    for i, n in enumerate(lens):
        for b in range(max(0, n - window) // bs, -(-n // bs)):
            tables[i, b % width] = free.pop()
    q = rng.normal(size=(len(lens), heads, width_vals)).astype(np.float32)
    return q, pages, tables, np.asarray(lens, np.int32)


def _plain_window(q, pages, tables, lens, value_width, scale, window, bs=4):
    """One query a row against its own last ``window`` tokens, gathered a
    position at a time: no ring arithmetic shared with the code under
    test."""
    width = tables.shape[1]
    out = np.zeros(q.shape[:2] + (value_width,), np.float32)
    for i, n in enumerate(lens):
        if n == 0:
            continue
        pos = np.arange(max(0, n - window), n)
        rows = pages[tables[i, (pos // bs) % width], pos % bs]
        s = q[i] @ rows.T * scale
        e = np.exp(s - s.max(-1, keepdims=True))
        out[i] = (e / e.sum(-1, keepdims=True)) @ rows[:, :value_width]
    return out


# a window of 12 in pages of 4 is a ring of 4: contexts shorter than the
# window, exactly it, one block past it, and several times round the ring
WINDOW_LENS = [5, 0, 12, 16, 17, 47, 1, 30]


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_window_decode_against_a_plain_gather(impl):
    rng = np.random.default_rng(39)
    q, pages, tables, lens = _ring_case(rng, WINDOW_LENS, 12)
    want = _plain_window(q, pages, tables, lens, 96, 0.1, 12)
    fn = (la.latent_attention_reference if impl == "reference" else
          lambda *a: la.latent_window_attention_pallas(
              *a[:4], value_width=a[4], scale=a[5], window=a[6],
              interpret=True))
    got = np.asarray(fn(*map(jnp.asarray, (q, pages, tables, lens)), 96,
                        0.1, 12))
    np.testing.assert_allclose(got, want, atol=3e-6)
    assert not got[1].any()                      # the empty row


def test_the_window_kernel_reads_the_windows_blocks_only():
    """NaN in every block no row's window reaches: the kernel's output is
    finite and the oracle's."""
    rng = np.random.default_rng(40)
    q, pages, tables, lens = _ring_case(rng, [13, 30, 5, 24], 12)
    held = {int(tables[i, b % tables.shape[1]]) for i, n in enumerate(lens)
            for b in range(max(0, n - 12) // 4, -(-int(n) // 4))}
    pages[np.setdiff1d(np.arange(48), sorted(held))] = np.nan
    got = np.asarray(la.latent_window_attention_pallas(
        *map(jnp.asarray, (q, pages, tables, lens)), value_width=96,
        scale=0.1, window=12, interpret=True))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, _plain_window(q, pages, tables, lens, 96, 0.1, 12), atol=3e-6)


def test_a_freed_window_block_is_never_read():
    """Decode one sequence far past the window while NaN is written into
    every window-pool block the sequence does not hold, after every step:
    the logits stay the plain forward's."""
    cfg = dots3_tiny(initializer_range=0.2)
    model, eng = _engine(cfg, max_seqs=2)
    prompt = np.random.default_rng(9).integers(0, 96, 21).tolist()
    rid = eng.submit(prompt, max_new_tokens=14)
    pool = eng.cache.pools["window"]
    while eng.has_work():
        eng.step()
        free = np.setdiff1d(np.arange(pool.num_blocks),
                            pool.tables.get(rid, []))
        pages = list(eng.cache.pages)
        for i in pool.layers:
            pages[i] = tuple(a.at[jnp.asarray(free)].set(jnp.nan)
                             for a in pages[i])
        eng.cache.update_pages(pages)
    got = eng.collect(rid)
    assert pool.freed_behind >= 3
    seq = prompt + got["tokens"][:-1]
    plain = np.asarray(model.apply(eng._params, jnp.asarray([seq])))[0]
    assert np.isfinite(np.stack(got["logits"])).all()
    np.testing.assert_allclose(np.stack(got["logits"]),
                               plain[len(prompt) - 1:], atol=ATOL)


# -- the full layers' walk over the live latent pages --------------------------
@pytest.mark.parametrize("bs", [4, 8])
def test_the_walk_reads_the_rows_live_pages_only(bs):
    """NaN in every block no row holds (but block 0, which the oracle
    gathers where a row has no position): the walk's output is finite and
    the oracle's, at the full kind's widths (rows of 640, values of 512):
    a dead slot of a wave and a dead wave name a block the row holds."""
    rng = np.random.default_rng(bs)
    lens, t, blocks = [23, 0, 2 * bs, 13, 30], 32 // bs, 64
    tables = rng.permutation(blocks)[:len(lens) * t].reshape(len(lens), t)
    pages = rng.normal(size=(blocks, bs, 640)).astype(np.float32)
    held = {int(tables[i, k]) for i, n in enumerate(lens)
            for k in range(-(-n // bs))}
    pages[np.setdiff1d(np.arange(1, blocks), sorted(held))] = np.nan
    live = np.arange(t * bs)[None, :] < np.asarray(lens)[:, None]
    scores = jnp.asarray(np.where(live, rng.normal(size=live.shape), -np.inf),
                         jnp.float32)
    q = jnp.asarray(rng.normal(size=(len(lens), 8, 640)), jnp.float32)
    pages, tables = jnp.asarray(pages), jnp.asarray(tables, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    sel = sa.dsa_select(scores, 8)
    got = np.asarray(sa.dsa_sparse_attn_pallas(
        q, pages, tables, lens, scores, sel.threshold, sel.last_tied, 512,
        0.05, pages_per_step=3, interpret=True))
    assert np.isfinite(got).all() and not got[1].any()
    np.testing.assert_allclose(got, np.asarray(sa.dsa_sparse_attn_reference(
        q, pages, sa.dsa_slots(sel.positions, tables, bs), sel.counts, 512,
        0.05)), atol=2e-5)


def test_decode_through_the_walk_gives_the_gather_routes_logits(
        monkeypatch):
    """The engine's decode steps with the TPU's route (the index kernel and
    the walk, interpreted) against the same engine off the TPU (the gather
    oracle): the same logits and the same selected positions."""
    cfg, _, _, prompts, want = _served(0)
    monkeypatch.setattr(sa, "_on_tpu", lambda: True)
    _, eng = _engine(cfg)
    rids = [eng.submit(p, max_new_tokens=NEW) for p in prompts]
    eng.run()
    got = [eng.collect(r) for r in rids]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g["per_logit"]["dsa_selected"],
                                      w["per_logit"]["dsa_selected"])
    np.testing.assert_allclose(_got(got), _got(want), atol=ATOL)
