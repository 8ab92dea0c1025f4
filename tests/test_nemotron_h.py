"""Nemotron-H (``models/nemotron_h.py``) against its plain reference
(``perfbench/reference/nemotron_h.py``: the Mamba-2 layers as their
sequential recurrence) at a small size on the CPU: the forward pass, prefill
then decode through the engine's state slots and pages, slots reused, a row
discarded by a unit launched ahead, preemption and a faulted unit; the
decode kernel and the chunked scan against their oracles; the expert share;
and the written controls, each a reading of the published description that
the model is not, which must move the logits by more than the tolerance."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference import ssm_state
from paddle_tpu.inference.kv_cache import STATE
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                          nemotron_h_tiny)
from paddle_tpu.nn.dropless_moe import DroplessMoE
from paddle_tpu.testing.faults import poison_request
from perfbench.builders.nemotron_h import NemotronHSystem
from perfbench.reference import nemotron_h as ref

TOL = 1e-3          # float32 program against float32 reference
MOVED = 1e-2        # what a written control must move the logits by
VOCAB = 96
PROMPTS = (13, 29, 5)   # no multiple of the tiny chunk (8) or of a bucket


def _config(**kw):
    return nemotron_h_tiny(ep_degree=4, ep_rank=0, initializer_range=0.2,
                           **kw)


def _ref_cfg(c):
    return {"hybrid_override_pattern": c.hybrid_override_pattern,
            "layer_norm_epsilon": c.rms_norm_eps,
            "mamba_num_heads": c.mamba_num_heads,
            "mamba_head_dim": c.mamba_head_dim, "n_groups": c.n_groups,
            "ssm_state_size": c.ssm_state_size,
            "conv_kernel": c.conv_kernel, "head_dim": c.head_dim,
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": c.norm_topk_prob,
            "routed_scaling_factor": c.routed_scaling_factor,
            "n_routed_experts": c.n_routed_experts,
            "ep_degree": c.ep_degree, "ep_rank": c.ep_rank,
            "rope_theta": 10000.0}


@pytest.fixture(scope="module")
def model():
    jax.config.update("jax_default_matmul_precision", "highest")
    pt.seed(11)
    m = NemotronHForCausalLM(_config())
    m.eval()
    yield m
    jax.config.update("jax_default_matmul_precision", None)


def _ref_params(model):
    system = NemotronHSystem(
        {"hybrid_override_pattern": model.config.hybrid_override_pattern}, 0)
    return system.reference_params(model.state_dict())


def reference_logits(model, seq, positions, **controls):
    """The reference's logits at ``positions`` of one sequence, under its
    own routing."""
    r = ref.Reference(_ref_cfg(model.config), query_block=8, **controls)
    logits, report = r.logits_at(_ref_params(model), np.asarray([seq]),
                                 np.asarray([positions]), [len(seq)])
    assert report["ok"]
    return logits[0]


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).tolist() for n in PROMPTS]


def _engine(model, **kw):
    args = dict(max_seqs=2, kv_block_size=4, max_model_len=64,
                capture_logits=True)
    args.update(kw)
    return ServingEngine(model, **args)


def _agree(model, prompt, out, tol=TOL):
    """The engine's captured logits of one request against the reference
    fed its own tokens; returns the largest difference."""
    seq = prompt + out["tokens"][:-1]
    want = reference_logits(model, seq, list(range(len(prompt) - 1,
                                                   len(seq))))
    got = np.stack(out["logits"])
    worst = float(np.abs(got - want).max())
    assert worst <= tol, worst
    return worst


# -- the forward pass and the engine ---------------------------------------
def test_the_forward_pass_agrees_with_the_reference(model):
    seq = _prompts()[1]
    got = np.asarray(model.apply(model.state_dict(),
                                 jnp.asarray([seq], jnp.int32)))[0]
    want = reference_logits(model, seq, list(range(len(seq))))
    assert np.abs(got - want).max() <= TOL
    assert np.abs(want).max() > 1.0          # logits worth comparing


def test_prefill_then_decode_through_slots_and_pages(model):
    eng = _engine(model)
    prompts = _prompts()
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    for p, rid in zip(prompts, rids):
        _agree(model, p, eng.collect(rid))
    pools = eng.stats()["kv_pools"]
    assert set(pools) == {"full", STATE}
    # more requests than rows: a slot was taken again
    state = eng.cache.pools[STATE].allocator
    assert state.total_allocs == len(prompts) and state.num_used == 0
    assert eng.cache.leak_report()["balanced"]
    counts = eng.stats()["model_counts"]["counters"]
    assert counts["serve.ssm_slots_reset"] == len(prompts)
    assert counts["serve.ssm_state_rows"] > 0
    gauges = eng.stats()["model_gauges"]
    c = model.config
    assert gauges["serve.ssm_state_bytes_per_seq"] == 3 * 4 * (
        c.conv_kernel * c.conv_dim + c.mamba_num_heads
        * c.mamba_head_dim * c.ssm_state_size)
    eng.stop()


def _reused_engine(model):
    """Three requests through two slots, one after another: the third
    takes a slot that a finished one held."""
    eng = _engine(model, max_seqs=1, num_kv_blocks={STATE: 2})
    prompts = _prompts(3)
    rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run()
    assert eng.cache.pools[STATE].allocator.total_allocs > 2
    return eng, prompts, rids


def test_a_reused_slot_starts_from_zero(model):
    eng, prompts, rids = _reused_engine(model)
    for p, rid in zip(prompts, rids):
        _agree(model, p, eng.collect(rid))
    eng.stop()


def test_without_the_reset_a_reused_slot_fails(model, monkeypatch):
    def no_reset(tails, states, slots, fresh):
        return (jnp.take(tails, slots, axis=0, mode="clip"),
                jnp.take(states, slots, axis=0, mode="clip"))
    monkeypatch.setattr(nh, "initial_state", no_reset)
    eng, prompts, rids = _reused_engine(model)
    with pytest.raises(AssertionError):
        for p, rid in zip(prompts, rids):
            _agree(model, p, eng.collect(rid))
    eng.stop()


def test_a_discarded_row_never_writes_a_slot_a_new_request_holds(model):
    """A request that ends on its end-of-sequence token leaves a row in the
    unit launched after it, discarded at that unit's landing: its slot is
    held until then, so no prefill launched ahead of that landing takes
    it, and every request agrees."""
    prompts = _prompts(5)
    probe = _engine(model)
    first = probe.collect(probe.submit(prompts[0], max_new_tokens=6))
    probe.stop()
    eos = first["tokens"][2]
    eng = _engine(model, num_kv_blocks={STATE: 2})
    clashes, held = [], []
    launch = eng._launch

    def watched(kind, seqs, bucket, prev, number):
        slots = {eng.cache.table(s.request_id, STATE)[0] for s in seqs
                 if eng.cache.table(s.request_id, STATE)}
        if prev is not None and kind == "prefill":
            clashes.append(slots & prev.slots)
        unit = launch(kind, seqs, bucket, prev, number)
        unit.slots = slots
        return unit
    eng._launch = watched
    rids = [eng.submit(prompts[0], max_new_tokens=6, eos_token_id=eos)]
    rids += [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    while eng.has_work():
        eng.step()
        held.append(len(eng.cache.pools[STATE].held))
    assert eng.stats()["ahead"]["ahead_rows_discarded"] > 0
    assert not any(clashes), clashes
    assert max(held) > 0
    first = eng.collect(rids[0])
    assert first["finish_reason"] == "eos"
    for p, rid in zip(prompts, rids):
        _agree(model, p, eng.collect(rid))
    assert eng.cache.leak_report()["balanced"]
    eng.stop()


def test_a_preempted_request_resumes_by_recompute(model):
    eng = _engine(model, num_kv_blocks={"full": 14})
    prompts = _prompts(7)
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts[:2]]
    eng.run()
    outs = [eng.collect(r) for r in rids]
    assert sum(o["preemptions"] for o in outs) > 0
    for p, out in zip(prompts, outs):
        _agree(model, p, out)
    eng.stop()


def test_a_faulted_unit_is_rebuilt_and_agrees(model):
    """A decode unit that raises for one request: that request is
    quarantined, and the others, whose states the unit, its probes and the
    unit dropped after it stepped in place, start over by recompute and
    agree with the reference (a second step of a state would not)."""
    prompts = _prompts(9)
    eng = _engine(model, step_fault=poison_request(1, mode="raise",
                                                   kinds=("decode",)))
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts[:2]]
    eng.run()
    assert eng.stats()["resilience"]["quarantined"] == [rids[1]]
    out = eng.collect(rids[0])
    assert out["preemptions"] >= 1 and len(out["tokens"]) == 6
    _agree(model, prompts[0], out)
    clean = _engine(model)
    again = clean.collect(clean.submit(prompts[0], max_new_tokens=6))
    assert again["tokens"] == out["tokens"]
    np.testing.assert_allclose(np.stack(again["logits"]),
                               np.stack(out["logits"]), atol=TOL)
    eng.stop()
    clean.stop()


@pytest.mark.parametrize("kept", ["float32", "bfloat16"])
def test_the_state_check_sees_the_precision_the_state_is_kept_in(
        model, monkeypatch, kept):
    """The builder's state check (``state_step_error``) on a float32 engine:
    against the float32 reference it reads little; against a reference
    whose state is kept in bf16 between steps, as a program that stored it
    so would, it reads the rounding, at once, in the heads that keep their
    state."""
    if kept == "bfloat16":
        monkeypatch.setattr(ref, "kept_state", lambda s: jax.lax.
                            reduce_precision(s, 8, 7))
    system = NemotronHSystem(
        {"hybrid_override_pattern": model.config.hybrid_override_pattern}, 0)
    system.engine = _engine(model)
    prompts, new = _prompts(13), 4
    ids = np.zeros((len(prompts), 64), np.int32)
    positions = np.zeros((len(prompts), new), np.int32)
    for b, p in enumerate(prompts):
        ids[b, :len(p)] = p
        positions[b] = np.arange(len(p) - 1, len(p) - 1 + new)
    state = system._state_check(
        ref.Reference(_ref_cfg(model.config), query_block=8),
        _ref_params(model), ids, positions)
    readings = system.state_readings
    mamba = model.config.hybrid_override_pattern.count("M")
    assert readings["step"].shape == (
        len(prompts) * mamba, model.config.mamba_num_heads)
    assert 0 < state["slow_heads"] < state["heads"]
    if kept == "float32":               # 1.5e-6 when written
        assert state["state_step_error"] < 1e-4, state
    else:                               # 1.6e-2
        assert state["state_step_error"] > 1e-3, state
    assert system.engine.cache.leak_report()["balanced"]
    system.engine.stop()


# -- the kernels ---------------------------------------------------------
# (groups, the groups a VMEM budget fits, the G the rule picks from them):
# the public entry, whose rule takes every group at these widths; budgets
# of 1, 2, 4 and all 8 groups; and one of 5 of 6, which 5 does not divide
@pytest.mark.parametrize("groups,fit,gs", [(2, None, None), (8, 1, 1),
                                           (8, 2, 2), (8, 4, 4), (8, 8, 8),
                                           (6, 5, 3)])
def test_the_decode_kernel_matches_its_oracle(groups, fit, gs):
    r = np.random.default_rng(0)
    h, p, n, g, rows, slots = 8 if gs is None else 2 * groups, 16, 128, \
        groups, 6, 7
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    states = f(slots + 1, h, p, n)
    at = jnp.asarray([5, 0, 3, 6, 7, 7], jnp.int32)     # out of order
    lens = jnp.asarray([9, 4, 1, 30, 0, 0], jnp.int32)  # two padding rows
    x, dt = f(rows, h, p), jnp.abs(f(rows, h)) * 0.3
    a, d = -jnp.exp(f(h)), f(h)
    bm, cm = f(rows, g, n), f(rows, g, n)
    tails, new = f(slots + 1, 4, 128 * g), f(rows, 4, 128 * g)
    y0, s0, t0 = ssm_state.ssm_state_decode_reference(
        states, tails, new, at, lens, x, dt, a, bm, cm, d)
    if gs is None:
        y1, s1, t1 = ssm_state.ssm_state_decode_pallas(
            states, tails, new, at, lens, x, dt, a, bm, cm, d,
            interpret=True)
    else:
        state_b, other_b = ssm_state._group_bytes(h // g, p, n, 4, 128,
                                                  tails.dtype)
        budget = (4 * fit * (state_b + other_b)
                  + ssm_state._GROUP_TEMPS * state_b)
        assert ssm_state._groups_a_step(g, state_b, other_b,
                                        vmem=budget) == gs
        y1, s1, t1 = ssm_state._decode_call(
            states, tails, new, at, lens, x, dt, a, bm, cm, d, gs=gs,
            interpret=True)
    np.testing.assert_allclose(np.asarray(y1)[:4], np.asarray(y0)[:4],
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1)[:slots],
                               np.asarray(s0)[:slots], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(t1)[:slots],
                                  np.asarray(t0)[:slots])
    np.testing.assert_array_equal(np.asarray(t1)[[5, 0, 3, 6]],
                                  np.asarray(new)[:4])
    np.testing.assert_array_equal(np.asarray(t1)[[1, 2, 4]],
                                  np.asarray(tails)[[1, 2, 4]])
    # the rows' windows gathered from the tails by DMA
    np.testing.assert_array_equal(
        np.asarray(ssm_state._tail_read_pallas(tails, at, interpret=True)),
        np.asarray(tails)[np.asarray(at)])
    # the live rows' slots moved, the others are as they were
    moved = np.abs(np.asarray(s1) - np.asarray(states)).max(axis=(1, 2, 3))
    assert (moved[[5, 0, 3, 6]] > 0).all()
    assert (moved[[1, 2, 4]] == 0).all()


@pytest.mark.parametrize("length,chunk", [(37, 8), (16, 16), (5, 8)])
def test_the_chunked_scan_matches_the_recurrence(length, chunk):
    r = np.random.default_rng(length)
    b, h, p, n, g = 2, 8, 4, 16, 2
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    x, dt = f(b, length, h, p), jnp.abs(f(b, length, h)) * 0.3
    a, d = -jnp.exp(f(h)), f(h)
    bm, cm, s = f(b, length, g, n), f(b, length, g, n), f(b, h, p, n)
    with jax.default_matmul_precision("highest"):
        y, final = ssm_state.ssm_chunk_scan(x, dt, a, bm, cm, d, s, chunk)
    st, ys = np.asarray(s), []
    for t in range(length):
        bh = np.repeat(np.asarray(bm[:, t]), h // g, axis=1)
        ch = np.repeat(np.asarray(cm[:, t]), h // g, axis=1)
        dtt = np.asarray(dt[:, t])
        st = (st * np.exp(dtt * np.asarray(a))[:, :, None, None]
              + (dtt[:, :, None] * np.asarray(x[:, t]))[..., None]
              * bh[:, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", st, ch)
                  + np.asarray(d)[None, :, None] * np.asarray(x[:, t]))
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), st, atol=1e-4)


def test_padded_positions_leave_the_state_at_the_last_real_token():
    r = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    x, dt = f(1, 16, 4, 4), jnp.abs(f(1, 16, 4)) * 0.3
    a, d, bm, cm = -jnp.exp(f(4)), f(4), f(1, 16, 2, 8), f(1, 16, 2, 8)
    s0 = jnp.zeros((1, 4, 4, 8), jnp.float32)
    cut = ssm_state.ssm_chunk_scan(x[:, :11], dt[:, :11], a, bm[:, :11],
                                   cm[:, :11], d, s0, 8)[1]
    padded = ssm_state.ssm_chunk_scan(
        x, dt.at[:, 11:].set(0.0), a, bm, cm, d, s0, 8)[1]
    np.testing.assert_allclose(np.asarray(padded), np.asarray(cut),
                               atol=1e-5)


# -- the expert share ------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """Every share's part of an expert layer (its held experts' sum through
    the latent), with the shared expert counted once, is what the uncut
    reference's layer gives."""
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        c = nemotron_h_tiny(initializer_range=0.2)
        pt.seed(2)
        whole = nh._expert_layer(c)
        params = whole.state_dict()
        h = jnp.asarray(np.random.default_rng(0).normal(size=(24, 64)),
                        jnp.float32)
        degree, held = 4, c.n_routed_experts // 4
        total = 0.0
        for rank in range(degree):
            share = DroplessMoE(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, n_shared_experts=1,
                routed_scaling_factor=c.routed_scaling_factor,
                norm_topk_prob=True, ep_degree=degree, ep_rank=rank,
                scoring_func="sigmoid", activation="relu2",
                latent_size=c.moe_latent_size,
                shared_width=c.moe_shared_expert_intermediate_size)
            mine = dict(params)
            for k in ("w_up", "w_down"):
                mine[k] = params[k][rank * held:(rank + 1) * held]
            y = share.apply(mine, h)[0]
            shared = share.shared.apply(
                {"w_up": params["shared.w_up"],
                 "w_down": params["shared.w_down"]}, h)
            total = total + (y - shared) + (shared if rank == 0 else 0.0)
        layer = {k: params[k] for k in ("router", "router_bias",
                                        "latent_down", "latent_up", "w_up",
                                        "w_down")}
        layer.update(shared_up=params["shared.w_up"],
                     shared_down=params["shared.w_down"])
        want = ref.expert_layer_whole(layer, {
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": True,
            "routed_scaling_factor": c.routed_scaling_factor}, h)
        np.testing.assert_allclose(np.asarray(total), want, atol=1e-4)
        assert np.abs(want).max() > 0.1
    finally:
        jax.config.update("jax_default_matmul_precision", None)


# -- the written controls ---------------------------------------------------
@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_written_control_moves_the_logits(model, control):
    seq = _prompts()[1]
    positions = list(range(len(seq)))
    got = np.asarray(model.apply(model.state_dict(),
                                 jnp.asarray([seq], jnp.int32)))[0]
    moved = reference_logits(model, seq, positions, **{control: True})
    # (a reading that overflows, such as no softplus, fails it too)
    assert not np.all(np.abs(got - moved) <= MOVED), control
