"""Serving resilience (ISSUE 15): request-lifecycle guard (deadlines,
cancellation), poisoned-request quarantine with batch bisection,
watchdog-supervised steps, graceful drain/resume, collect timeouts,
callback-error accounting, KV-block leak-freedom, and the doctor /
healthz surfaces.  Every engine-level test runs once a model family: a
GPT (two page arrays of ``(heads, head_dim)`` a layer) and a DeepSeek-V2
(one latent row a layer, one dense and one expert layer)."""
import importlib
import json
import os

import numpy as np
import pytest
from serving_families import (dense_continuation, family,  # noqa: F401
                              tiny_model)

from paddle_tpu.inference import CollectTimeout, ServingEngine
from paddle_tpu.observability import doctor
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.testing import faults

pytestmark = [pytest.mark.serving, pytest.mark.faults]


# the module each family's decode step calls for attention over its pages
ATTENTION = {"gpt": ("paddle_tpu.inference.paged_attention",
                     "paged_attention"),
             "deepseek_v2": ("paddle_tpu.inference.latent_attention",
                             "latent_attention")}


def make_engine(model=None, **kw):
    model = model or tiny_model()
    kw.setdefault("registry", MetricsRegistry())
    return ServingEngine(model, **kw)


def run_traffic(model, n=4, max_new=6, prepare=None, **kw):
    """``n`` short requests through a fresh engine (``prepare(engine)``
    first, to plant a fault); the engine, the ids, the token lists."""
    eng = make_engine(model, max_seqs=n, kv_block_size=4, **kw)
    if prepare is not None:
        prepare(eng)
    prompts = [[1 + i, 2, 3 + i] for i in range(n)]
    rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run(max_steps=500)
    return eng, rids, [eng.collect(r)["tokens"] for r in rids]


# ---------------------------------------------------------------------------
# deadlines & cancellation
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestLifecycleGuard:
    def test_deadline_eviction(self):
        clk = faults.expire_clock()
        eng = make_engine(max_seqs=4, kv_block_size=4, clock=clk)
        doomed = eng.submit([1, 2, 3], max_new_tokens=20,
                            deadline_ms=50.0)
        healthy = eng.submit([4, 5], max_new_tokens=4)
        eng.step()                      # some progress before expiry
        clk.advance(1.0)                # way past 50ms
        eng.run(max_steps=100)
        out = eng.collect(doomed)
        assert out["finish_reason"] == "deadline"
        assert eng.collect(healthy)["finish_reason"] == "max_new_tokens"
        assert eng.cache.allocator.num_used == 0
        st = eng.stats()["resilience"]
        assert st["deadline_misses"] == 1 and st["cancelled"] == 0
        reg = eng._reg().snapshot()
        assert reg["serve.deadline_misses"]["value"] == 1

    def test_ttft_deadline_only_hits_before_first_token(self):
        clk = faults.expire_clock()
        eng = make_engine(max_seqs=2, kv_block_size=4, clock=clk)
        # queued behind nothing: first token arrives on step 1, so a
        # ttft deadline passed AFTER that must not evict
        rid = eng.submit([1, 2, 3], max_new_tokens=4,
                         ttft_deadline_ms=100.0)
        eng.step()                      # prefill → first token
        clk.advance(10.0)
        eng.run(max_steps=50)
        assert eng.collect(rid)["finish_reason"] == "max_new_tokens"

    def test_ttft_deadline_expires_while_queued(self):
        clk = faults.expire_clock()
        # max_seqs=1: the second submit waits behind the first
        eng = make_engine(max_seqs=1, kv_block_size=4, clock=clk)
        eng.submit([1, 2, 3], max_new_tokens=20)
        queued = eng.submit([4, 5, 6], max_new_tokens=4,
                            ttft_deadline_ms=50.0)
        eng.step()
        clk.advance(1.0)
        eng.run(max_steps=200)
        out = eng.collect(queued)
        assert out["finish_reason"] == "deadline"
        assert out["tokens"] == []      # never started

    def test_cancel_running_and_waiting(self):
        eng = make_engine(max_seqs=1, kv_block_size=4)
        running = eng.submit([1, 2, 3], max_new_tokens=20)
        waiting = eng.submit([4, 5], max_new_tokens=4)
        eng.step()
        assert eng.cancel(running) and eng.cancel(waiting)
        assert not eng.cancel("no-such-request")
        eng.run(max_steps=50)
        assert eng.collect(running)["finish_reason"] == "cancelled"
        assert eng.collect(waiting)["finish_reason"] == "cancelled"
        assert eng.cache.allocator.num_used == 0
        assert eng.stats()["resilience"]["cancelled"] == 2
        assert not eng.cancel(running)  # already finished

    def test_terminal_reason_reaches_callback(self):
        events = []
        eng = make_engine(max_seqs=2, kv_block_size=4)
        rid = eng.submit([1, 2, 3], max_new_tokens=20,
                         on_token=lambda r, t, fin: events.append(
                             (r, t, fin)))
        eng.step()
        eng.cancel(rid)
        eng.run(max_steps=50)
        assert eng.drain_callbacks(timeout=5.0)
        assert events[-1] == (rid, None, True)

    def test_env_default_deadline(self, monkeypatch):
        monkeypatch.setenv("PTPU_SERVE_DEADLINE_MS", "50")
        clk = faults.expire_clock()
        eng = make_engine(max_seqs=2, kv_block_size=4, clock=clk)
        rid = eng.submit([1, 2, 3], max_new_tokens=20)
        eng.step()
        clk.advance(1.0)
        eng.run(max_steps=100)
        assert eng.collect(rid)["finish_reason"] == "deadline"


# ---------------------------------------------------------------------------
# poisoned-request quarantine
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestQuarantine:
    _traffic = staticmethod(run_traffic)

    def test_decode_raise_bisects_to_culprit(self, tmp_path):
        model = tiny_model()
        _, _, clean = self._traffic(model)
        injector = faults.poison_request(2, mode="raise",
                                         kinds=("decode",))
        eng, rids, outs = self._traffic(model, step_fault=injector,
                                        run_dir=str(tmp_path))
        assert injector.fired > 1       # bisection probes re-fired it
        bad = eng._submit_order[2]
        assert list(eng.quarantined) == [bad]
        assert eng.sched.finished[bad].finish_reason == "poisoned"
        # peers token-exact vs the clean run
        for i in (0, 1, 3):
            assert outs[i] == clean[i], (i, outs[i], clean[i])
        # durable record
        qdir = tmp_path / "serve" / "replica-0" / "quarantine"
        files = os.listdir(qdir)
        assert len(files) == 1
        rec = json.loads((qdir / files[0]).read_text())
        assert rec["request_id"] == bad
        assert rec["reason"] == "poisoned"
        assert rec["step_kind"] == "decode"
        assert "injected poisoned step" in rec["error"]
        assert eng.cache.allocator.num_used == 0

    def test_prefill_raise_quarantines_immediately(self, tmp_path):
        model = tiny_model()
        injector = faults.poison_request(1, mode="raise",
                                         kinds=("prefill",))
        eng, rids, outs = self._traffic(model, step_fault=injector,
                                        run_dir=str(tmp_path))
        bad = eng._submit_order[1]
        assert eng.sched.finished[bad].finish_reason == "poisoned"
        assert eng.quarantined[bad]["step_kind"] == "prefill"
        assert eng.collect(rids[1])["tokens"] == []

    def test_first_run_failure_is_not_a_poisoned_request(self, family,
                                                         monkeypatch):
        """A step program that has never run to completion cannot poison
        a request: a lowering/compile failure on the first decode (here a
        raising kernel stub) propagates out of run() instead of ending
        with every request 'poisoned' and exit 0."""
        # (the package re-exports the function under the module's name)
        module, name = ATTENTION[family]

        def boom(*a, **kw):
            raise NotImplementedError("Mosaic could not lower this block")
        monkeypatch.setattr(importlib.import_module(module), name, boom)
        eng = make_engine(tiny_model(), max_seqs=2, kv_block_size=4)
        rids = [eng.submit([1, 2, 3], max_new_tokens=4) for _ in range(2)]
        with pytest.raises(NotImplementedError, match="Mosaic"):
            eng.run(max_steps=50)
        assert not eng.quarantined
        assert eng.stats()["resilience"]["poisoned"] == 0
        assert not any(r in eng.sched.finished for r in rids)

    def test_nan_guard_names_culprit_without_bisection(self, tmp_path):
        model = tiny_model()
        _, _, clean = self._traffic(model)
        injector = faults.poison_request(0, mode="nan",
                                         kinds=("decode",))
        eng, rids, outs = self._traffic(model, step_fault=injector,
                                        nan_guard=True,
                                        run_dir=str(tmp_path))
        bad = eng._submit_order[0]
        assert list(eng.quarantined) == [bad]
        assert "nonfinite" in eng.quarantined[bad]["error"]
        for i in (1, 2, 3):
            assert outs[i] == clean[i]

    def test_nan_without_guard_flows_through(self):
        # guard off: NaN logits do NOT fault the step — argmax still
        # returns a token (garbage-tolerant, the pre-ISSUE-15 behavior)
        model = tiny_model()
        injector = faults.poison_request(0, mode="nan",
                                         kinds=("decode",), count=1)
        eng, rids, outs = self._traffic(model, step_fault=injector,
                                        nan_guard=False)
        assert not eng.quarantined
        assert all(len(t) > 0 for t in outs)

    def test_quarantine_counters_and_timeline(self, tmp_path):
        model = tiny_model()
        injector = faults.poison_request(2, mode="raise",
                                         kinds=("decode",))
        eng, _, _ = self._traffic(model, step_fault=injector,
                                  run_dir=str(tmp_path))
        snap = eng._reg().snapshot()
        assert snap["serve.poisoned"]["value"] == 1
        assert eng.stats()["resilience"]["poisoned"] == 1
        assert eng.stats()["resilience"]["quarantined"] == \
            [eng._submit_order[2]]


# ---------------------------------------------------------------------------
# a pool that the step program consumes (ISSUE 27)
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestConsumedPool:
    """The step program is handed the KV pool donated, so a step that
    fails cannot be undone by keeping the old arrays: its page writes
    stay (harmless), a culprit's blocks are scrubbed, and a pool that a
    failed call ate is rebuilt."""

    @staticmethod
    def _traffic(model, **kw):
        eng, _, outs = run_traffic(model, **kw)
        return eng, outs

    @pytest.mark.parametrize("mode,guard", [("raise", False),
                                            ("nan", True)])
    def test_culprits_blocks_are_scrubbed_before_they_are_freed(
            self, mode, guard):
        model = tiny_model()
        _, clean = self._traffic(model)
        seen = {}

        def prepare(eng):
            scrub = eng.cache.scrub_seq

            def checked(seq_id):
                blocks = list(eng.cache.table(seq_id))
                before = [np.asarray(a)[blocks] for kv in eng.cache.pages
                          for a in kv]
                scrub(seq_id)
                after = [np.asarray(a)[blocks] for kv in eng.cache.pages
                         for a in kv]
                seen[seq_id] = (blocks, before, after)
            eng.cache.scrub_seq = checked

        injector = faults.poison_request(1, mode=mode, kinds=("decode",))
        eng, outs = self._traffic(model, prepare=prepare, nan_guard=guard,
                                  step_fault=injector)
        bad = eng._submit_order[1]
        assert list(eng.quarantined) == [bad] and list(seen) == [bad]
        blocks, before, after = seen[bad]
        assert blocks                              # it still owned them
        assert all(a.any() for a in before)        # and had written K/V
        assert not any(a.any() for a in after)     # every layer, K and V
        for i in (0, 2, 3):                        # survivors token-exact
            assert outs[i] == clean[i], (i, outs[i], clean[i])
        assert eng.pool_rebuilds == 0 and not eng.cache.pages_lost()
        assert eng.cache.allocator.num_used == 0

    @pytest.mark.parametrize("kind,nth", [("decode", 2), ("decode", 4),
                                          ("prefill", 3)])
    def test_a_call_that_dies_with_the_pool_rebuilds_it(self, kind, nth):
        """The jitted call raises after it consumed its inputs: a zeroed
        pool, every running row back through recompute-prefill, nobody
        quarantined, every request token-exact."""
        model = tiny_model()
        _, clean = self._traffic(model)
        calls = {"n": 0, "died": 0}

        def prepare(eng):
            real = eng._build_step_fn()

            def flaky(params, packed, pages, key, prev, *, rows, chunk):
                if (chunk == 1) == (kind == "decode"):
                    calls["n"] += 1
                    if calls["n"] == nth:
                        calls["died"] += 1
                        for kv in pages:
                            for a in kv:
                                a.delete()
                        raise RuntimeError("device lost mid-step")
                return real(params, packed, pages, key, prev, rows=rows,
                            chunk=chunk)
            eng._jit_step = flaky

        eng, outs = self._traffic(model, prepare=prepare)
        assert calls["died"] == 1
        assert outs == clean
        assert not eng.quarantined
        res = eng.stats()["resilience"]
        assert res["pool_rebuilds"] == 1 and res["poisoned"] == 0
        snap = eng._reg().snapshot()
        assert snap["serve.pool_rebuilds"]["value"] == 1
        assert snap["serve.kv_pool_bytes"]["value"] == \
            eng.cache.pool_bytes() > 0
        assert eng.cache.allocator.num_used == 0

    def test_a_first_run_that_dies_with_the_pool_still_propagates(self):
        eng = make_engine(tiny_model(), max_seqs=2, kv_block_size=4)

        def dies(params, packed, pages, key, prev, **program):
            pages[0][0].delete()
            raise RuntimeError("device lost on the first step")
        eng._jit_step = dies
        eng.submit([1, 2, 3], max_new_tokens=2)
        with pytest.raises(RuntimeError, match="first step"):
            eng.step()
        # not a request's fault, and the engine is not left without a pool
        assert not eng.quarantined and eng.pool_rebuilds == 1
        assert not eng.cache.pages_lost()

    def test_outputs_of_a_program_that_failed_on_the_device_are_dropped(
            self, monkeypatch):
        import jax
        model = tiny_model()
        _, clean = self._traffic(model)
        real, state = jax.block_until_ready, {"n": 0}

        def flaky(x):
            state["n"] += 1
            if state["n"] == 6:          # the second decode step
                raise RuntimeError("async device error")
            return real(x)

        def prepare(eng):
            monkeypatch.setattr(jax, "block_until_ready", flaky)
        eng, outs = self._traffic(model, prepare=prepare)
        assert outs == clean and not eng.quarantined
        assert eng.pool_rebuilds == 1

    def test_a_probe_that_loses_the_pool_blames_nobody(self):
        model = tiny_model()
        _, clean = self._traffic(model)
        state = {"armed": False, "died": 0}

        def fault(engine, kind, rids, logits):
            # the first full decode batch faults once; its first probe
            # then dies inside the jitted call
            if kind == "decode" and len(rids) == 4 and not state["died"]:
                state["armed"] = True
                raise RuntimeError("transient step fault")

        def prepare(eng):
            real = eng._build_step_fn()

            def flaky(params, packed, pages, key, prev, *, rows, chunk):
                if state["armed"] and not state["died"]:
                    state["died"] = 1
                    pages[0][0].delete()
                    raise RuntimeError("device lost in a probe")
                return real(params, packed, pages, key, prev, rows=rows,
                            chunk=chunk)
            eng._jit_step = flaky

        eng, outs = self._traffic(model, prepare=prepare, step_fault=fault)
        assert state["died"] == 1 and eng.pool_rebuilds == 1
        assert not eng.quarantined and outs == clean

    def test_hang_recovery_replaces_a_consumed_pool(self):
        model = tiny_model()
        _, clean = self._traffic(model)
        eng = make_engine(model, max_seqs=4, kv_block_size=4)
        rids = [eng.submit([1 + i, 2, 3 + i], max_new_tokens=6)
                for i in range(4)]
        for _ in range(6):                   # four prefills, two decodes
            eng.step()
        eng.cache.drop_pages()               # a step cut inside the call
        eng._recover_from_hang()
        assert eng.pool_rebuilds == 1 and eng.watchdog_restarts == 1
        assert not eng.cache.pages_lost()
        eng.run(max_steps=500)
        assert [eng.collect(r)["tokens"] for r in rids] == clean

    def test_hang_recovery_keeps_a_live_pool(self):
        eng = make_engine(tiny_model(), max_seqs=2, kv_block_size=4)
        eng.submit([1, 2, 3], max_new_tokens=4)
        eng.step()
        handles = [a for kv in eng.cache.pages for a in kv]
        eng._recover_from_hang()
        assert eng.pool_rebuilds == 0
        assert all(x is y for x, y in zip(
            handles, [a for kv in eng.cache.pages for a in kv]))


# ---------------------------------------------------------------------------
# watchdog supervision
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestWatchdogRecovery:
    # step_timeout must cover a COLD compile (the watchdog cannot tell
    # XLA compiling from a wedged device) — these tests warm the shape
    # set under a generous timeout, then tighten it for the hang drill;
    # the post-recovery rebuild re-traces but hits jax's backend compile
    # cache, so the tight timeout only has to cover tracing.

    def test_hung_step_recovers_token_exact(self):
        model = tiny_model()
        prompt = [2, 3, 4]
        want = dense_continuation(model, prompt, 6)
        injector = faults.poison_request(1, mode="hang", seconds=30.0,
                                         kinds=("decode",), count=1)
        eng = make_engine(model, max_seqs=2, kv_block_size=4,
                          step_timeout=120.0, step_fault=injector)
        try:
            eng.submit([1, 2, 3], max_new_tokens=6)   # warm (index 0)
            eng.run(max_steps=100)
            eng.step_timeout = 2.0
            rid = eng.submit(prompt, max_new_tokens=6)  # target (index 1)
            eng.run(max_steps=200)
            assert eng.watchdog_restarts == 1
            assert injector.fired == 1
            out = eng.collect(rid)
            # recompute-prefill re-admission: same tokens as a clean run
            assert out["tokens"] == want
            assert out["preemptions"] >= 1
            assert eng.stats()["resilience"]["watchdog_restarts"] == 1
        finally:
            eng.stop()

    def test_jitted_fns_rebuilt_after_hang(self):
        model = tiny_model()
        injector = faults.poison_request(1, mode="hang", seconds=30.0,
                                         kinds=("decode",), count=1)
        eng = make_engine(model, max_seqs=2, kv_block_size=4,
                          step_timeout=120.0, step_fault=injector)
        try:
            eng.submit([1, 2, 3], max_new_tokens=3)   # warm (index 0)
            eng.run(max_steps=100)
            eng.step_timeout = 2.0
            eng.submit([2, 3, 4], max_new_tokens=3)   # target (index 1)
            eng.step()                   # prefill
            assert eng._decode_tracked is not None
            eng.step()                   # decode hangs → recovery
            assert eng._decode_tracked is None
            assert eng._prefill_tracked == {}
            eng.run(max_steps=100)
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# graceful drain / resume
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestDrainResume:
    def test_drain_finishes_running_spills_waiting(self, tmp_path):
        model = tiny_model()
        eng = make_engine(model, max_seqs=2, kv_block_size=4,
                          run_dir=str(tmp_path))
        rids = [eng.submit([1 + i, 2, 3], max_new_tokens=4)
                for i in range(6)]
        eng.step(); eng.step()
        report = eng.drain(timeout=30.0)
        assert eng.state == "stopped"
        assert not report["timed_out"]
        assert report["spilled"] > 0
        assert report["finished"] + report["spilled"] == 6 \
            or report["finished"] >= 2  # running set finished at minimum
        for r in rids:
            assert r in eng.sched.finished
        spilled_rids = [r for r in rids
                        if eng.sched.finished[r].finish_reason
                        == "spilled"]
        assert len(spilled_rids) == report["spilled"]
        assert eng.cache.allocator.num_used == 0
        # the spill file is a fresh engine's intake
        payload = json.loads(
            open(report["spill_path"]).read())  # noqa: fsio — test-side read
        assert payload["version"] == 1
        assert {r["request_id"] for r in payload["spilled"]} \
            == set(spilled_rids)

    def test_resume_continues_token_exact(self, tmp_path):
        model = tiny_model()
        prompts = {f"r{i}": [1 + i, 2, 3] for i in range(4)}
        want = {rid: dense_continuation(model, p, 6)
                for rid, p in prompts.items()}
        eng = make_engine(model, max_seqs=1, kv_block_size=4,
                          run_dir=str(tmp_path))
        for rid, p in prompts.items():
            eng.submit(p, max_new_tokens=6, request_id=rid)
        eng.step(); eng.step(); eng.step()   # partial progress
        report = eng.drain(timeout=30.0)
        finished = {r: eng.sched.finished[r].output
                    for r in prompts if
                    eng.sched.finished[r].finish_reason != "spilled"}
        fresh = make_engine(model, max_seqs=1, kv_block_size=4)
        resumed = fresh.resume(report["spill_path"])
        assert set(resumed) | set(finished) == set(prompts)
        fresh.run(max_steps=500)
        for rid in resumed:
            out = fresh.collect(rid)
            assert out["tokens"] == want[rid], (rid, out["tokens"],
                                               want[rid])
        for rid, toks in finished.items():
            assert toks == want[rid]

    def test_submit_refused_after_drain_begins(self, tmp_path):
        eng = make_engine(max_seqs=2, kv_block_size=4,
                          run_dir=str(tmp_path))
        eng.submit([1, 2], max_new_tokens=2)
        eng.begin_drain()
        assert eng.state == "draining"
        with pytest.raises(Exception, match="draining"):
            eng.submit([3, 4], max_new_tokens=2)
        eng.drain(timeout=30.0)
        with pytest.raises(Exception, match="stopped"):
            eng.submit([3, 4], max_new_tokens=2)

    def test_drain_timeout_spills_running(self, tmp_path):
        model = tiny_model()
        eng = make_engine(model, max_seqs=2, kv_block_size=4,
                          run_dir=str(tmp_path))
        eng.submit([1, 2, 3], max_new_tokens=20)
        eng.step()                         # admit → running mid-decode
        report = eng.drain(timeout=0.0)    # no time to finish anything
        assert report["timed_out"]
        assert report["spilled"] == 1
        assert eng.cache.allocator.num_used == 0

    def test_resume_rejects_bad_version(self, tmp_path):
        spill = tmp_path / "serve_spill.json"
        spill.write_text(json.dumps({"version": 99, "spilled": []}))
        eng = make_engine(max_seqs=2, kv_block_size=4)
        with pytest.raises(Exception, match="version"):
            eng.resume(str(spill))

    def test_spill_lands_in_replica_namespace(self, tmp_path):
        # ISSUE 16: per-replica artifact namespacing — default spill
        # path is <run_dir>/serve/replica-<i>/spill.json
        eng = make_engine(max_seqs=2, kv_block_size=4,
                          run_dir=str(tmp_path), replica_id=3)
        eng.submit([1, 2, 3], max_new_tokens=20)
        eng.step()
        report = eng.drain(timeout=0.0)
        assert report["spilled"] == 1
        assert report["spill_path"] == str(
            tmp_path / "serve" / "replica-3" / "spill.json")
        assert report["spilled_records"][0]["request_id"] \
            == eng._submit_order[0]

    def test_resume_reads_legacy_spill_path(self, tmp_path):
        # pre-ISSUE-16 run dirs keep <run_dir>/serve_spill.json — a
        # fresh engine with only run_dir must still find and resume it
        model = tiny_model()
        want = dense_continuation(model, [1, 2, 3], 6)
        eng = make_engine(model, max_seqs=2, kv_block_size=4)
        eng.submit([1, 2, 3], max_new_tokens=6, request_id="legacy")
        eng.step(); eng.step()
        legacy = tmp_path / "serve_spill.json"
        eng.drain(timeout=0.0, spill_path=str(legacy))
        assert legacy.exists()
        fresh = make_engine(model, max_seqs=2, kv_block_size=4,
                            run_dir=str(tmp_path))
        assert fresh.resume() == ["legacy"]
        fresh.run(max_steps=200)
        assert fresh.collect("legacy")["tokens"] == want


# ---------------------------------------------------------------------------
# collect timeout / stuck-run diagnostics
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestCollectTimeout:
    def test_collect_timeout_names_scheduler_state(self):
        eng = make_engine(max_seqs=1, kv_block_size=4)
        eng.submit([1, 2, 3], max_new_tokens=20)
        queued = eng.submit([4, 5], max_new_tokens=2)
        eng.step()
        eng.begin_drain()           # queued can never be admitted now
        with pytest.raises(CollectTimeout) as ei:
            eng.collect(queued, timeout=0.3)
        msg = str(ei.value)
        assert queued in msg and "queue_position" in msg

    def test_run_names_stuck_requests(self):
        eng = make_engine(max_seqs=1, kv_block_size=4)
        stuck = eng.submit([1, 2, 3], max_new_tokens=20)
        with pytest.raises(RuntimeError, match=stuck):
            eng.run(max_steps=2)


# ---------------------------------------------------------------------------
# callback-error accounting
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestCallbackErrors:
    def test_consumer_exception_counted_not_fatal(self):
        eng = make_engine(max_seqs=2, kv_block_size=4)

        def bad_cb(rid, token, finished):
            raise ValueError("consumer bug")

        rid = eng.submit([1, 2, 3], max_new_tokens=3, on_token=bad_cb)
        eng.run(max_steps=50)
        assert eng.drain_callbacks(timeout=5.0)
        assert eng.collect(rid)["finish_reason"] == "max_new_tokens"
        st = eng.stats()["resilience"]["callbacks"]
        assert st["errors"] == 3 and st["dispatched"] == 3
        assert "consumer bug" in st["last_error"]
        snap = eng._reg().snapshot()
        assert snap["serve.callback_errors"]["value"] == 3
        eng.stop()

    def test_stop_terminates_callback_thread(self):
        eng = make_engine(max_seqs=2, kv_block_size=4)
        eng.submit([1, 2], max_new_tokens=2,
                   on_token=lambda *a: None)
        eng.run(max_steps=50)
        assert eng.drain_callbacks(timeout=5.0)
        thread = eng._cb_thread
        assert thread is not None and thread.is_alive()
        eng.stop()
        assert eng._cb_thread is None
        assert not thread.is_alive()


# ---------------------------------------------------------------------------
# KV-block leak freedom (property-style)
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestLeakFreedom:
    def test_any_interleaving_returns_to_baseline(self, tmp_path):
        """Finish / cancel / deadline-evict / preempt / quarantine, all
        interleaved on a tight pool across several rounds — occupancy
        must return exactly to baseline with balanced alloc/free
        ledgers every round."""
        model = tiny_model()
        clk = faults.expire_clock()
        rng = np.random.RandomState(3)
        for round_idx in range(4):
            injector = faults.poison_request(
                int(rng.randint(0, 6)), mode="raise", kinds=("decode",))
            # tight pool: 10 blocks of 4 for up to 6 seqs forces
            # preemption churn alongside the evictions
            eng = make_engine(model, max_seqs=4, kv_block_size=4,
                              num_kv_blocks=10, clock=clk,
                              step_fault=injector,
                              run_dir=str(tmp_path / str(round_idx)))
            assert eng.cache.allocator.num_used == 0
            rids = []
            for i in range(6):
                kw = {}
                if i == 1:
                    kw["deadline_ms"] = 50.0
                rids.append(eng.submit(
                    [1 + i, 2, 3, 4], max_new_tokens=int(
                        rng.randint(2, 8)), **kw))
            for s in range(40):
                if s == 3:
                    eng.cancel(rids[int(rng.randint(0, 6))])
                if s == 5:
                    clk.advance(1.0)    # expire rids[1] (if still live)
                eng.step()
                if not eng.has_work():
                    break
            eng.run(max_steps=500)
            stats = eng.cache.allocator.stats()
            assert stats["num_used"] == 0, eng.cache.leak_report()
            assert stats["balanced"], stats
            report = eng.cache.leak_report()
            assert report["leaked_blocks"] == 0
            assert report["tabled_blocks"] == 0
            for r in rids:
                assert r in eng.sched.finished


# ---------------------------------------------------------------------------
# observability surfaces: /healthz, /statusz, doctor
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestSurfaces:
    def test_healthz_draining_then_stopped(self):
        from paddle_tpu.observability.monitor import StatusServer
        eng = make_engine(max_seqs=2, kv_block_size=4)
        srv = StatusServer(registry=eng._registry, engine=eng)
        code, state = srv.healthz()
        assert code == 200
        eng.begin_drain()
        code, state = srv.healthz()
        assert (code, state) == (503, "draining")
        eng.drain(timeout=10.0)
        code, state = srv.healthz()
        assert (code, state) == (503, "stopped")

    def test_statusz_resilience_section(self):
        from paddle_tpu.observability.monitor import StatusServer
        eng = make_engine(max_seqs=2, kv_block_size=4)
        rid = eng.submit([1, 2, 3], max_new_tokens=4)
        eng.cancel(rid)
        eng.run(max_steps=50)
        srv = StatusServer(registry=eng._registry, engine=eng)
        res = srv.statusz()["serving"]["resilience"]
        assert res["cancelled"] == 1
        assert res["state"] == "serving"
        assert res["callbacks"]["errors"] == 0


def test_doctor_check_serving():
    workers = {0: [
        {"kind": "serve.quarantine", "request_id": "req-7",
         "step_kind": "decode", "error": "RuntimeError('boom')"},
        {"kind": "serve.deadline_miss", "request_id": "req-8",
         "miss": "ttft"},
        {"kind": "serve.deadline_miss", "request_id": "req-9",
         "miss": "total"},
    ]}
    findings = doctor.check_serving(workers)
    kinds = {f["kind"]: f for f in findings}
    assert set(kinds) == {"serve_poisoned", "serve_deadline_misses"}
    assert kinds["serve_poisoned"]["data"]["count"] == 1
    assert kinds["serve_deadline_misses"]["data"]["count"] == 2
    assert kinds["serve_deadline_misses"]["data"]["ttft_misses"] == 1
    assert kinds["serve_poisoned"]["severity"] \
        > kinds["serve_deadline_misses"]["severity"]
    assert doctor.check_serving({0: []}) == []
