"""Telemetry-layer tests (ISSUE 3): registry concurrency + histogram
bounds, span nesting + chrome-trace export, JSONL sink durability through
injected fsio faults, the cross-worker aggregator, the vlog flag cache,
and an e2e ``Model.fit`` run asserting step-breakdown + MFU records land
on the same timeline as supervisor events."""
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.observability import (Counter, Histogram, MetricsRegistry,
                                      MetricsWriter, PrometheusTextfile,
                                      StderrSummary)
from paddle_tpu.observability import aggregate as agg_mod
from paddle_tpu.observability import tracing
from paddle_tpu.utils import fsio

pytestmark = pytest.mark.telemetry


class _ListSink:
    def __init__(self):
        self.records = []
        self.flushed = 0

    def write(self, record):
        self.records.append(record)

    def flush(self):
        self.flushed += 1

    def close(self):
        self.flush()


class TestRegistry:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        assert reg.counter("c") is c          # same name → same instrument
        g = reg.gauge("g")
        assert g.value is None
        g.set(7)
        assert g.value == 7.0

    def test_type_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_counter_concurrency_exact(self):
        reg = MetricsRegistry()
        c = reg.counter("n")
        threads = [threading.Thread(
            target=lambda: [c.inc() for _ in range(5000)])
            for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 40000

    def test_histogram_exact_stats_bounded_reservoir(self):
        h = Histogram("h", max_samples=64, seed=0)
        for i in range(10000):
            h.observe(float(i))
        snap = h.snapshot()
        assert snap["count"] == 10000
        assert snap["sum"] == sum(range(10000))
        assert snap["min"] == 0.0 and snap["max"] == 9999.0
        assert len(h._samples) == 64          # bounded regardless of count
        # reservoir percentiles are estimates; order must still hold
        assert snap["p50"] <= snap["p90"] <= snap["p99"] <= snap["max"]
        assert h.percentile(0) >= 0.0

    def test_histogram_concurrency_count_exact(self):
        h = Histogram("h", max_samples=32)
        threads = [threading.Thread(
            target=lambda: [h.observe(1.0) for _ in range(2000)])
            for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == 8000 and h.sum == 8000.0

    def test_counter_inc_overhead_under_a_microsecond(self):
        # acceptance: with no sink attached, counter increments must stay
        # hot-path cheap.  Budget 5 µs/call (measured ~0.25 µs) so a
        # loaded CI box can't flake the bound.
        c = MetricsRegistry().counter("hot")
        n = 100000
        t0 = time.perf_counter()
        for _ in range(n):
            c.inc()
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, f"inc() cost {per_call * 1e6:.2f} µs/call"
        assert c.value == n

    def test_emit_no_sink_is_noop_and_fast(self):
        reg = MetricsRegistry()
        n = 50000
        t0 = time.perf_counter()
        for _ in range(n):
            reg.emit("step", step=1, loss=0.5)
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6

    def test_emit_fans_out_and_stamps_ts(self):
        reg = MetricsRegistry(clock=lambda: 123.0)
        sink = reg.add_sink(_ListSink())
        reg.emit("step", step=3, loss=0.5)
        reg.emit("custom", ts=99.0)
        assert sink.records[0] == {"ts": 123.0, "kind": "step", "step": 3,
                                   "loss": 0.5}
        assert sink.records[1]["ts"] == 99.0
        reg.remove_sink(sink)
        reg.emit("step", step=4)
        assert len(sink.records) == 2         # detached sinks see nothing

    def test_broken_sink_never_raises_and_peers_still_receive(self):
        reg = MetricsRegistry()

        class Broken:
            def write(self, record):
                raise RuntimeError("boom")

            def flush(self):
                raise RuntimeError("boom")

            def close(self):
                pass

        good = _ListSink()
        reg.add_sink(Broken())
        reg.add_sink(good)
        reg.emit("step", step=1)
        reg.flush()
        assert len(good.records) == 1

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("a").inc(2)
        reg.gauge("b").set(1.5)
        reg.histogram("c").observe(10.0)
        snap = reg.snapshot()
        assert snap["a"] == {"type": "counter", "value": 2.0}
        assert snap["b"]["value"] == 1.5
        assert snap["c"]["count"] == 1


class TestTracing:
    def setup_method(self):
        tracing.reset_tracing()

    def test_span_nesting_paths_and_self_time(self):
        with obs.span("step"):
            with obs.span("dispatch"):
                time.sleep(0.01)
            with obs.span("readback"):
                time.sleep(0.005)
        tree = obs.span_tree_totals()
        assert set(tree) == {"step", "step/dispatch", "step/readback"}
        step = tree["step"]
        assert step["count"] == 1
        # self time excludes the children
        child_total = (tree["step/dispatch"]["total_ms"]
                       + tree["step/readback"]["total_ms"])
        assert step["self_ms"] <= step["total_ms"] - child_total + 1.0
        assert tree["step/dispatch"]["total_ms"] >= 9.0

    def test_span_elapsed_exposed(self):
        with obs.span("x") as sp:
            time.sleep(0.002)
        assert sp.elapsed >= 0.002

    def test_same_leaf_under_different_parents_distinct(self):
        with obs.span("a"):
            with obs.span("io"):
                pass
        with obs.span("b"):
            with obs.span("io"):
                pass
        tree = obs.span_tree_totals()
        assert "a/io" in tree and "b/io" in tree

    def test_chrome_trace_export(self, tmp_path):
        with obs.span("outer"):
            with obs.span("inner"):
                time.sleep(0.002)
        path = str(tmp_path / "trace.json")
        n = obs.export_chrome_trace(path)
        assert n == 2
        doc = json.loads(fsio.read_bytes(path))
        events = doc["traceEvents"]
        by_name = {e["name"]: e for e in events}
        assert set(by_name) == {"outer", "outer/inner"}
        inner, outer = by_name["outer/inner"], by_name["outer"]
        for e in events:
            assert e["ph"] == "X" and e["dur"] > 0
        # the child interval sits inside the parent's
        assert inner["ts"] >= outer["ts"] - 1.0
        assert (inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1.0)

    def test_spans_feed_profiler_host_table_and_summary(self):
        from paddle_tpu.profiler import Profiler, profiler_summary
        profiler_summary(reset=True)
        with obs.span("step"):
            with obs.span("dispatch"):
                pass
        stats = profiler_summary()
        assert stats["step"][0] == 1
        assert stats["step/dispatch"][0] == 1
        text = Profiler(timer_only=True).summary()
        assert "step/dispatch" in text and "self ms" in text

    def test_reset(self):
        with obs.span("x"):
            pass
        tracing.reset_tracing()
        assert obs.span_tree_totals() == {}
        assert tracing.trace_events() == []
        assert obs.spans_between(0.0, float("inf")) == []
        assert obs.dropped() == 0

    def test_a_child_cut_off_goes_with_its_parent(self):
        # an asynchronous exception (the watchdog's StepTimeout) can land
        # after a span's push and before its ``with``: no __exit__ follows
        with obs.span("step"):
            obs.span("child").__enter__()
        assert tracing.current_span() is None
        with obs.span("next"):
            pass
        tree = obs.span_tree_totals()
        assert "next" in tree and "step" in tree
        assert not [p for p in tree if p.endswith("/next")]

    def test_spans_start_on_perf_counter(self):
        before = time.perf_counter()
        with obs.span("outer"):
            with obs.span("inner"):
                time.sleep(0.002)
        after = time.perf_counter()
        got = {p: (a, b) for p, a, b, _ in obs.spans_between(before, after)}
        assert set(got) == {"outer", "outer/inner"}
        (oa, ob), (ia, ib) = got["outer"], got["outer/inner"]
        # the harness's clock, not the wall's: joinable without an offset
        assert before <= oa <= ia < ib <= ob <= after
        assert ib - ia >= 0.002
        # the chrome export is the same spans on wall time
        ev = {e["name"]: e for e in tracing.trace_events()}
        assert abs(ev["outer"]["ts"] * 1e-6 - time.time()) < 60.0
        assert abs(ev["outer"]["dur"] * 1e-6 - (ob - oa)) < 1e-6

    def test_span_attributes_stay_with_the_record(self):
        with obs.span("engine.step", step=7) as root:
            with obs.span("schedule", step=7):
                pass
            root.set(kind="decode", rows=3)
        spans = obs.spans_between(0.0, float("inf"))
        by_path = {p: attrs for p, _, _, attrs in spans}
        assert by_path["engine.step"] == {"step": 7, "kind": "decode",
                                          "rows": 3}
        assert by_path["engine.step/schedule"] == {"step": 7}
        # oldest first: a child completes before its parent
        assert [p for p, *_ in spans] == ["engine.step/schedule",
                                          "engine.step"]
        ev = {e["name"]: e for e in tracing.trace_events()}
        assert ev["engine.step"]["args"]["kind"] == "decode"

    def test_spans_between_keeps_what_overlaps(self):
        marks = []
        for name in ("a", "b", "c"):
            with obs.span(name):
                time.sleep(0.001)
            marks.append(time.perf_counter())
            time.sleep(0.001)       # every mark lies between two spans
        names = lambda t0, t1: [p for p, *_ in obs.spans_between(t0, t1)]
        assert names(0.0, float("inf")) == ["a", "b", "c"]
        assert names(marks[0], marks[1]) == ["b"]
        assert names(marks[2], float("inf")) == []
        # overlap is enough: the window need not hold the whole span
        (_, b0, b1, _), = obs.spans_between(marks[0], marks[1])
        assert names((b0 + b1) / 2, marks[1]) == ["b"]

    def test_dropped_says_when_a_window_is_no_longer_whole(self,
                                                           monkeypatch):
        from collections import deque
        monkeypatch.setattr(tracing, "_buffer", deque(maxlen=4))
        for i in range(4):
            with obs.span(f"s{i}"):
                pass
        assert obs.dropped() == 0
        t_mid = time.perf_counter()
        for i in range(4, 7):
            with obs.span(f"s{i}"):
                pass
        # s0..s2 went; all of them ended before t_mid
        assert [p for p, *_ in obs.spans_between(0.0, float("inf"))] == \
            ["s3", "s4", "s5", "s6"]
        assert obs.dropped() == 3
        assert obs.dropped(0.0) == 3          # a window from 0 lost spans
        assert obs.dropped(t_mid) == 0        # a window from t_mid is whole
        with obs.span("s7"):
            pass                              # pushes s3 out, which ended
        assert obs.dropped(t_mid) == 0        # ... before t_mid too
        with obs.span("s8"):
            pass                              # s4 ended after t_mid
        assert obs.dropped(t_mid) == 5
        # the tree is not bounded by the buffer
        assert len(obs.span_tree_totals()) == 9


class TestTrackJitHits:
    """ISSUE 33: a call that lands on a trace jax already holds builds no
    signature; a call that traced gives the record it always gave."""

    @staticmethod
    def tracked(fn, **kw):
        import jax
        from paddle_tpu.observability import compilation
        reg = MetricsRegistry()
        sink = _ListSink()
        reg.add_sink(sink)
        tr = compilation.CompileTracker(registry=reg)
        jitted = jax.jit(fn, **kw.pop("jit", {}))
        return compilation.track_jit(jitted, tracker=tr, **kw), tr, reg, sink

    def test_n_calls_on_one_signature_walk_once(self, monkeypatch):
        import jax.numpy as jnp
        from paddle_tpu.observability import compilation
        f, tr, reg, sink = self.tracked(
            lambda tree, x: x + tree["a"].sum(), name="f",
            arg_names=("tree", "x"))
        seen = {"arg_signature": 0, "_describe_leaf": 0}
        for fn_name in seen:
            real = getattr(compilation, fn_name)

            def spy(*a, _real=real, _name=fn_name):
                seen[_name] += 1
                return _real(*a)
            monkeypatch.setattr(compilation, fn_name, spy)
        tree = {"a": jnp.ones((3,)), "b": [jnp.zeros((2, 2))] * 5}
        f(tree, jnp.zeros((4,)))
        first = dict(seen)
        assert first == {"arg_signature": 2, "_describe_leaf": 7}
        for _ in range(19):
            f(tree, jnp.ones((4,)))           # fresh arrays, same signature
        assert seen == first                   # nothing described again
        assert tr.stats("f") == {"calls": 20, "walks": 1, "traces": 1,
                                 "retraces": 0, "storms": 0}
        assert reg.counter("compile.cache_hit[fn=f]").value == 19
        assert reg.counter("compile.count[fn=f]").value == 1
        assert [r["kind"] for r in sink.records] == ["compile"]

    MISSES = {
        # case: (jit kwargs, first call, second call, changed)
        "leaf_shape": (
            {}, lambda z: (z(2, 8), {"w": z(3)}),
            lambda z: (z(2, 12), {"w": z(3)}),
            [{"arg": "data", "detail": "float32[2,8] -> float32[2,12]"}]),
        "leaf_of_many": (
            {}, lambda z: (z(2), {"w": z(3), "v": z(1)}),
            lambda z: (z(2), {"w": z(4), "v": z(1)}),
            [{"arg": "state",
              "detail": "leaf 1: float32[3] -> float32[4]"}]),
        "structure": (
            {}, lambda z: (z(2), {"w": z(3)}),
            lambda z: (z(2), {"w": z(3), "v": z(3)}),
            [{"arg": "state", "detail": "structure changed"}]),
        "static": (
            {"static_argnums": (2,)}, lambda z: (z(2), {"w": z(3)}, 4),
            lambda z: (z(2), {"w": z(3)}, 5),
            [{"arg": "arg2", "detail": "4 -> 5"}]),
        "donated": (
            {"donate_argnums": (0,)}, lambda z: (z(2, 8), {"w": z(3)}),
            lambda z: (z(2, 12), {"w": z(3)}),
            [{"arg": "data", "detail": "float32[2,8] -> float32[2,12]"}]),
    }

    @pytest.mark.parametrize("case", sorted(MISSES))
    def test_a_call_that_traced_gives_todays_record(self, case):
        import jax.numpy as jnp
        jit_kw, first, second, changed = self.MISSES[case]

        def fn(data, state, *static):
            return data * 2.0, state["w"].sum()
        f, tr, reg, sink = self.tracked(fn, name="step", jit=jit_kw,
                                        arg_names=("data", "state"))
        z = lambda *shape: jnp.zeros(shape, jnp.float32)
        args = first(z)
        f(*args)
        f(*first(z))                           # a hit between the traces
        args = second(z)
        f(*args)
        if case == "donated":                  # described after it went
            assert args[0].is_deleted()
        one, two = [r for r in sink.records if r["kind"] == "compile"]
        for rec in (one, two):
            assert rec.pop("wall_ms") > 0 and rec.pop("ts") > 0
            rec.pop("kind")
        nargs = len(args)
        assert one == {"function": "step", "trace": True, "retrace": False,
                       "changed": [], "nargs": nargs}
        assert two == {"function": "step", "trace": True, "retrace": True,
                       "changed": changed, "nargs": nargs}
        assert tr.stats("step") == {"calls": 3, "walks": 2, "traces": 2,
                                    "retraces": 1, "storms": 0}
        assert reg.counter("compile.retraces[fn=step]").value == 1
        assert reg.counter("compile.cache_hit[fn=step]").value == 1

    def test_three_retraces_in_sixteen_calls_are_a_storm(self):
        import jax.numpy as jnp
        f, tr, reg, sink = self.tracked(
            lambda w, seq: (w * seq).sum(), name="step",
            arg_names=("weights", "seq"))
        w = jnp.ones((4,))
        for call in range(16):                 # a new length every fifth
            f(w, jnp.zeros((8 + call // 5, 4)))
        assert tr.stats("step") == {"calls": 16, "walks": 4, "traces": 4,
                                    "retraces": 3, "storms": 1}
        storm, = [r for r in sink.records
                  if r["kind"] == "compile.retrace_storm"]
        assert storm["culprit"] == "seq" and storm["retraces"] == 3
        assert storm["window"] == 16
        assert reg.counter("compile.storms[fn=step]").value == 1

    def test_a_capture_window_gets_a_program_warmed_before_it(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.observability import roofline
        f, tr, reg, sink = self.tracked(lambda a: jnp.tanh(a).sum(),
                                        name="warm_step")
        a = jnp.ones((8, 4))
        f(a)
        roofline.reset_observatory()
        try:
            with roofline.capture_window():
                f(a)                           # a hit, and still recorded
                entry = roofline.get_observatory().entries()["warm_step"]
        finally:
            roofline.reset_observatory()
        assert entry["args"] == (jax.ShapeDtypeStruct((8, 4), jnp.float32),)
        f(a)
        assert tr.stats("warm_step")["calls"] == 3
        assert tr.stats("warm_step")["walks"] == 2     # first; the window's
        assert tr.stats("warm_step")["traces"] == 1

    @pytest.mark.parametrize("how", ["plain_callable", "after_reset"])
    def test_where_jax_cannot_say_every_call_walks(self, how):
        import jax.numpy as jnp
        from paddle_tpu.observability import compilation
        if how == "plain_callable":            # no cache to ask
            tr = compilation.CompileTracker(registry=MetricsRegistry())
            f = compilation.track_jit(lambda x: x * 2, name="f", tracker=tr)
            for _ in range(3):
                f(jnp.ones((2,)))
            assert tr.stats("f")["walks"] == 3
        else:                                  # the tracker forgot the name
            f, tr, reg, sink = self.tracked(lambda x: x * 2, name="f")
            f(jnp.ones((2,)))
            f(jnp.ones((2,)))
            tr.reset()
            f(jnp.ones((2,)))
            f(jnp.ones((2,)))
            assert tr.stats("f") == {"calls": 2, "walks": 1, "traces": 1,
                                     "retraces": 0, "storms": 0}


class TestMetricsWriter:
    def test_writes_jsonl(self, tmp_path):
        w = MetricsWriter(str(tmp_path), worker_id=3, flush_every=2)
        w.write({"ts": 1.0, "kind": "step", "step": 0})
        w.write({"ts": 2.0, "kind": "step", "step": 1})   # triggers flush
        w.write({"ts": 3.0, "kind": "step", "step": 2})
        w.close()                                          # flushes the tail
        path = tmp_path / "worker-3.jsonl"
        recs = [json.loads(l) for l in path.read_text().splitlines()]
        assert [r["step"] for r in recs] == [0, 1, 2]
        assert w.written == 3 and w.dropped == 0

    def test_survives_injected_fsio_faults(self, tmp_path, monkeypatch):
        w = MetricsWriter(str(tmp_path), worker_id=0, flush_every=1)
        real_append = fsio.append_bytes
        fail = {"on": True}

        def flaky(path, payload):
            if fail["on"]:
                raise OSError("injected telemetry fault")
            real_append(path, payload)

        monkeypatch.setattr(fsio, "append_bytes", flaky)
        w.write({"kind": "step", "step": 0})   # flush fails, record kept
        w.write({"kind": "step", "step": 1})
        assert w.written == 0
        fail["on"] = False                      # fault clears
        w.write({"kind": "step", "step": 2})
        w.close()
        recs = [json.loads(l) for l in
                (tmp_path / "worker-0.jsonl").read_text().splitlines()]
        # nothing was lost across the fault window
        assert [r["step"] for r in recs] == [0, 1, 2]
        assert w.dropped == 0

    def test_wedged_stream_drops_oldest_and_counts(self, tmp_path,
                                                   monkeypatch):
        w = MetricsWriter(str(tmp_path), worker_id=0, flush_every=1,
                          max_buffered=5)

        def always_fail(path, payload):
            raise OSError("wedged")

        monkeypatch.setattr(fsio, "append_bytes", always_fail)
        for i in range(9):
            w.write({"kind": "step", "step": i})
        assert w.dropped == 4                   # 9 written, 5 retained
        assert len(w._buf) == 5
        assert json.loads(w._buf[0])["step"] == 4   # oldest dropped first


class TestSnapshotSinks:
    def test_stderr_summary_logs_line(self):
        reg = MetricsRegistry()
        reg.counter("supervisor.rollback").inc()
        s = reg.add_sink(StderrSummary(interval=0.0))
        reg.emit("step", step=5, step_time_ms=12.0, tokens_per_sec=100.0,
                 mfu=0.41)
        assert s.emitted >= 1
        assert s._last_step["step"] == 5

    def test_prometheus_textfile(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("step.count").inc(3)
        reg.gauge("step.mfu").set(0.45)
        reg.histogram("step.time_ms").observe(10.0)
        sink = reg.add_sink(PrometheusTextfile(
            str(tmp_path / "m.prom"), interval=0.0))
        reg.emit("step", step=0)
        text = (tmp_path / "m.prom").read_text()
        assert "# TYPE paddle_tpu_step_count counter" in text
        assert "paddle_tpu_step_count 3" in text
        assert "paddle_tpu_step_mfu 0.45" in text
        assert 'paddle_tpu_step_time_ms{quantile="0.5"} 10' in text
        assert "paddle_tpu_step_time_ms_count 1" in text


class TestMfuHelpers:
    def test_flops_per_token_matches_bench_formula(self):
        n, L, h, S = 125_000_000, 12, 768, 2048
        want = 6 * n + 12 * L * h * S // 2
        assert obs.flops_per_token(n, L, h, S, causal=True) == want
        assert obs.flops_per_token(n, L, h, S, causal=False) == \
            6 * n + 12 * L * h * S
        assert obs.flops_per_token(n) == 6 * n   # shapeless fallback

    def test_param_count_and_mfu(self):
        params = {"w": np.zeros((4, 8)), "b": np.zeros((8,))}
        assert obs.param_count(params) == 40
        assert obs.mfu(1000.0, 1e9, peak=1e13) == pytest.approx(1e-1)
        # a device outside DEVICE_SPECS (the CPU mesh) has no peak, and an
        # MFU over it is "not measured" — never another device's peak
        assert obs.peak_flops_per_sec() is None
        assert obs.mfu(1000.0, 1e9) is None


class TestAggregate:
    def _write_worker(self, mdir, wid, records, torn_tail=False):
        lines = "".join(json.dumps(r) + "\n" for r in records)
        if torn_tail:
            lines += '{"ts": 9, "kind": "st'      # mid-append death
        os.makedirs(mdir, exist_ok=True)
        with open(os.path.join(mdir, f"worker-{wid}.jsonl"), "w") as f:
            f.write(lines)

    def test_merges_workers_and_skips_torn_lines(self, tmp_path):
        run_dir = str(tmp_path)
        mdir = obs.metrics_dir(run_dir)
        self._write_worker(mdir, 0, [
            {"ts": 1.0, "kind": "supervisor.run_start"},
            {"ts": 2.0, "kind": "step", "step": 0, "step_time_ms": 10.0,
             "tokens": 64, "tokens_per_sec": 6400.0, "mfu": 0.2},
            {"ts": 3.0, "kind": "step", "step": 1, "step_time_ms": 30.0,
             "tokens": 64, "tokens_per_sec": 2133.0, "mfu": 0.1},
        ], torn_tail=True)
        self._write_worker(mdir, 1, [
            {"ts": 2.5, "kind": "step", "step": 0, "step_time_ms": 20.0,
             "tokens": 64, "tokens_per_sec": 3200.0, "mfu": 0.3},
        ])
        summary = obs.aggregate_run(run_dir)
        assert summary["workers"] == [0, 1]
        assert summary["records"] == 4            # torn line skipped
        assert summary["kinds"]["step"] == 3
        assert summary["supervisor_events"] == {
            "supervisor.run_start": 1}
        assert summary["overall"]["steps"] == 3
        assert summary["overall"]["total_tokens"] == 192.0
        assert summary["overall"]["step_time_ms"]["min"] == 10.0
        assert summary["overall"]["step_time_ms"]["max"] == 30.0
        assert summary["overall"]["mfu"]["max"] == 0.3
        assert summary["per_worker"]["1"]["steps"] == 1
        assert summary["time_range"] == [1.0, 3.0]
        on_disk = json.loads(
            (tmp_path / "metrics" / "summary.json").read_text())
        assert on_disk["records"] == 4

    def test_no_metrics_dir_returns_none(self, tmp_path):
        assert obs.aggregate_run(str(tmp_path / "nope")) is None

    def test_cli_main(self, tmp_path, capsys):
        mdir = obs.metrics_dir(str(tmp_path))
        self._write_worker(mdir, 0, [{"ts": 1.0, "kind": "step",
                                      "step": 0}])
        assert agg_mod.main([str(tmp_path)]) == 0
        assert json.loads(capsys.readouterr().out)["records"] == 1
        assert agg_mod.main([str(tmp_path / "missing")]) == 1


class TestVlogFlagCache:
    def test_cache_invalidated_by_set_flags(self):
        from paddle_tpu.framework import flags as fl
        from paddle_tpu.framework import log as fw_log
        base = fl.get_flags(["log_level"])["log_level"]
        calls = []
        orig_info = fw_log.get_logger().info
        try:
            fw_log.get_logger().info = lambda msg, *a: calls.append(msg)
            fw_log.vlog(3, "hidden")           # level 0: suppressed
            assert calls == []
            pt.set_flags({"log_level": 3})     # invalidates the cache
            fw_log.vlog(3, "shown")
            assert calls == ["shown"]
            pt.set_flags({"log_level": base})
            fw_log.vlog(3, "hidden again")
            assert calls == ["shown"]
        finally:
            fw_log.get_logger().info = orig_info
            pt.set_flags({"log_level": base})

    def test_disabled_vlog_is_cheap(self):
        from paddle_tpu.framework.log import vlog
        n = 50000
        t0 = time.perf_counter()
        for _ in range(n):
            vlog(9, "never shown %d", 1)
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, f"vlog cost {per_call * 1e6:.2f} µs/call"


class TestFsioAppend:
    def test_append_bytes(self, tmp_path):
        p = str(tmp_path / "a.jsonl")
        fsio.append_bytes(p, b"one\n")
        fsio.append_bytes(p, b"two\n")
        assert fsio.read_bytes(p) == b"one\ntwo\n"


class TestCollectiveInstrumentation:
    def test_barrier_records_latency(self):
        import paddle_tpu.distributed as dist
        reg = obs.get_registry()
        before = reg.counter("collective.barrier.calls").value
        dist.barrier()
        assert reg.counter("collective.barrier.calls").value == before + 1
        assert reg.histogram("collective.barrier.ms").count >= 1


def _tiny_model():
    net = pt.nn.Sequential(pt.nn.Linear(8, 16), pt.nn.ReLU(),
                           pt.nn.Linear(16, 4))
    model = pt.Model(net)
    model.prepare(optimizer=pt.optimizer.Adam(learning_rate=1e-3),
                  loss=pt.nn.CrossEntropyLoss())
    return model


def _tiny_data(n=32):
    rng = np.random.RandomState(0)
    x = rng.randn(n, 8).astype("float32")
    y = rng.randint(0, 4, (n,)).astype("int64")
    return list(zip(x, y))


class TestFitTelemetryE2E:
    def test_fit_emits_step_breakdown_and_mfu(self, tmp_path):
        reg = obs.get_registry()
        sink = reg.add_sink(_ListSink())
        try:
            _tiny_model().fit(_tiny_data(), batch_size=8, epochs=1,
                              verbose=0)
        finally:
            reg.remove_sink(sink)
        steps = [r for r in sink.records if r["kind"] == "step"]
        assert len(steps) == 4
        for r in steps:
            for key in ("ts", "step", "step_time_ms", "data_ms",
                        "compute_ms", "readback_ms", "tokens",
                        "tokens_per_sec", "mfu", "loss"):
                assert key in r, f"step record missing {key}"
            assert r["step_time_ms"] >= r["data_ms"]
            assert r["tokens"] == 8
            assert r["tokens_per_sec"] > 0
            assert r["mfu"] is None      # the CPU mesh has no known peak
        # instruments accumulated alongside the event stream
        assert reg.counter("step.count").value >= 4
        assert reg.histogram("step.time_ms").count >= 4
        assert reg.gauge("step.tokens_per_sec").value > 0

    def test_fit_with_supervisor_single_timeline(self, tmp_path):
        """The acceptance-criteria drill: a supervised CPU fit leaves
        <run_dir>/metrics/worker-0.jsonl whose one stream holds per-step
        breakdown records AND supervisor events."""
        from paddle_tpu.supervisor import RunSupervisor
        run_dir = str(tmp_path / "run")
        sup = RunSupervisor(run_dir, watchdog_secs=60.0, worker_id=0)
        _tiny_model().fit(_tiny_data(), batch_size=8, epochs=1, verbose=0,
                          supervisor=sup)
        path = os.path.join(run_dir, "metrics", "worker-0.jsonl")
        assert os.path.exists(path)
        recs = [json.loads(l) for l in open(path)]
        kinds = {r["kind"] for r in recs}
        assert "step" in kinds
        assert "supervisor.run_start" in kinds
        assert "supervisor.run_end" in kinds
        steps = [r for r in recs if r["kind"] == "step"]
        assert all("step_time_ms" in r and "mfu" in r
                   and "tokens_per_sec" in r for r in steps)
        # the stream is one ordered timeline: run_start precedes the
        # first step record, run_end follows the last
        ordered = [r["kind"] for r in recs]
        assert ordered.index("supervisor.run_start") \
            < ordered.index("step")
        assert ordered.index("supervisor.run_end") \
            > len(ordered) - 1 - ordered[::-1].index("step")
        # and the launcher-side aggregator reads it back
        summary = obs.aggregate_run(run_dir)
        assert summary["overall"]["steps"] == len(steps)
        assert summary["supervisor_events"]["supervisor.run_start"] == 1
