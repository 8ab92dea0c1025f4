"""Interconnect microscope tests (ISSUE 20): the ICI spec table, the
algorithm-aware cost model, the sub-budget sum invariant, the synthetic
drill, the schema v3 round-trip, and the doctor's comm_budget verdict.

Pinned math doctrine (mirrors test_roofline): the cost-model factors
and modeled wire times are asserted against hand-computed figures, so
a silent change to the model is a test failure, not a drifting
dashboard.
"""

import pytest

from paddle_tpu.bench import schema
from paddle_tpu.observability import interconnect as ic
from paddle_tpu.observability import doctor
from paddle_tpu.observability.registry import split_labels


# -- ICI spec table ---------------------------------------------------------
class TestIciSpec:
    def test_known_kinds(self):
        from paddle_tpu.observability.mfu import DEVICE_SPECS
        # one key set for both tables: the device_kind strings jax reports
        assert set(ic.ICI_SPECS) == set(DEVICE_SPECS)
        for kind, row in ic.ICI_SPECS.items():
            spec = ic.ici_spec(kind)
            assert spec["known"] is True
            assert spec["gen"] == row["gen"] == DEVICE_SPECS[kind]["gen"]
            assert spec["ici_gbps"] == row["ici_gbps"]
            assert spec["links"] == row["links"]
        assert ic.ici_spec("TPU v5 lite")["ici_gbps"] == 1600.0

    def test_v4_figures(self):
        spec = ic.ici_spec("TPU v4")
        assert spec["ici_gbps"] == 2400.0
        assert spec["links"] == 6
        assert spec["topology"] == "3d_torus"

    def test_unknown_carries_no_figures(self):
        # the marketing name is not a device_kind; neither is a CPU
        for kind in ("cpu", "TPU v5e", "some-future-chip"):
            spec = ic.ici_spec(kind)
            assert spec["known"] is False
            assert spec["gen"] is None
            assert "ici_gbps" not in spec and "links" not in spec
            assert ic.modeled_wire_time_ms("all_reduce", 1e6, 4, spec) == 0.0


# -- cost model -------------------------------------------------------------
class TestWireFactor:
    def test_ring_all_reduce(self):
        # 2(n-1)/n: reduce-scatter + all-gather rings
        assert ic.wire_factor("all_reduce", 8) == pytest.approx(2 * 7 / 8)
        assert ic.wire_factor("sync_gradients", 4) == pytest.approx(1.5)

    def test_gather_scatter_family(self):
        for op in ("all_gather", "reduce_scatter", "broadcast", "reduce",
                   "scatter"):
            assert ic.wire_factor(op, 8) == pytest.approx(7 / 8), op

    def test_all_to_all_bisection_penalty(self):
        # (n-1)/n for small groups, × n/4 once the torus bisection binds
        assert ic.wire_factor("all_to_all", 4) == pytest.approx(3 / 4)
        assert ic.wire_factor("all_to_all", 8) == pytest.approx(
            (7 / 8) * 2.0)
        assert ic.wire_factor("ragged_all_to_all", 16) == pytest.approx(
            (15 / 16) * 4.0)

    def test_permute_and_free_ops(self):
        assert ic.wire_factor("send_recv_permute", 8) == 1.0
        assert ic.wire_factor("ppermute", 2) == 1.0
        assert ic.wire_factor("split", 8) == 0.0
        assert ic.wire_factor("barrier", 8) == 0.0

    def test_single_rank_ships_nothing(self):
        assert ic.wire_factor("all_reduce", 1) == 0.0
        assert ic.wire_factor("all_reduce", 0) == 0.0
        assert ic.wire_factor("all_reduce", None) == 0.0

    def test_unknown_op_crosses_once(self):
        assert ic.wire_factor("mystery_collective", 8) == 1.0


class TestModeledWireTime:
    def test_v4_all_gather_pinned(self):
        # v4: 2400 Gbps / 6 links / 8 = 50 GB/s per link; the
        # bidirectional ring uses two links -> 100 GB/s.  1 GB payload
        # all-gathered over 8 ranks ships 0.875 GB -> 8.75 ms.
        spec = ic.ici_spec("TPU v4")
        t = ic.modeled_wire_time_ms("all_gather", 1e9, 8, spec)
        assert t == pytest.approx(8.75)

    def test_v5e_all_reduce_pinned(self):
        # v5e: 1600/4/8 = 50 GB/s per link, ring 100 GB/s; all_reduce
        # over 4 ranks ships 1.5x the payload: 1 MB -> 0.015 ms
        spec = ic.ici_spec("TPU v5 lite")
        t = ic.modeled_wire_time_ms("all_reduce", 1e6, 4, spec)
        assert t == pytest.approx(1e6 * 1.5 / 100e9 * 1e3)

    def test_zero_payload_or_solo(self):
        spec = ic.ici_spec("TPU v4")
        assert ic.modeled_wire_time_ms("all_reduce", 0, 8, spec) == 0.0
        assert ic.modeled_wire_time_ms("all_reduce", 1e9, 1, spec) == 0.0


# -- sub-budget assembly ----------------------------------------------------
def _per_op(**over):
    rec = {"op": "all_reduce", "axis": "dp", "participants": 8,
           "calls": 1.0, "ms": 2.0, "payload_bytes": 1e6}
    rec.update(over)
    return rec


class TestBuildBlock:
    def test_sum_invariant_by_construction(self):
        blk = ic.build_block(
            10.0, [_per_op(), _per_op(op="all_gather", ms=3.0)],
            spec=ic.ici_spec("TPU v4"))
        total = sum(e["measured_ms"] for e in blk["entries"])
        assert total == pytest.approx(blk["comm_bucket_ms"], abs=1e-6)
        assert ic.unattributed_ms(blk) == pytest.approx(5.0)
        assert ic.attributed_total_ms(blk) == pytest.approx(5.0)

    def test_negative_unattributed_still_sums(self):
        # nested observation (reduce wraps all_reduce) can attribute
        # MORE than the bucket — the signed remainder absorbs it
        blk = ic.build_block(1.0, [_per_op(ms=2.0)],
                             spec=ic.ici_spec("TPU v4"))
        assert ic.unattributed_ms(blk) == pytest.approx(-1.0)
        total = sum(e["measured_ms"] for e in blk["entries"])
        assert total == pytest.approx(blk["comm_bucket_ms"], abs=1e-6)

    def test_efficiency_is_modeled_over_measured(self):
        spec = ic.ici_spec("TPU v4")
        blk = ic.build_block(10.0, [_per_op()], spec=spec)
        e = blk["entries"][0]
        want = ic.modeled_wire_time_ms("all_reduce", 1e6, 8, spec)
        assert e["modeled_ms"] == pytest.approx(want, abs=1e-6)
        assert e["efficiency"] == pytest.approx(want / 2.0, abs=1e-4)
        assert e["wire_bytes"] == pytest.approx(1e6 * 2 * 7 / 8)

    def test_unknown_device_has_no_model(self):
        blk = ic.build_block(10.0, [_per_op()], spec=ic.ici_spec("cpu"))
        e = blk["entries"][0]
        assert blk["device"]["known"] is False
        # measured attribution still happens; the model refuses to guess
        assert e["measured_ms"] == pytest.approx(2.0)
        assert e["modeled_ms"] is None
        assert e["efficiency"] is None
        assert blk["modeled_ms_total"] is None
        assert blk["hlo_modeled_ms"] is None
        assert blk["overlapped_ms"] is None

    def test_hlo_ops_and_overlap_estimate(self):
        spec = ic.ici_spec("TPU v4")
        hlo = {"all-reduce": {"count": 2, "bytes": 1e9,
                              "participants": 8}}
        blk = ic.build_block(1.0, [_per_op()], hlo_comm=hlo, spec=spec)
        rec = blk["hlo_ops"]["all-reduce"]
        want = ic.modeled_wire_time_ms("all_reduce", 1e9, 8, spec)
        assert rec["modeled_ms"] == pytest.approx(want, abs=1e-5)
        assert blk["hlo_modeled_ms"] == pytest.approx(want, abs=1e-5)
        # exposed = the whole comm bucket; anything modeled beyond it is
        # what XLA's schedule hid behind compute
        assert blk["exposed_ms"] == pytest.approx(1.0)
        assert blk["overlapped_ms"] == pytest.approx(
            max(0.0, want - 1.0), abs=1e-5)

    def test_hlo_default_participants_backfill(self):
        spec = ic.ici_spec("TPU v4")
        hlo = {"all-gather": {"count": 1, "bytes": 1e6,
                              "participants": None}}
        blk = ic.build_block(1.0, None, hlo_comm=hlo, spec=spec,
                             default_participants=4)
        assert blk["hlo_ops"]["all-gather"]["participants"] == 4

    def test_degraded_block(self):
        blk = ic.degraded_block(5.0, reason="test reason",
                                spec=ic.ici_spec("TPU v4"))
        assert blk["degraded"] == "test reason"
        assert ic.attributed_total_ms(blk) == 0.0
        assert ic.unattributed_ms(blk) == pytest.approx(5.0)


class TestInflationDrill:
    def test_injects_named_op_axis(self, monkeypatch):
        monkeypatch.setenv(ic.INFLATE_ENV, "all_to_all:ep:0.8")
        blk = ic.build_block(10.0, [_per_op()],
                             spec=ic.ici_spec("TPU v4"))
        assert blk["injected"] == {"op": "all_to_all", "axis": "ep",
                                   "frac": 0.8}
        named = next(e for e in blk["entries"]
                     if e["op"] == "all_to_all")
        assert named["axis"] == "ep"
        assert named["measured_ms"] == pytest.approx(8.0)
        # the invariant survives the drill
        total = sum(e["measured_ms"] for e in blk["entries"])
        assert total == pytest.approx(10.0, abs=1e-6)

    def test_rescales_existing_entries(self, monkeypatch):
        monkeypatch.setenv(ic.INFLATE_ENV, "all_reduce:dp:0.5")
        blk = ic.build_block(
            10.0, [_per_op(ms=2.0), _per_op(op="all_gather", ms=2.0)],
            spec=ic.ici_spec("TPU v4"))
        named = next(e for e in blk["entries"]
                     if e["op"] == "all_reduce")
        other = next(e for e in blk["entries"]
                     if e["op"] == "all_gather")
        assert named["measured_ms"] == pytest.approx(5.0)
        # the other attributed entry absorbs the rest of the bucket
        assert other["measured_ms"] == pytest.approx(5.0)
        assert ic.unattributed_ms(blk) == pytest.approx(0.0, abs=1e-6)

    def test_bad_spec_is_ignored(self, monkeypatch):
        for bad in ("all_to_all:ep", "all_to_all", "a:b:notafloat", ":"):
            monkeypatch.setenv(ic.INFLATE_ENV, bad)
            blk = ic.build_block(10.0, [_per_op()],
                                 spec=ic.ici_spec("TPU v4"))
            assert blk["injected"] is None, bad

    def test_zero_bucket_skips_drill(self, monkeypatch):
        monkeypatch.setenv(ic.INFLATE_ENV, "all_to_all:ep:0.8")
        blk = ic.build_block(0.0, None, spec=ic.ici_spec("TPU v4"))
        assert blk["injected"] is None


# -- schema v3 round-trip ---------------------------------------------------
def _mk_row(interconnect=None, phases=None):
    return schema.new_row(
        "gpt_pretrain_fused", "smoke",
        step_times_ms=[10.0] * 8,
        phases_ms=phases or {"data": 1.0, "compute": 7.0,
                             "readback": 1.0, "collective": 1.0},
        interconnect=interconnect)


class TestSchemaV3:
    def test_version_and_metrics(self):
        assert schema.SCHEMA_VERSION == 3
        assert 3 in schema.KNOWN_SCHEMA_VERSIONS
        assert schema.COMM_METRICS == ("comm_modeled_ms",
                                       "comm_overlapped_ms",
                                       "comm_unattributed_ms")
        for m in schema.COMM_METRICS:
            assert m in schema.METRICS

    def test_new_row_synthesizes_degraded_block(self):
        row = _mk_row()
        blk = row["interconnect"]
        assert blk is not None and blk["degraded"]
        assert schema.validate_row(row) == []
        # the synthesized block's bucket tracks the roofline comm bucket
        rl_comm = row["roofline"]["buckets_ms"]["comm"]
        assert blk["comm_bucket_ms"] == pytest.approx(rl_comm, abs=1e-6)

    def test_explicit_block_round_trips(self):
        row = _mk_row()
        rl_comm = float(row["roofline"]["buckets_ms"]["comm"])
        blk = ic.build_block(rl_comm, [_per_op(ms=rl_comm / 2)],
                             spec=ic.ici_spec("TPU v4"))
        row2 = _mk_row(interconnect=blk)
        assert schema.validate_row(row2) == []

    def test_validate_catches_sum_violation(self):
        row = _mk_row()
        row["interconnect"]["entries"][0]["measured_ms"] += 5.0
        errs = schema.validate_row(row)
        assert any("sum" in e or "bucket" in e for e in errs), errs

    def test_validate_catches_bucket_mismatch(self):
        row = _mk_row()
        row["interconnect"]["comm_bucket_ms"] += 7.0
        for e in row["interconnect"]["entries"]:
            if e["op"] == ic.UNATTRIBUTED:
                e["measured_ms"] += 7.0
        errs = schema.validate_row(row)
        assert any("roofline" in e for e in errs), errs

    def test_metric_value_reads_comm_axes(self):
        row = _mk_row()
        blk = row["interconnect"]
        assert (schema.metric_value(row, "comm_unattributed_ms")
                == blk["unattributed_ms"])
        assert (schema.metric_value(row, "comm_modeled_ms")
                == blk["modeled_ms_total"])
        assert (schema.metric_value(row, "comm_overlapped_ms")
                == blk["overlapped_ms"])


# -- doctor verdict ---------------------------------------------------------
def _bench_rec(ic_block, measured=10.0, scenario="gpt_pretrain_fused"):
    return {"kind": "bench.row", "scenario": scenario, "ts": 1.0,
            "roofline": {"measured_step_ms": measured},
            "interconnect": {
                "comm_bucket_ms": ic_block["comm_bucket_ms"],
                "unattributed_ms": ic_block["unattributed_ms"],
                "overlapped_ms": ic_block["overlapped_ms"],
                "entries": ic_block["entries"],
                "injected": ic_block["injected"],
                "degraded": bool(ic_block.get("degraded"))}}


class TestDoctorCommBudget:
    def test_names_dominant_op_and_axis(self):
        blk = ic.build_block(5.0, [_per_op(ms=4.0)],
                             spec=ic.ici_spec("TPU v4"))
        (f,) = doctor.check_comm_budget({0: [_bench_rec(blk)]})
        assert f["kind"] == "comm_budget"
        assert f["data"]["op"] == "all_reduce"
        assert f["data"]["axis"] == "dp"
        assert f["data"]["efficiency"] is not None
        assert "all_reduce[axis=dp]" in f["title"]

    def test_quiet_below_threshold(self):
        blk = ic.build_block(1.0, [_per_op(ms=0.5)],
                             spec=ic.ici_spec("TPU v4"))
        assert doctor.check_comm_budget({0: [_bench_rec(blk)]}) == []

    def test_honest_when_unattributed_dominates(self):
        blk = ic.degraded_block(5.0, spec=ic.ici_spec("TPU v4"))
        (f,) = doctor.check_comm_budget({0: [_bench_rec(blk)]})
        assert f["data"]["op"] == ic.UNATTRIBUTED
        assert f["data"]["axis"] is None
        assert any("lower bound" in ev for ev in f["evidence"])

    def test_injected_fires_and_is_flagged(self, monkeypatch):
        monkeypatch.setenv(ic.INFLATE_ENV, "all_to_all:ep:0.8")
        blk = ic.build_block(1.0, [_per_op(ms=0.2)],
                             spec=ic.ici_spec("TPU v4"))
        # share is only 10% of the step — the injected marker alone
        # must make the drill verdict fire, flagged as staged
        (f,) = doctor.check_comm_budget({0: [_bench_rec(blk)]})
        assert f["data"]["op"] == "all_to_all"
        assert f["data"]["axis"] == "ep"
        assert any("drill" in ev for ev in f["evidence"])

    def test_newest_row_wins(self):
        old = ic.build_block(5.0, [_per_op(ms=4.0)],
                             spec=ic.ici_spec("TPU v4"))
        new = ic.build_block(5.0, [_per_op(op="all_gather", axis="mp",
                                           ms=4.0)],
                             spec=ic.ici_spec("TPU v4"))
        r_old = _bench_rec(old)
        r_old["ts"] = 1.0
        r_new = _bench_rec(new)
        r_new["ts"] = 2.0
        (f,) = doctor.check_comm_budget({0: [r_old, r_new]})
        assert f["data"]["op"] == "all_gather"


# -- label plumbing ---------------------------------------------------------
class TestSplitLabels:
    def test_labeled(self):
        base, labels = split_labels("collective.all_reduce.ms[axis=dp,n=8]")
        assert base == "collective.all_reduce.ms"
        assert labels == {"axis": "dp", "n": "8"}

    def test_unlabeled_passthrough(self):
        assert split_labels("collective.all_reduce.ms") == (
            "collective.all_reduce.ms", {})

    def test_comm_bound_reads_both_name_forms(self):
        def window(name):
            snap = {name: {"type": "histogram", "count": 8, "sum": 40.0,
                           "p50": 5.0, "p99": 5.0}}
            steps = [{"kind": "step", "step_time_ms": 10.0}
                     for _ in range(8)]
            return {0: steps + [{"kind": "metrics.snapshot",
                                 "snapshot": snap}]}
        for name in ("collective.all_reduce.ms",
                     "collective.all_reduce.ms[axis=dp,n=8]"):
            findings = doctor.check_comm_bound(window(name), frac=0.25)
            assert len(findings) == 1, name
            assert findings[0]["data"]["op"] == "all_reduce"

    def test_comm_bound_no_double_count_across_labels(self):
        # the same op on two axes: two family members, one op verdict
        snap = {
            "collective.all_reduce.ms[axis=dp,n=8]":
                {"type": "histogram", "count": 8, "sum": 40.0,
                 "p50": 5.0, "p99": 5.0},
            "collective.all_reduce.ms[axis=mp,n=2]":
                {"type": "histogram", "count": 8, "sum": 48.0,
                 "p50": 6.0, "p99": 6.0},
        }
        steps = [{"kind": "step", "step_time_ms": 10.0}
                 for _ in range(8)]
        workers = {0: steps + [{"kind": "metrics.snapshot",
                                "snapshot": snap}]}
        findings = doctor.check_comm_bound(workers, frac=0.25)
        assert len(findings) == 1
        f = findings[0]
        # worst family member wins; its axis is named
        assert f["data"]["p50_ms"] == 6.0
        assert f["data"]["axis"] == "mp"
