"""The reduction from a trace to numbers: on a synthetic trace whose answers
are known by hand, and on a small recorded trace cut from a traced chip run
of PR 24 (``data/trace_*.json.gz``, written by ``run.py --dump-trace`` and
cut with ``trace_reduce.cut``)."""
import os

import pytest

from perfbench.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def synthetic():
    # times in ns; two devices; device 1 starts 10 ns later
    ops0 = [["fusion.12", 0, 100, "bf16[8,128]"],
            ["fusion.13", 100, 50, "bf16[8,128]"],
            ["while.3", 200, 300, ""],                 # a loop ...
            ["jvp_flash_fwd_.7", 210, 100, ""],        # ... and its body
            ["all-reduce.4", 320, 100, "f32[64]"],
            ["copy.9", 700, 100, "bf16[1856,16,16,128]"],
            ["all-gather-start.2", 900, 20, ""],
            ["paged_decode.5", 930, 40, ""]]
    ops1 = [[n, s + 10, d, x] for n, s, d, x in ops0]
    host = [["traced", 0, 1000], ["engine.step", 480, 250],
            ["submit", 800, 95], ["batch_make", 150, 45]]
    return {"devices": [{"name": "/device:TPU:0", "events": ops0},
                        {"name": "/device:TPU:1", "events": ops1}],
            "host": host}


@pytest.fixture(scope="module")
def red():
    return tr.reduce(synthetic())


def test_busy_is_the_union_averaged_over_devices(red):
    # device 0: 0-150, 200-500, 700-800, 900-920, 930-970 = 610 ns
    # device 1: the same shifted by 10 ns, all inside the window
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(610e-9)


def test_a_loop_and_its_body_are_not_counted_twice(red):
    ops = red["ops"]
    assert ops["while"][1] == pytest.approx(100e-9)      # 300 - 100 - 100
    assert ops["flash_fwd"] == [1, pytest.approx(100e-9)]
    assert sum(v[1] for v in ops.values()) == pytest.approx(610e-9)


def test_stable_names_drop_the_running_number_and_keep_the_shape(red):
    assert red["ops"]["fusion_bf16_8_128"] == [2, pytest.approx(150e-9)]
    assert "copy_bf16_1856_16_16_128" in red["ops"]
    assert tr.stable_name("fusion.123") == "fusion"
    assert tr.stable_name("jvp_flash_bwd_dq_.3", "f32[4]") == \
        "flash_bwd_dq_f32_4"
    assert tr.kernel_of("custom-call.4") is None


def test_kernels_by_their_own_names(red):
    assert red["kernels"] == {"flash_fwd": [1, pytest.approx(100e-9)],
                              "paged_decode": [1, pytest.approx(40e-9)]}


def test_collective_share(red):
    # all-reduce 100 + all-gather-start 20, the same on both devices
    assert red["collective_s"] == pytest.approx(120e-9)


def test_idle_gaps_are_named_by_the_harness_span(red):
    gaps = dict(red["idle_gaps"])
    # 150-200 batch_make covers 45 of 50; 500-700 engine.step covers all;
    # 800-900 submit covers 95; 920-930 and 970-1000 nobody
    assert gaps["batch_make"] == pytest.approx(50e-9)
    assert gaps["engine.step"] == pytest.approx(200e-9)
    assert gaps["submit"] == pytest.approx(100e-9)
    assert gaps["_no_span_"] == pytest.approx(40e-9)
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - 610e-9)


def test_top_lists_are_sorted_and_short():
    many = synthetic()
    many["devices"][0]["events"] += [[f"op.{i}", 2000 + 10 * i, 5, f"f32[{i}]"]
                                     for i in range(30)]
    many["host"][0] = ["traced", 0, 3000]
    r = tr.reduce(many)
    assert len(r["device_ops"]) == 10
    secs = [s for _, s in r["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert r["device_ops"][0][0].endswith("__x2")      # name, then calls


def test_without_the_window_span_the_stretch_is_first_to_last_operation():
    raw = synthetic()
    raw["host"] = [h for h in raw["host"] if h[0] != "traced"]
    r = tr.reduce(raw)
    assert r["window_s"] == pytest.approx(980e-9)       # 0 .. 980 (device 1)


def test_cut_keeps_what_starts_inside():
    part = tr.cut(synthetic(), 200, 800)
    assert [e[0] for e in part["devices"][0]["events"]] == [
        "while.3", "jvp_flash_fwd_.7", "all-reduce.4", "copy.9"]
    assert {h[0] for h in part["host"]} == {"traced", "engine.step"}
    assert ["traced", 200, 600] in part["host"]


def test_no_device_plane_reduces_to_nothing():
    assert tr.reduce({"devices": [], "host": []})["busy_s"] == 0.0


def test_union_and_self_times():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    ev = [["a", 0, 100], ["b", 10, 20], ["c", 40, 20], ["d", 45, 5]]
    assert tr.self_times(ev) == [60, 20, 15, 5]


RECORDED = {
    # cut from PR 24's first traced chip runs (one v5e): 0.30 s of the
    # gpt3-125m.train stretch and 0.45 s of the gpt3-xl.chat stretch
    "trace_train.json.gz": {
        "window_s": 0.30, "busy_s": 0.299850927,
        "kernels": {"flash_fwd": 17, "flash_bwd_dkdv": 18,
                    "flash_bwd_dq": 18},
        "kernel_s": 0.113717809, "top_gap": "wait"},
    "trace_chat.json.gz": {
        "window_s": 0.45, "busy_s": 0.371533693,
        "kernels": {"paged_decode": 103}, "kernel_s": 0.201091975,
        "top_gap": "engine.step"},
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace(name):
    want = RECORDED[name]
    raw = tr.load_raw(os.path.join(DATA, name))
    r = tr.reduce(raw)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert {k: v[0] for k, v in r["kernels"].items()} == want["kernels"]
    assert sum(v[1] for v in r["kernels"].values()) == pytest.approx(
        want["kernel_s"], rel=1e-6)
    # per-operation self times add up to the busy time: nothing is counted
    # twice and nothing is lost
    assert sum(v[1] for v in r["ops"].values()) == pytest.approx(
        r["busy_s"], rel=1e-6)
    # the idle gaps add up to the rest of the stretch, each named
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["idle_gaps"][0][0] == want["top_gap"]
    assert r["collective_s"] == 0.0            # one chip: no collective
    # every name is stable: no compiler's running number, no HLO operands
    assert all("%" not in k and " " not in k for k in r["ops"])


def test_recorded_chat_trace_shows_the_pool_copies():
    r = tr.reduce(tr.load_raw(os.path.join(DATA, "trace_chat.json.gz")))
    calls, seconds = r["ops"]["copy_bf16_1856_16_16_128"]
    assert calls > 400 and seconds / calls == pytest.approx(0.373e-3,
                                                            rel=0.02)
    assert r["device_ops"][0][0].startswith("paged_decode_bf16_128_16_128")


def test_parse_op_drops_the_operands():
    line = ("%fusion.9 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[8,128]{1,0} "
            "%jvp_flash_bwd_dq_.3), kind=kLoop")
    assert tr.parse_op(line) == ("fusion.9", "bf16[8,128]")
    assert tr.kernel_of(tr.parse_op(line)[0]) is None
    assert tr.parse_op("%t.1 = (f32[8,512]{1,0}, f32[4]{0}) fusion(s32[] "
                       "%x)") == ("t.1", "f32[8,512]")
    assert tr.parse_op("paged_decode.3") == ("paged_decode.3", "")
