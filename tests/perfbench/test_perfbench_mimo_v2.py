"""The MiMo-V2-Flash configuration and its cell (PR 35): the manifest's new
entries held BY NAME (never by position or count, so the next append breaks
nothing), the files they name, the grouped-query arithmetic against
hand-worked numbers, the new readers on a made-up run, and the cell's CPU
rehearsal."""
import json
import os
import subprocess
import sys
import types

import pytest

from perfbench.harness import gqa_arith, gqa_reads, phase_reads
from perfbench.harness.manifest import REPO, Manifest, validate
from perfbench.harness.peaks import PEAKS

DATA = os.path.join(os.path.dirname(__file__), "data")
RUN = os.path.join(REPO, "perfbench", "run.py")
CONFIG = "mimo-v2-flash-ep16-l7"
CELL = "mimo-v2-flash-ep16-l7.mixedctx-backlog"
SERVE = "serving loop (inference/engine.py, scheduler.py, kv_cache.py)"
GQA = "kernels (inference/gqa_attention.py)"
PR35 = {
    "gqa_full_decode_roofline.mixedctx": ("%", "higher", "device_trace",
                                          GQA),
    "gqa_window_decode_roofline.mixedctx": ("%", "higher", "device_trace",
                                            GQA),
    "kv_held_share.mixedctx": ("%", "lower", "program_counter", SERVE),
    "moe_expert_roofline.mixedctx": (
        "%", "higher", "device_trace",
        "kernels (inference/latent_attention.py, ops/grouped_matmul.py)"),
    "moe_load_max_over_mean.mixedctx": (
        "ratio", "lower", "program_counter",
        "expert layer (nn/dropless_moe.py, ops/grouped_matmul.py)"),
    "decode_batch_occupancy.mixedctx": ("%", "higher", "program_counter",
                                        SERVE),
    "prefill_time_share.mixedctx": ("%", "lower", "host_clock", SERVE),
    "device_idle_share.mixedctx": ("%", "lower", "device_trace",
                                   "device (TPU v5e)"),
    "step_host_only_share.mixedctx": ("%", "lower", "program_span", SERVE),
}
CUT = {"num_hidden_layers": 7, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0],
       "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "n_routed_experts": 16,
       "vocab_size": 19072}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "v_head_dim", "swa_head_dim", "swa_v_head_dim",
          "num_attention_heads", "swa_num_attention_heads",
          "num_key_value_heads", "swa_num_key_value_heads",
          "sliding_window", "num_experts_per_tok", "partial_rotary_factor",
          "attention_value_scale")


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row.get("name") == "MiMo-V2-Flash":
                return row
    return None


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


# -- the manifest: by name -------------------------------------------------------
def test_the_manifest_is_valid_with_the_new_entries(manifest):
    assert validate(manifest) == []
    cells = manifest.data["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert four == ["gpt3-xl-l16.train-hybrid4"]       # still one cell of four
    # 2 + 14 runs a cell of run_seconds + 60, 180 more a cell, 1200 spare
    runs = 2 + 14 * len(cells)
    assert runs * (manifest.data["run_seconds"] + 60) + 180 * len(cells) \
        + 1200 <= 43200


def test_the_configuration_entry(manifest):
    c = manifest.config_entry(CONFIG)
    assert c["file"] == "perfbench/configs/mimo-v2-flash-ep16-l7.json"
    assert c["source"] == ("https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/"
                           "blob/main/config.json")
    assert sorted(c["reduced"]) == sorted(CUT) and len(c["why"]) <= 200
    for key in c["reduced"]:                          # no width is cut
        assert not (key.endswith("_dim") or key.endswith("_rank"))
        assert key not in WIDTHS


def test_the_cell_entry(manifest):
    w = manifest.cell(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "mixedctx-backlog", 1)
    assert "128 rows" in w["why"] and "16x" in w["why"] \
        and len(w["why"]) <= 200
    serve = next(m for m in manifest.data["end_to_end"]
                 if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.08
    # what was there keeps its cells
    assert {"gpt3-xl.doc-backlog", "deepseek-v2-ep4-l5.reason-backlog",
            "glm-5-ep16-l5.longctx-backlog"} <= set(serve["workloads"])
    reported = {m["name"] for m in manifest.metrics_for("end_to_end", CELL)}
    assert reported == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("name", sorted(PR35))
def test_a_per_layer_entry_and_its_reader(manifest, name):
    by_name = {x["name"]: x for x in manifest.data["per_layer"]}
    unit, better, source, layer = PR35[name]
    assert by_name[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "serve_tok_s", "workloads": [CELL]}
    read = manifest.load_module("layer_metrics", name).read
    assert callable(read)
    # a run with nothing to read (the parent's): None, never a raise
    empty = {"job": "train", "trace": None, "peaks": None, "serve": None,
             "spans": types.SimpleNamespace(records=[]), "shape": {},
             "window": {"t0": 0.0, "t1": 1.0, "seconds": 1.0}}
    if name.startswith(("gqa_", "moe_", "kv_")):
        assert read(empty) is None


def test_the_cell_reports_what_it_lists(manifest):
    reported = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert reported == set(PR35) | {"compile_cache_hit_share",
                                    "recompiles_in_window"}
    # the twins read what their .reason siblings read
    by_name = {x["name"]: x for x in manifest.data["per_layer"]}
    for stem in ("decode_batch_occupancy", "prefill_time_share",
                 "device_idle_share", "step_host_only_share",
                 "moe_expert_roofline", "moe_load_max_over_mean"):
        twin = by_name[f"{stem}.reason"]
        assert by_name[f"{stem}.mixedctx"] == dict(
            twin, name=f"{stem}.mixedctx", workloads=[CELL])
    # no older metric took the new cell, and no older cell a new metric
    for x in manifest.data["per_layer"]:
        if x["name"] not in PR35:
            assert CELL not in x.get("workloads", [])


# -- the configuration file --------------------------------------------------------
def test_published_widths_and_the_stated_cut(manifest):
    c = manifest.load_config(CONFIG)
    row = _catalog_row()
    if row is None:
        pytest.skip("no catalog beside the model-configs guide here")
    for key, value in row["config"].items():
        assert c[key] == CUT.get(key, value), key
    assert c["published"] == {k: row["config"][k] for k in CUT}
    assert sorted(c["reduced"]) == sorted(CUT)
    for key in WIDTHS:
        assert key not in c["reduced"] and c[key] == row["config"][key]
    # the cut keeps the leading dense layer and one whole published period
    assert row["config"]["hybrid_layer_pattern"][6:12] == CUT[
        "hybrid_layer_pattern"][1:]
    assert row["config"]["moe_layer_freq"][:1] == [0]


def test_the_share_and_the_arithmetic_of_the_cut(manifest):
    c = manifest.load_config(CONFIG)
    assert c["entry"] == "mimo_v2:build"
    assert (c["ep_degree"], c["ep_rank"]) == (16, 0)
    assert c["n_routed_experts"] * c["ep_degree"] == 256
    assert c["vocab_size"] * 8 == 152576
    assert c["deployment"].startswith("16 chips share each layer: routed "
                                      "experts 16 a chip")
    for key in ("rotary_dims_and_pairing", "window_counts_the_query",
                "value_scale", "sink", "router_bias", "initializer_range",
                "dtype", "prediction_layers"):
        assert len(c["assumed"][key]) > 40
    for key in CUT:
        assert len(c["reduced"][key]) > 40
    # ISSUE 35's count: 3,429,955,392 parameters = 6.86 GB in bf16
    h, heads, dk, dv = (c["hidden_size"], c["num_attention_heads"],
                        c["head_dim"], c["v_head_dim"])
    def attn(n_kv, sinks):
        return (h * heads * dk + h * n_kv * dk + h * n_kv * dv
                + heads * dv * h + sinks)
    full = attn(c["num_key_value_heads"], 0)
    window = attn(c["swa_num_key_value_heads"], heads)
    assert (full, window) == (89128960, 94371904)
    expert = 3 * h * c["moe_intermediate_size"]
    moe = h * 256 + 256 + c["n_routed_experts"] * expert
    dense = 3 * h * c["intermediate_size"]
    norms = 2 * h
    total = (2 * c["vocab_size"] * h + h
             + full + dense + norms
             + 5 * (window + moe + norms) + full + moe + norms)
    assert total == 3_429_955_392
    assert total * 2 / 1e9 == pytest.approx(6.86, abs=0.01)
    for stem in ("routing_tie_eps", "routing_differ_share"):
        assert 0 < c[stem] < 1.5 and len(c[stem + "_why"]) > 40
        assert "TBD" not in c[stem + "_why"]


def test_traffic_parameters_of_the_issue(manifest):
    t = manifest.load_traffic("mixedctx-backlog")
    assert (t["job"], t["kind"]) == ("serve", "backlog")
    assert (t["keep_waiting"], t["documents"], t["stratify_block"]) == (
        32, 512, 16)
    assert t["prompt"]["dist"] == "lognormal" and t["prompt"]["median"] == 2048
    # sigma 0.7 clipped 256-8192, or the issue's named fall-back
    assert (t["prompt"]["sigma"], t["prompt"]["min"], t["prompt"]["max"]) \
        in ((0.7, 256, 8192), (0.5, 512, 6144))
    assert t["output"] == {"dist": "lognormal", "median": 1024,
                           "sigma": 0.4, "min": 512, "max": 2048}
    assert t["ramp_s"] in (40, 60)
    e = t["engine"]
    assert e["max_seqs"] in (128, 96) and e["max_model_len"] == 10240
    assert e["kv_block_size"] == 128
    assert e["max_model_len"] >= t["prompt"]["max"] + t["output"]["max"]
    blocks = e["num_kv_blocks"]
    assert set(blocks) == {"full", "window"}
    # the window pool holds every row's ring; the full pool the mean
    # contexts twice over
    assert blocks["window"] >= 2 * e["max_seqs"]
    assert blocks["window"] <= 3 * e["max_seqs"]
    assert blocks["full"] * 128 >= 2 * e["max_seqs"] * 3100
    buckets = t["warm_buckets"]
    assert set(buckets) >= {256, 512, 1024, 2048, 4096, 8192}
    assert t["check"]["prompt_lens"] == [100, 1020, 3000, 7000]
    assert t["check"]["new_tokens"] == 8 and t["trace_s"] == 3
    for why in ("why", "engine_why", "warm_buckets_why"):
        assert len(t[why]) > 40 and "TBD" not in t[why]
    assert "TBD" not in t["check"]["logits_tolerance_why"]
    # weights + pools fill the chip: over 10 GB of 15.75
    pool = 128 * (blocks["full"] * 2 * 2560 + blocks["window"] * 5 * 5120)
    assert 6.86e9 + pool >= 10e9


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "perfbench", "reference",
                            "mimo_v2.py")).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src
    assert 'default_matmul_precision("highest")' in src


def test_kernel_names_do_not_fold_into_an_older_kernel():
    from perfbench.harness.trace_reduce import kernel_of
    for name in ("gqa_full_decode", "gqa_window_decode"):
        assert kernel_of(name + "_bf16_128_64_128") in (None, name)
    assert not "gqa_full_decode_x".startswith(gqa_reads.WINDOW_KERNEL)
    assert not "gqa_window_decode_x".startswith(gqa_reads.FULL_KERNEL)


# -- the arithmetic, by hand ---------------------------------------------------------
def test_full_decode_cost_by_hand():
    # 128 rows at 3,100 live tokens through 2 full layers: 793,600 tokens
    # of 4 heads x (192 + 128) bf16 values
    flops, moved = gqa_arith.gqa_decode_cost(793600, 64, 4, 192, 128)
    assert moved == 793600 * 2560 == 2031616000
    assert flops == 793600 * 2 * 64 * 320 == 32505856000
    # memory-bound on a v5e: 2.48 ms against 0.165 ms
    assert moved / 819e9 == pytest.approx(2.48e-3, rel=1e-2)
    assert flops / 197e12 == pytest.approx(0.165e-3, rel=1e-2)


def test_window_decode_cost_by_hand():
    # 128 rows x min(context, 128) = 128 tokens x 5 window layers
    flops, moved = gqa_arith.gqa_decode_cost(128 * 128 * 5, 64, 8, 192, 128)
    assert moved == 81920 * 5120 == 419430400
    assert flops == 81920 * 40960
    assert moved / 819e9 == pytest.approx(0.512e-3, rel=1e-2)


def test_held_share_by_hand():
    # a step's 128 rows at 3,100 tokens: 25 full blocks a row, 2 window
    # blocks a row; a block takes 128 x 5,120 B and 128 x 25,600 B
    bytes_ = {"full": 128 * 5120, "window": 128 * 25600}
    share = gqa_arith.held_share({"full": 128 * 25, "window": 128 * 2},
                                 bytes_)
    assert share == pytest.approx(100 * (25 * 5120 + 2 * 25600)
                                  / (25 * 30720), abs=1e-9)
    assert share == pytest.approx(23.33, abs=0.01)
    assert gqa_arith.held_share({"full": 0, "window": 0}, bytes_) is None


# -- the readers, on a made-up run ------------------------------------------------------
def _run(monkeypatch, ops, spans):
    """A run record with a traced stretch ``[10, 13)`` holding ``spans``
    ((t0, t1, attributes) of ``engine.step``) and the traced ``ops``."""
    fake = types.SimpleNamespace(spans_between=lambda a, b: [
        ("engine.step", t0, t1, at) for t0, t1, at in spans])
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    return {"job": "serve", "peaks": PEAKS["TPU v5 lite"],
            "trace": {"ops": ops},
            "spans": types.SimpleNamespace(records=[("traced", 10.0, 13.0)]),
            "shape": {"heads": 64, "qk_head_dim": 192, "v_head_dim": 128,
                      "full_kv_heads": 4, "window_kv_heads": 8},
            "serve": {"stats_at_open": None, "stats_at_end": None}}


def test_rooflines_from_the_traced_steps_counters(monkeypatch):
    steps = [(10.5, 10.6, {"attn_full_tokens": 793600,
                           "attn_window_tokens": 81920}),
             (9.9, 10.1, {"attn_full_tokens": 10 ** 9,         # cut: left out
                          "attn_window_tokens": 10 ** 9}),
             (11.0, 11.2, {"moe_pairs": 7})]                   # a prefill
    run = _run(monkeypatch, {
        "gqa_full_decode_bf16_128_64_128": [2, 2 * 1.6e-3],
        "gqa_window_decode_bf16_128_64_128": [5, 5 * 0.2e-3]}, steps)
    assert gqa_reads.gqa_full_decode_roofline(run) == pytest.approx(
        100 * 2.4806e-3 / 3.2e-3, rel=1e-3)
    assert gqa_reads.gqa_window_decode_roofline(run) == pytest.approx(
        100 * 0.51212e-3 / 1e-3, rel=1e-3)
    # a program without the kernels, or without the counters: nothing
    run["trace"]["ops"] = {"mla_latent_attn_bf16": [5, 1e-3]}
    assert gqa_reads.gqa_full_decode_roofline(run) is None
    assert gqa_reads.gqa_window_decode_roofline(run) is None
    run = _run(monkeypatch, {"gqa_full_decode_x": [5, 1e-3]}, steps[2:])
    assert gqa_reads.gqa_full_decode_roofline(run) is None
    run["shape"] = {"heads": 16}                  # another model's shapes
    assert gqa_reads.gqa_full_decode_roofline(run) is None


def test_held_share_from_the_windows_counts(monkeypatch, capsys):
    run = _run(monkeypatch, {}, [])
    assert gqa_reads.kv_held_share(run) is None
    pools = lambda full, window: {"kv_pools": {           # noqa: E731
        "full": {"blocks_live": full, "block_bytes": 128 * 5120,
                 "total": 7168, "high_water": 4000},
        "window": {"blocks_live": window, "block_bytes": 128 * 25600,
                   "total": 256, "high_water": 256}}}
    run["serve"] = {"stats_at_open": pools(1000, 100),
                    "stats_at_end": pools(1000 + 3200, 100 + 256)}
    assert gqa_reads.kv_held_share(run) == pytest.approx(
        100 * (3200 * 5120 + 256 * 25600) / (3200 * 30720))
    run["serve"]["stats_at_open"] = None        # counted from the start
    assert gqa_reads.kv_held_share(run) == pytest.approx(
        100 * (4200 * 5120 + 356 * 25600) / (4200 * 30720))
    gqa_reads.say_kv_pools(run)
    assert '"high_water": 256' in capsys.readouterr().out
    # a model of one kind of layer (every older cell): nothing
    run["serve"]["stats_at_end"] = {"kv_pools": {"full": {
        "blocks_live": 5, "block_bytes": 1}}}
    assert gqa_reads.kv_held_share(run) is None
    run["serve"]["stats_at_end"] = {}                     # the parent's stats
    assert gqa_reads.kv_held_share(run) is None


# -- the rehearsal ---------------------------------------------------------------------
def test_rehearsal_manifest_is_valid():
    m = Manifest(os.path.join(DATA, "manifest-mimo.json"), [DATA])
    assert validate(m) == []
    assert m.load_config("tiny-mimo")["entry"] == "mimo_v2:build"
    assert {x["name"] for x in m.data["per_layer"]} >= set(PR35)


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_through_the_same_job(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0", BENCH_RUN="ignored",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "tiny.mixedctx", "--seed",
         "3500000017", "--seconds", "1", "--manifest",
         os.path.join(DATA, "manifest-mimo.json"), "--root", DATA,
         "--trace", str(trace), "--rehearsal"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True, [x for x in lines if "check" in x]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(v["value"] is None for v in last["metrics"].values())
    check = json.loads(next(x for x in lines if x.startswith(
        "choices_check: ")).split(": ", 1)[1])
    assert check["ok"]
    # 3 expert layers x every cached token of the four checked sequences
    assert check["routing"]["tokens"] == 3 * (9 + 20 + 33 + 50 + 4 * 3)
    assert check["routing"]["max_margin"] <= check["limits"][
        "routing_tie_eps"]
    if trace:
        # counters and spans are read on the CPU too; a device trace is not
        assert {"kv_held_share.mixedctx", "decode_batch_occupancy.mixedctx",
                "moe_load_max_over_mean.mixedctx",
                "prefill_time_share.mixedctx"} <= set(last["metrics"])
        assert "gqa_full_decode_roofline.mixedctx" not in last["metrics"]
        counts = json.loads(next(x for x in lines if x.startswith(
            "engine_counts: ")).split(": ", 1)[1])
        assert counts["serve.moe_pairs_dropped"] == 0
        assert 0 < counts["serve.attn_window_tokens"] \
            < counts["serve.attn_full_tokens"]
        assert counts["preemptions_since_open"] == 0
        assert counts["model_gauges"] == {
            "serve.kv_full_bytes_per_token": 2 * 2 * 40 * 2.0,
            "serve.kv_window_bytes_per_seq": 2 * 4 * 40 * 2.0 * 8}
        pools = json.loads(next(x for x in lines if x.startswith(
            "kv_pools: ")).split(": ", 1)[1])
        assert set(pools) == {"full", "window"}
        assert pools["window"]["freed_behind"] > 0
        # a ring of 3 blocks a row at most: 8 rows
        assert pools["window"]["high_water"] <= 8 * 3
        assert pools["window"]["block_bytes"] == 4 * 2 * 4 * 40 * 2
    else:
        assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}


def test_the_precision_controls_run_the_cells_check_with_other_casts():
    """The tool that brought the limits' readings, at the tiny size on the
    CPU: bf16 as served reads least, every lower precision more, and each
    control is the cell's own comparison (372 routed tokens)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "tools",
                                      "precision_controls_mimo_v2.py"),
         "--workload", "tiny.mixedctx", "--seed", "5", "--manifest",
         os.path.join(DATA, "manifest-mimo.json"), "--root", DATA],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    read = {line.split(":", 1)[0].split()[1]: json.loads(
        line.split(": ", 1)[1]) for line in out.stdout.splitlines()
        if line.startswith("control ")}
    assert list(read) == ["bf16_as_served", "int8_weights_per_channel",
                          "e4m3_weights_per_channel", "int8_pages_per_token",
                          "e4m3_pages_per_token"]
    served = read.pop("bf16_as_served")
    assert served["routing_differ"][1] == 372
    for name, r in read.items():
        assert r["logits_max_abs_diff"] > served["logits_max_abs_diff"], name
        assert r["routing_differ"][0] >= served["routing_differ"][0], name
