"""The GLM-5 configuration and its cell (PR 32): the manifest's new entries
held BY NAME (never by position or count, so the next append breaks
nothing), the files they name, the sparse-attention arithmetic against
hand-worked numbers, the new readers on a made-up run, and the cell's CPU
rehearsal."""
import json
import os
import subprocess
import sys
import types

import pytest

from perfbench.harness import phase_reads, sparse_arith, sparse_reads
from perfbench.harness.manifest import REPO, Manifest, validate
from perfbench.harness.peaks import PEAKS

DATA = os.path.join(os.path.dirname(__file__), "data")
RUN = os.path.join(REPO, "perfbench", "run.py")
CONFIG = "glm-5-ep16-l5"
CELL = "glm-5-ep16-l5.longctx-backlog"
SPARSE = "sparse attention (inference/sparse_attention.py)"
PR32 = {
    "dsa_index_roofline.longctx": ("%", "higher", "device_trace", SPARSE),
    "dsa_sparse_attn_roofline.longctx": ("%", "higher", "device_trace",
                                         SPARSE),
    "dsa_kept_share.longctx": ("%", "lower", "program_counter", SPARSE),
    "moe_expert_roofline.longctx": (
        "%", "higher", "device_trace",
        "kernels (inference/latent_attention.py, ops/grouped_matmul.py)"),
    "decode_batch_occupancy.longctx": (
        "%", "higher", "program_counter",
        "serving loop (inference/engine.py, scheduler.py, kv_cache.py)"),
    "prefill_time_share.longctx": (
        "%", "lower", "host_clock",
        "serving loop (inference/engine.py, scheduler.py, kv_cache.py)"),
    "device_idle_share.longctx": ("%", "lower", "device_trace",
                                  "device (TPU v5e)"),
    "step_host_only_share.longctx": (
        "%", "lower", "program_span",
        "serving loop (inference/engine.py, scheduler.py, kv_cache.py)"),
}
CUT = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
       "n_routed_experts": 16, "vocab_size": 19360,
       "num_nextn_predict_layers": 0}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "qk_head_dim", "v_head_dim", "head_dim",
          "index_head_dim", "index_n_heads", "index_topk",
          "num_attention_heads", "num_experts_per_tok")


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row.get("name") == "GLM-5":
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
    assert len(four) <= max(1, len(cells) // 4)


def test_the_configuration_entry(manifest):
    c = manifest.config_entry(CONFIG)
    assert c["file"] == "perfbench/configs/glm-5-ep16-l5.json"
    assert c["source"].startswith(
        "https://huggingface.co/zai-org/GLM-5/blob/main/config.json")
    assert "DeepSeek-V3.2-Exp" in c["source"] and len(c["source"]) <= 200
    assert sorted(c["reduced"]) == sorted(CUT)
    for key in c["reduced"]:                          # no width is cut
        assert not (key.endswith("_dim") or key.endswith("_rank"))
        assert key not in WIDTHS


def test_the_cell_entry(manifest):
    w = manifest.cell(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "longctx-backlog", 1)
    assert "1/16" in w["why"] and "DeepSeek-V2" in w["why"]
    serve = next(m for m in manifest.data["end_to_end"]
                 if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.08
    # what was there keeps its cells
    assert {"gpt3-xl.doc-backlog", "deepseek-v2-ep4-l5.reason-backlog"} \
        <= set(serve["workloads"])
    reported = {m["name"] for m in manifest.metrics_for("end_to_end", CELL)}
    assert reported == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("name", sorted(PR32))
def test_a_per_layer_entry_and_its_reader(manifest, name):
    by_name = {x["name"]: x for x in manifest.data["per_layer"]}
    unit, better, source, layer = PR32[name]
    assert by_name[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "serve_tok_s", "workloads": [CELL]}
    read = manifest.load_module("layer_metrics", name).read
    assert callable(read)
    # a run with nothing to read: None, never a raise
    empty = {"job": "train", "trace": None, "peaks": None, "serve": None,
             "spans": types.SimpleNamespace(records=[]), "shape": {},
             "window": {"t0": 0.0, "t1": 1.0, "seconds": 1.0}}
    if name.startswith(("dsa_", "moe_")):
        assert read(empty) is None


def test_the_cell_reports_what_it_lists(manifest):
    reported = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert reported == set(PR32) | {"compile_cache_hit_share",
                                    "recompiles_in_window"}
    # the twins read what their .reason siblings read
    by_name = {x["name"]: x for x in manifest.data["per_layer"]}
    for stem in ("decode_batch_occupancy", "prefill_time_share",
                 "device_idle_share", "step_host_only_share",
                 "moe_expert_roofline"):
        twin = by_name[f"{stem}.reason"]
        assert by_name[f"{stem}.longctx"] == dict(
            twin, name=f"{stem}.longctx", workloads=[CELL])


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


def test_the_share_and_the_arithmetic_of_the_cut(manifest):
    c = manifest.load_config(CONFIG)
    assert c["entry"] == "glm5:build"
    assert (c["ep_degree"], c["ep_rank"]) == (16, 0)
    assert c["n_routed_experts"] * c["ep_degree"] == 256
    assert c["vocab_size"] * 8 == 154880
    assert c["deployment"].startswith("16 chips share each layer: routed "
                                      "experts 16 a chip")
    for key in ("index_rope_dims", "index_rotation_and_fp8", "router_bias",
                "latent_row", "index_keys", "initializer_range"):
        assert len(c["assumed"][key]) > 40
    for key in CUT:
        assert len(c["reduced"][key]) > 40
    # ISSUE 32's count: 3,909.6M parameters = 7.82 GB in bf16
    h, qr, r = c["hidden_size"], c["q_lora_rank"], c["kv_lora_rank"]
    heads = c["num_attention_heads"]
    attn = (h * qr + qr * heads * 256 + h * 576 + r * heads * 448
            + heads * 256 * h + qr * 32 * 128 + h * 128 + h * 32)
    assert attn == pytest.approx(174.4e6, rel=1e-3)
    expert = 3 * h * c["moe_intermediate_size"]
    layer = attn + 16 * expert + expert + h * 256
    dense = attn + 3 * h * c["intermediate_size"]
    total = 2 * c["vocab_size"] * h + dense + 4 * layer
    assert total * 2 / 1e9 == pytest.approx(7.82, abs=0.01)
    for stem in ("routing_tie_eps", "routing_differ_share",
                 "index_tie_eps", "index_differ_share"):
        assert 0 < c[stem] < 1.5 and len(c[stem + "_why"]) > 40


def test_traffic_parameters_of_the_issue(manifest):
    t = manifest.load_traffic("longctx-backlog")
    assert (t["job"], t["kind"]) == ("serve", "backlog")
    assert (t["keep_waiting"], t["documents"], t["stratify_block"]) == (
        8, 256, 16)
    assert t["prompt"]["dist"] == "lognormal" and t["prompt"]["median"] == 8192
    assert (t["prompt"]["min"], t["prompt"]["max"]) == (4096, 16384)
    assert t["prompt"]["sigma"] in (0.35, 0.25)        # 0.25: the fallback
    assert t["output"] == {"dist": "lognormal", "median": 1024,
                           "sigma": 0.4, "min": 512, "max": 2048}
    e = t["engine"]
    assert (e["max_seqs"], e["max_model_len"]) == (16, 18432)
    assert e["max_model_len"] % e["kv_block_size"] == 0
    # every row at its longest fits: nothing is preempted
    assert e["num_kv_blocks"] * e["kv_block_size"] >= 16 * 18432
    assert t["warm_buckets"] == [4096, 8192, 16384]
    assert t["check"]["prompt_lens"] == [2300, 4600, 9000, 15000]
    assert t["check"]["new_tokens"] == 8 and t["trace_s"] == 3
    assert t["ramp_s"] % 10 == 0
    for why in ("why", "engine_why", "warm_buckets_why"):
        assert len(t[why]) > 40 and "TBD" not in t[why]
    assert "TBD" not in t["check"]["logits_tolerance_why"]
    # weights + pool fill the chip: at least 9.8 GB of 15.75
    pool = e["num_kv_blocks"] * e["kv_block_size"] * (640 + 128) * 2 * 5
    assert 7.82e9 + pool >= 9.8e9


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "perfbench", "reference",
                            "glm5.py")).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    assert "approx_max_k" not in src


def test_kernel_names_do_not_fold_into_an_older_kernel():
    from perfbench.harness.trace_reduce import kernel_of
    for name in ("dsa_index_scores", "dsa_sparse_attn"):
        assert kernel_of(name + "_f32_16_1_18432") in (None, name)


# -- the arithmetic, by hand ---------------------------------------------------------
def test_index_scores_cost_by_hand():
    # 16 rows at 8,704 live tokens: 139,264 keys of 128 bf16 values
    flops, moved = sparse_arith.index_scores_cost(139264, 32, 128)
    assert moved == 139264 * 256 == 35651584
    assert flops == 139264 * 8192 == 1140850688
    # memory-bound on a v5e: 43.5 us against 5.8 us
    assert moved / 819e9 == pytest.approx(43.5e-6, rel=1e-2)
    assert flops / 197e12 == pytest.approx(5.8e-6, rel=1e-2)


def test_sparse_attn_cost_by_hand():
    # 16 rows x 2,048 selected latent rows of 576 values
    flops, moved = sparse_arith.sparse_attn_cost(32768, 64, 576, 512)
    assert moved == 32768 * 1152 == 37748736
    assert flops == 32768 * 2 * 64 * 1088 == 4563402752
    assert moved / 819e9 == pytest.approx(46.1e-6, rel=1e-2)
    assert flops / 197e12 == pytest.approx(23.2e-6, rel=1e-2)


def test_kept_share_by_hand():
    assert sparse_arith.kept_share(2048 * 16, 8704 * 16) == pytest.approx(
        23.53, abs=0.01)
    assert sparse_arith.kept_share(100, 100) == 100.0
    assert sparse_arith.kept_share(0, 0) is None


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
            "shape": {"heads": 64, "kv_lora_rank": 512,
                      "qk_rope_head_dim": 64, "index_heads": 32,
                      "index_dim": 128},
            "serve": {"stats_at_open": None, "stats_at_end": None}}


def test_rooflines_from_the_traced_steps_counters(monkeypatch):
    steps = [(10.5, 10.6, {"dsa_context_tokens": 5 * 139264,
                           "dsa_selected_tokens": 5 * 32768}),
             (9.9, 10.1, {"dsa_context_tokens": 10 ** 9,       # cut: left out
                          "dsa_selected_tokens": 10 ** 9}),
             (11.0, 11.2, {"moe_pairs": 7})]                   # a prefill
    run = _run(monkeypatch, {
        "dsa_index_scores_f32_16_1_18432": [5, 5 * 100e-6],
        "dsa_sparse_attn_bf16_16_64_512": [5, 5 * 60e-6]}, steps)
    assert sparse_reads.dsa_index_roofline(run) == pytest.approx(
        100 * 43.53e-6 / 100e-6, rel=1e-2)
    assert sparse_reads.dsa_sparse_attn_roofline(run) == pytest.approx(
        100 * 46.09e-6 / 60e-6, rel=1e-2)
    # a program without the kernels, or without the counters: nothing
    run["trace"]["ops"] = {"mla_latent_attn_bf16": [5, 1e-3]}
    assert sparse_reads.dsa_index_roofline(run) is None
    assert sparse_reads.dsa_sparse_attn_roofline(run) is None
    run = _run(monkeypatch, {"dsa_index_scores_x": [5, 1e-3]}, steps[2:])
    assert sparse_reads.dsa_index_roofline(run) is None


def test_kept_share_from_the_windows_counters(monkeypatch):
    run = _run(monkeypatch, {}, [])
    assert sparse_reads.dsa_kept_share(run) is None
    counts = lambda sel, ctx: {"model_counts": {"counters": {
        "serve.dsa_selected_tokens": sel, "serve.dsa_context_tokens": ctx},
        "gauges": {}}}
    run["serve"] = {"stats_at_open": counts(1000, 2000),
                    "stats_at_end": counts(1000 + 2048, 2000 + 8192)}
    assert sparse_reads.dsa_kept_share(run) == pytest.approx(25.0)
    run["serve"]["stats_at_open"] = None        # counted from the start
    assert sparse_reads.dsa_kept_share(run) == pytest.approx(
        100 * 3048 / 10192)
    run["serve"]["stats_at_end"] = {"model_counts": {"counters": {
        "serve.moe_pairs": 3}, "gauges": {}}}    # a model with no indexer
    assert sparse_reads.dsa_kept_share(run) is None


# -- the rehearsal ---------------------------------------------------------------------
def test_rehearsal_manifest_is_valid():
    m = Manifest(os.path.join(DATA, "manifest-glm5.json"), [DATA])
    assert validate(m) == []
    assert m.load_config("tiny-glm5")["entry"] == "glm5:build"


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_through_the_same_job(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0", BENCH_RUN="ignored",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "tiny.longctx", "--seed",
         "3200000019", "--seconds", "1", "--manifest",
         os.path.join(DATA, "manifest-glm5.json"), "--root", DATA,
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
    assert check["routing"]["tokens"] == 2 * (9 + 20 + 33 + 50 + 4 * 3)
    # 3 layers x 4 compared positions x min(8, t + 1) entries a sequence
    assert check["index"]["entries"] == 3 * 4 * 4 * 8
    assert check["index"]["max_margin"] <= check["limits"]["index_tie_eps"]
    if trace:
        # counters and spans are read on the CPU too; a device trace is not
        assert {"dsa_kept_share.longctx", "decode_batch_occupancy.longctx",
                "prefill_time_share.longctx"} <= set(last["metrics"])
        assert "dsa_index_roofline.longctx" not in last["metrics"]
        counts = json.loads(next(x for x in lines if x.startswith(
            "engine_counts: ")).split(": ", 1)[1])
        assert counts["serve.moe_pairs_dropped"] == 0
        assert 0 < counts["serve.dsa_selected_tokens"] \
            < counts["serve.dsa_context_tokens"]
        assert counts["model_gauges"]["serve.kv_index_bytes_per_token"] \
            == 3 * 16 * 2.0
    else:
        assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
