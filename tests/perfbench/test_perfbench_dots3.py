"""The dots3-note-prev configuration and its cell: the manifest's
new entries held BY NAME (never by position or count, so the next append
breaks nothing), the files they name, the window kernel's arithmetic against
hand-worked numbers, the new reader on a made-up run, and the cell's CPU
rehearsal."""
import json
import os
import subprocess
import sys
import types

import pytest

from perfbench.harness import phase_reads, window_latent
from perfbench.harness.manifest import REPO, Manifest, validate
from perfbench.harness.peaks import PEAKS

DATA = os.path.join(os.path.dirname(__file__), "data")
RUN = os.path.join(REPO, "perfbench", "run.py")
CONFIG = "dots3-note-ep16-l5"
CELL = "dots3-note-ep16-l5.agentctx-backlog"
SERVE = "serving loop (inference/engine.py, scheduler.py, kv_cache.py)"
EXPERT = "expert layer (nn/dropless_moe.py, ops/grouped_matmul.py)"
NEW = {
    "mla_window_decode_roofline.agentctx": (
        "higher", "device_trace",
        "kernels (inference/latent_attention.py, ops/grouped_matmul.py)"),
    "dsa_sparse_attn_roofline.agentctx": (
        "higher", "device_trace",
        "sparse attention (inference/sparse_attention.py)"),
    "kv_held_share.agentctx": ("lower", "program_counter", SERVE),
    "prefill_device_share.agentctx": ("lower", "program_counter", SERVE),
    "device_idle_share.agentctx": ("lower", "device_trace",
                                   "device (TPU v5e)"),
    "dsa_index_roofline.agentctx": (
        "higher", "device_trace",
        "sparse attention (inference/sparse_attention.py)"),
    "dsa_kept_share.agentctx": (
        "lower", "program_counter",
        "sparse attention (inference/sparse_attention.py)"),
    "moe_expert_roofline.agentctx": (
        "higher", "device_trace",
        "kernels (inference/latent_attention.py, ops/grouped_matmul.py)"),
    "host_slack_share.agentctx": ("higher", "program_counter", SERVE),
    "device_starved_share.agentctx": ("lower", "program_span", SERVE),
    "decode_batch_occupancy.agentctx": ("higher", "program_counter", SERVE),
}
# units other than percent
UNITS = {"moe_load_max_over_mean.agentctx": ("ratio", "lower",
                                             "program_counter", EXPERT)}
# the readers the twins share with an older cell's metric
TWINS = {"dsa_sparse_attn_roofline.agentctx":
         "dsa_sparse_attn_roofline.longctx",
         "kv_held_share.agentctx": "kv_held_share.mixedctx",
         "prefill_device_share.agentctx": "prefill_device_share.mixedctx",
         "device_idle_share.agentctx": "device_idle_share.mixedctx",
         "dsa_index_roofline.agentctx": "dsa_index_roofline.longctx",
         "moe_expert_roofline.agentctx": "moe_expert_roofline.longctx",
         "moe_load_max_over_mean.agentctx":
         "moe_load_max_over_mean.mixedctx",
         "host_slack_share.agentctx": "host_slack_share.mixedctx",
         "device_starved_share.agentctx": "device_starved_share.mixedctx",
         "decode_batch_occupancy.agentctx":
         "decode_batch_occupancy.mixedctx"}
CUT = {"num_hidden_layers": 5,
       "layer_types": ["full_attention"] + ["sliding_attention"] * 3
       + ["full_attention"],
       "n_routed_experts": 16, "vocab_size": 19008}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
          "num_key_value_heads", "index_n_heads", "index_head_dim",
          "index_topk", "swa_q_lora_rank", "swa_kv_lora_rank",
          "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
          "swa_num_attention_heads", "swa_num_key_value_heads",
          "sliding_window_size", "num_experts_per_tok")


# the published config's widths and the layer pattern's first six entries
PUBLISHED = {
    "hidden_size": 5120, "intermediate_size": 13824,
    "moe_intermediate_size": 1536, "q_lora_rank": 1024, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_attention_heads": 128, "num_key_value_heads": 128,
    "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
    "swa_q_lora_rank": 1024, "swa_kv_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_v_head_dim": 128, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "sliding_window_size": 513,
    "num_experts_per_tok": 8, "rope_theta": 80000000,
    "swa_rope_theta": 50000, "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "n_shared_experts": 1, "routed_scaling_factor": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "num_hidden_layers": 46,
    "n_routed_experts": 256, "vocab_size": 152064}
FIRST_SIX = ["full_attention", "full_attention"] + ["sliding_attention"] * 3 \
    + ["full_attention"]


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


# -- the manifest: by name -------------------------------------------------------
def test_the_configuration_entry(manifest):
    assert validate(manifest) == []
    c = manifest.config_entry(CONFIG)
    assert c["file"] == "perfbench/configs/dots3-note-ep16-l5.json"
    assert c["source"] == ("https://huggingface.co/dots-studio/"
                           "dots3-note-prev/blob/main/config.json")
    assert sorted(c["reduced"]) == sorted(CUT) and len(c["why"]) <= 200
    for key in c["reduced"]:                          # no width is cut
        assert not (key.endswith("_dim") or key.endswith("_rank"))
        assert key not in WIDTHS


def test_the_cell_entry(manifest):
    w = manifest.cell(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "agentctx-backlog", 1)
    assert "128 rows" in w["why"] and len(w["why"]) <= 200
    serve = next(m for m in manifest.data["end_to_end"]
                 if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.08
    assert {"gpt3-xl.doc-backlog", "deepseek-v2-ep4-l5.reason-backlog",
            "glm-5-ep16-l5.longctx-backlog",
            "mimo-v2-flash-ep16-l7.mixedctx-backlog"} <= set(
                serve["workloads"])
    reported = {m["name"] for m in manifest.metrics_for("end_to_end", CELL)}
    assert reported == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("name", sorted(NEW) + sorted(UNITS))
def test_a_per_layer_entry_and_its_reader(manifest, name):
    by_name = {x["name"]: x for x in manifest.data["per_layer"]}
    unit, better, source, layer = UNITS.get(name, ("%",) + NEW.get(name, ()))
    assert by_name[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "serve_tok_s", "workloads": [CELL]}
    read = manifest.load_module("layer_metrics", name).read
    # a run with nothing to read (the parent's): None, never a raise
    empty = {"job": "train", "trace": None, "peaks": None, "serve": None,
             "spans": types.SimpleNamespace(records=[]), "shape": {},
             "window": {"t0": 0.0, "t1": 1.0, "seconds": 1.0}}
    if name.startswith(("mla_", "dsa_", "kv_")):
        assert read(empty) is None
    if name in TWINS:
        # the same layer, and the same reader, as the older cell's metric
        assert by_name[TWINS[name]]["layer"] == layer
        twin = manifest.load_module("layer_metrics", TWINS[name]).read
        assert read.__code__.co_code == twin.__code__.co_code


def test_the_new_cell_took_no_older_metric_and_no_older_cell_a_new_one(
        manifest):
    reported = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert set(NEW) | set(UNITS) <= reported
    for x in manifest.data["per_layer"]:
        if x["name"] in NEW or x["name"] in UNITS:
            assert x["workloads"] == [CELL]
        elif "workloads" in x:
            assert CELL not in x["workloads"]


# -- the configuration file --------------------------------------------------------
def test_published_widths_and_the_stated_cut(manifest):
    c = manifest.load_config(CONFIG)
    for key, value in PUBLISHED.items():
        assert c[key] == CUT.get(key, value), key
    assert c["published"]["layer_types"][:6] == FIRST_SIX
    assert len(c["published"]["layer_types"]) == 46
    assert c["published"]["layer_types"].count("full_attention") == 13
    assert {k: v for k, v in c["published"].items()
            if k != "layer_types"} == {k: PUBLISHED[k] for k in CUT
                                       if k != "layer_types"}
    assert sorted(c["reduced"]) == sorted(CUT)
    for key in WIDTHS:
        assert key not in c["reduced"] and c[key] == PUBLISHED[key]
    # layer 0 and one whole published period (S, S, S, F)
    assert c["layer_types"] == CUT["layer_types"]
    assert FIRST_SIX[:1] + FIRST_SIX[2:6] == CUT["layer_types"]


def test_the_share_and_the_arithmetic_of_the_cut(manifest):
    c = manifest.load_config(CONFIG)
    assert c["entry"] == "dots3:build"
    assert (c["ep_degree"], c["ep_rank"]) == (16, 0)
    assert c["n_routed_experts"] * c["ep_degree"] == 256
    assert c["vocab_size"] * 8 == 152064
    assert c["deployment"].startswith("16 chips share each layer: routed "
                                      "experts 16 a chip")
    for key in ("apply_mla_qkv_lora_rescale", "gate_input", "window",
                "prediction_layer_and_towers", "router_bias", "dtype"):
        assert len(c["assumed"][key]) > 40
    for key in CUT:
        assert len(c["reduced"][key]) > 40
    # the count of the matrices: 2,577,137,664 = 5.15 GB in bf16
    h = c["hidden_size"]

    def attn(p, index):
        heads = c[p + "num_attention_heads"]
        nope, rope = c[p + "qk_nope_head_dim"], c[p + "qk_rope_head_dim"]
        q, r, v = c[p + "q_lora_rank"], c[p + "kv_lora_rank"], \
            c[p + "v_head_dim"]
        n = (h * q + q * heads * (nope + rope) + h * (r + rope)
             + r * heads * (nope + v) + heads * v * h + h * heads)
        if index:
            n += (q * c["index_n_heads"] * c["index_head_dim"]
                  + h * c["index_head_dim"] + h * c["index_n_heads"])
        return n
    full, sliding = attn("", True), attn("swa_", False)
    assert (full, sliding) == (144048128, 90832896)
    expert = 3 * h * c["moe_intermediate_size"]
    assert expert == 23592960
    moe = h * 256 + (c["n_routed_experts"] + 1) * expert
    assert moe == 402391040
    dense = 3 * h * c["intermediate_size"]
    total = 2 * c["vocab_size"] * h + 2 * full + 3 * sliding + dense \
        + 4 * moe
    assert total == c["parameters"]["count"] == 2_577_137_664
    assert total * 2 / 1e9 == pytest.approx(5.15, abs=0.01)
    for stem in ("routing_tie_eps", "routing_differ_share",
                 "index_tie_eps", "index_differ_share"):
        assert 0 < c[stem] < 1.5 and len(c[stem + "_why"]) > 40
        assert "TBD" not in c[stem + "_why"]


def test_traffic_parameters_of_the_issue(manifest):
    t = manifest.load_traffic("agentctx-backlog")
    assert (t["job"], t["kind"]) == ("serve", "backlog")
    assert (t["keep_waiting"], t["documents"], t["stratify_block"]) == (
        32, 512, 16)
    assert t["prompt"]["dist"] == "lognormal" and t["prompt"]["median"] == 3072
    # sigma 0.4, or the issue's named fall-back of 0.25
    assert t["prompt"]["sigma"] in (0.4, 0.25)
    assert (t["prompt"]["min"], t["prompt"]["max"]) == (2304, 8192)
    assert t["output"] == {"dist": "lognormal", "median": 1536,
                           "sigma": 0.4, "min": 768, "max": 3072}
    assert t["ramp_s"] == 40
    e = t["engine"]
    assert e["max_seqs"] == 128 and e["max_model_len"] == 11264
    assert e["kv_block_size"] in (128, 256)
    assert e["max_model_len"] >= t["prompt"]["max"] + t["output"]["max"]
    blocks = e["num_kv_blocks"]
    assert set(blocks) == {"full", "window"}
    # every prompt is past index_topk and over 4x the window
    assert t["prompt"]["min"] > 2048 and t["prompt"]["min"] > 4 * 513
    buckets = t["warm_buckets"]
    assert set(buckets) >= {512, 2048, 4096, 8192}
    assert t["check"]["prompt_lens"] == [300, 1500, 3070, 8000]
    # one checked sequence crosses a block of both pools in its 8 steps
    n = t["check"]["prompt_lens"][2]
    assert n // 128 != (n + 7) // 128
    assert (n - 513) // 128 != (n + 7 - 513) // 128
    assert t["check"]["new_tokens"] == 8 and t["trace_s"] == 3
    for why in ("why", "engine_why", "warm_buckets_why"):
        assert len(t[why]) > 40 and "TBD" not in t[why]
    assert "TBD" not in t["check"]["logits_tolerance_why"]
    # weights + pools: over 4 GB (the 25% floor) on the weights alone
    pool = e["kv_block_size"] * (blocks["full"] * 2 * (640 + 128) * 2
                                 + blocks["window"] * 3 * 1152 * 2)
    assert 5.15e9 + pool < 15e9


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "perfbench", "reference",
                            "dots3.py")).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src
    assert 'default_matmul_precision("highest")' in src


def test_kernel_names_do_not_fold_into_an_older_kernel():
    from perfbench.harness.trace_reduce import kernel_of
    assert kernel_of("mla_window_decode_bf16_128_64_1024") in (
        None, "mla_window_decode")
    assert not "mla_window_decode".startswith("mla_latent_attn")
    assert not "mla_latent_attn".startswith(window_latent.KERNEL)


# -- the arithmetic, by hand ---------------------------------------------------------
def test_window_decode_cost_by_hand():
    # 128 rows x min(context, 513) = 513 tokens x 3 window layers, rows of
    # 1,088 values, 64 heads, values of 1,024
    tokens = 128 * 513 * 3
    flops, moved = window_latent.window_decode_cost(tokens, 64, 1088, 1024)
    assert moved == tokens * 1088 * 2 == 428654592
    assert flops == tokens * 2 * 64 * 2112 == 53254029312
    # memory-bound on a v5e: 0.52 ms against 0.27 ms
    assert moved / 819e9 == pytest.approx(0.5234e-3, rel=1e-3)
    assert flops / 197e12 == pytest.approx(0.2703e-3, rel=1e-3)


# -- the reader, on a made-up run ------------------------------------------------------
def _run(monkeypatch, ops, spans):
    fake = types.SimpleNamespace(spans_between=lambda a, b: [
        ("engine.step", t0, t1, at) for t0, t1, at in spans])
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    return {"job": "serve", "peaks": PEAKS["TPU v5 lite"],
            "trace": {"ops": ops},
            "spans": types.SimpleNamespace(records=[("traced", 10.0, 13.0)]),
            "shape": {"heads": 128, "window_heads": 64,
                      "window_kv_lora_rank": 1024,
                      "window_qk_rope_head_dim": 64},
            "serve": {"stats_at_open": None, "stats_at_end": None}}


def test_the_window_roofline_from_the_traced_steps_counter(monkeypatch):
    steps = [(10.5, 10.6, {"attn_window_tokens": 196992}),
             (9.9, 10.1, {"attn_window_tokens": 10 ** 9}),     # cut: out
             (11.0, 11.2, {"moe_pairs": 7})]                   # a prefill
    run = _run(monkeypatch, {
        "mla_window_decode_bf16_128_64_1024": [3, 3 * 0.25e-3]}, steps)
    want = 196992 * 1088 * 2 / 819e9
    assert window_latent.mla_window_decode_roofline(run) == pytest.approx(
        100 * want / 0.75e-3, rel=1e-6)
    # a program without the kernel (the parent's), or another model's shape
    run["trace"]["ops"] = {"mla_latent_attn_bf16": [5, 1e-3]}
    assert window_latent.mla_window_decode_roofline(run) is None
    run = _run(monkeypatch, {"mla_window_decode_x": [5, 1e-3]}, steps)
    run["shape"] = {"heads": 16}
    assert window_latent.mla_window_decode_roofline(run) is None
    run = _run(monkeypatch, {"mla_window_decode_x": [5, 1e-3]}, steps[2:])
    assert window_latent.mla_window_decode_roofline(run) is None


# -- the rehearsal ---------------------------------------------------------------------
def test_rehearsal_manifest_is_valid():
    m = Manifest(os.path.join(DATA, "manifest-dots3.json"), [DATA])
    assert validate(m) == []
    assert m.load_config("tiny-dots3")["entry"] == "dots3:build"
    assert {x["name"] for x in m.data["per_layer"]} >= set(NEW) | set(UNITS)


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_through_the_same_job(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0", BENCH_RUN="ignored",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "tiny.agentctx", "--seed",
         "3900000017", "--seconds", "1", "--manifest",
         os.path.join(DATA, "manifest-dots3.json"), "--root", DATA,
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
    # 4 expert layers x every cached token of the four checked sequences;
    # 2 full layers x 4 sequences x 4 positions x index_topk 8
    assert check["routing"]["tokens"] == 4 * (9 + 20 + 33 + 50 + 4 * 3)
    assert check["index"]["entries"] == sum(
        2 * min(8, n + j) for n in (9, 20, 33, 50) for j in range(4))
    if trace:
        # counters and spans are read on the CPU too; a device trace is not
        assert {"kv_held_share.agentctx", "prefill_device_share.agentctx",
                "dsa_kept_share.agentctx", "moe_load_max_over_mean.agentctx",
                "host_slack_share.agentctx",
                "decode_batch_occupancy.agentctx"} <= set(last["metrics"])
        # the device trace's readers, and the stretch it measured
        assert not {"mla_window_decode_roofline.agentctx",
                    "dsa_index_roofline.agentctx",
                    "moe_expert_roofline.agentctx",
                    "device_starved_share.agentctx"} & set(last["metrics"])
        counts = json.loads(next(x for x in lines if x.startswith(
            "engine_counts: ")).split(": ", 1)[1])
        assert counts["serve.moe_pairs_dropped"] == 0
        assert 0 < counts["serve.attn_window_tokens"]
        assert 0 < counts["serve.dsa_selected_tokens"] \
            < counts["serve.dsa_context_tokens"]
        assert counts["preemptions_since_open"] == 0
        assert counts["model_gauges"] == {
            "serve.kv_full_bytes_per_token": 2 * (128 + 16) * 2.0,
            "serve.kv_window_bytes_per_seq": 3 * 128 * 2.0 * 12}
        pools = json.loads(next(x for x in lines if x.startswith(
            "kv_pools: ")).split(": ", 1)[1])
        assert set(pools) == {"full", "window"}
        assert pools["window"]["freed_behind"] > 0
        # a ring of 4 blocks a row at most: 8 rows
        assert pools["window"]["high_water"] <= 8 * 4
        assert pools["window"]["block_bytes"] == 3 * 4 * 128 * 2
    else:
        assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}


def test_the_precision_controls_run_the_cells_check_with_other_casts():
    """The tool that brought the limits' readings, at the tiny size on the
    CPU: each control is the cell's own comparison, and every lower
    precision reads at least what bf16 as served reads."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "tools",
                                      "precision_controls_dots3.py"),
         "--workload", "tiny.agentctx", "--seeds", "5", "--manifest",
         os.path.join(DATA, "manifest-dots3.json"), "--root", DATA],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    read = {line.split(":", 1)[0].split()[1]: json.loads(
        line.split(": ", 1)[1]) for line in out.stdout.splitlines()
        if line.startswith("control ")}
    assert list(read) == ["bf16_as_served", "int8_weights_per_channel",
                          "e4m3_weights_per_channel",
                          "int8_latent_rows_per_token",
                          "int8_index_keys_per_token",
                          "int8_pages_per_token", "e4m3_pages_per_token"]
    served = read.pop("bf16_as_served")
    assert served["routing_differ"][1] == 4 * (9 + 20 + 33 + 50 + 4 * 3)
    # at this size a cached row's rounding is lost among the weights'; the
    # weights' and e4m3 pages' read above bf16 as served
    for name in ("int8_weights_per_channel", "e4m3_weights_per_channel",
                 "e4m3_pages_per_token"):
        assert read[name]["logits_max_abs_diff"] > served[
            "logits_max_abs_diff"], name
