"""The DeepSeek-V2 configuration and its cell (PR 28): the manifest's new
entries and the files they name, the two roofline counts on a fixture, the
new readers on a made-up run, and the cell's CPU rehearsal."""
import json
import os
import subprocess
import sys
import types

import pytest

from perfbench.harness import (expert_reads, latent_arith, moe_arith,
                               phase_reads)
from perfbench.harness.manifest import REPO, Manifest, validate
from perfbench.harness.peaks import PEAKS

DATA = os.path.join(os.path.dirname(__file__), "data")
RUN = os.path.join(REPO, "perfbench", "run.py")
CELL = "deepseek-v2-ep4-l5.reason-backlog"
GPT_CONFIGS = ["gpt3-125m", "gpt3-xl", "gpt3-xl-l16"]
PR26 = [f"{stem}.{suffix}" for stem in (
    "step_plan_share", "step_inputs_share", "step_logits_copy_share",
    "step_accept_share", "step_host_only_share")
    for suffix in ("chat", "backlog")]
PR28 = ["mla_decode_roofline.reason", "moe_expert_roofline.reason",
        "moe_load_max_over_mean.reason", "decode_batch_occupancy.reason",
        "prefill_time_share.reason", "device_idle_share.reason",
        "step_host_only_share.reason", "step_plan_share.reason",
        "step_inputs_share.reason", "step_logits_copy_share.reason",
        "step_accept_share.reason"]
KERNELS = "kernels (inference/latent_attention.py, ops/grouped_matmul.py)"
# the catalog row's `config` (model-configs guide, DeepSeek-V2), as published
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


# -- the manifest ---------------------------------------------------------------
def test_entries_are_appended_and_what_was_there_keeps_its_place(manifest):
    """Every clause of ``test_issue_names_are_there`` and of
    ``test_the_ten_entries_and_their_readers``, against the manifest as it
    is now.  Those two pin the lists' tails as PR 24 and PR 26 left them
    (exactly three configurations; the ten entries last), so they fail
    once a configuration and its metrics are appended; only a
    ``benchmark`` PR may edit their files (PERF.md section 7)."""
    d = manifest.data
    assert validate(manifest) == []
    assert {m["name"] for m in d["end_to_end"]} == {
        "train_tok_s_chip", "tpot_ms_p90", "serve_tok_s", "setup_s"}
    assert not any("ttft" in m["name"] for m in d["end_to_end"])
    assert [c["name"] for c in d["configs"]] == GPT_CONFIGS + [
        "deepseek-v2-ep4-l5"]
    assert [w["name"] for w in d["workloads"] if w["chips"] == 4] == [
        "gpt3-xl-l16.train-hybrid4"]
    assert [w["name"] for w in d["workloads"]][-1] == CELL
    assert len(d["workloads"]) == 5
    by_name = {x["name"]: x for x in d["per_layer"]}
    loop = by_name["prefill_time_share.chat"]["layer"]
    cells = {"chat": ("gpt3-xl.chat", "tpot_ms_p90"),
             "backlog": ("gpt3-xl.doc-backlog", "serve_tok_s")}
    for name in PR26:
        cell, moves = cells[name.rsplit(".", 1)[1]]
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "lower",
            "source": "program_span", "layer": loop, "moves": moves,
            "workloads": [cell]}
        assert callable(manifest.load_module("layer_metrics", name).read)
    names = [x["name"] for x in d["per_layer"]]
    new = len(PR26) + len(PR28)
    assert names[-new:] == PR26 + PR28
    assert not [x for x in d["per_layer"][:-new]
                if x["source"] == "program_span"]
    serve = next(m for m in d["end_to_end"] if m["name"] == "serve_tok_s")
    assert serve["workloads"] == ["gpt3-xl.doc-backlog", CELL]
    assert serve["bound"] == 0.08 and d["run_seconds"] == 51


def test_the_new_entries(manifest):
    d = manifest.data
    c = manifest.config_entry("deepseek-v2-ep4-l5")
    assert c["source"].startswith(
        "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/"
        "config.json; arXiv:2405.04434")
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    w = manifest.cell(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "deepseek-v2-ep4-l5", "reason-backlog", 1)
    by_name = {x["name"]: x for x in d["per_layer"]}
    layers = {x["layer"] for x in d["per_layer"][:-len(PR28)]}
    for name in PR28:
        x = by_name[name]
        assert x["workloads"] == [CELL] and x["moves"] == "serve_tok_s"
        assert callable(manifest.load_module("layer_metrics", name).read)
        if name.endswith("_roofline.reason"):
            assert x["layer"] == KERNELS          # the files they time
        elif name != "moe_load_max_over_mean.reason":
            assert x["layer"] in layers           # a layer already named
    for stem in ("plan", "inputs", "logits_copy", "accept", "host_only"):
        twin = by_name[f"step_{stem}_share.backlog"]
        assert by_name[f"step_{stem}_share.reason"] == dict(
            twin, name=f"step_{stem}_share.reason", workloads=[CELL])
    assert by_name["mla_decode_roofline.reason"]["source"] == "device_trace"
    assert by_name["moe_load_max_over_mean.reason"]["source"] \
        == "program_counter"
    reported = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert reported == set(PR28) | {"compile_cache_hit_share",
                                    "recompiles_in_window"}


def test_published_widths_and_the_stated_cut(manifest):
    c = manifest.load_config("deepseek-v2-ep4-l5")
    cut = {"num_hidden_layers": 5, "n_routed_experts": 40,
           "vocab_size": 25600}
    for key, value in PUBLISHED.items():
        assert c[key] == cut.get(key, value), key
    assert sorted(c["reduced"]) == sorted(cut)
    assert c["published"] == {k: PUBLISHED[k] for k in cut}
    assert (c["ep_degree"], c["ep_rank"]) == (4, 0)
    assert c["n_routed_experts"] * c["ep_degree"] == 160
    assert c["deployment"].startswith("4 chips share each layer: routed "
                                      "experts 40 a chip (2 of 8 groups)")
    assert c["entry"] == "deepseek_v2:build"
    # the routing limits: a bf16 reading (widest margin 0.123, 9.9% of the
    # choices other than the reference's own) passes, an int8 one does not
    # (weights 0.309, pages 14.2%); my chip runs, PR 28
    assert 0.123 < c["routing_tie_eps"] < 0.309 and c["routing_tie_eps_why"]
    assert 0.099 < c["routing_differ_share"] < 0.142
    assert c["routing_differ_share_why"]
    # the sizes the issue reckons: 5,163.7M parameters = 10.33 GB in bf16
    h, r, qr = c["hidden_size"], c["kv_lora_rank"], c["q_lora_rank"]
    heads = c["num_attention_heads"]
    attn = (h * qr + qr * heads * 192 + h * 576 + r * heads * 256
            + heads * 128 * h)
    expert = 3 * h * c["moe_intermediate_size"]
    layer = attn + 40 * expert + 2 * expert + h * 160
    dense = attn + 3 * h * c["intermediate_size"]
    total = 2 * c["vocab_size"] * h + dense + 4 * layer
    assert round(total / 1e6, 1) == pytest.approx(5163.7, abs=0.3)


def test_traffic_parameters_of_the_issue(manifest):
    t = manifest.load_traffic("reason-backlog")
    assert (t["job"], t["kind"]) == ("serve", "backlog")
    assert (t["keep_waiting"], t["documents"]) == (32, 256)
    assert t["prompt"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                           "min": 64, "max": 1024}
    assert t["output"] == {"dist": "lognormal", "median": 1024,
                           "sigma": 0.4, "min": 512, "max": 2048}
    assert (t["engine"]["max_seqs"], t["engine"]["max_model_len"]) == (
        256, 4096)
    assert (t["stratify_block"], t["ramp_s"], t["trace_s"]) == (16, 20, 3)
    assert t["warm_buckets"][:5] == [64, 128, 256, 512, 1024]
    assert t["check"]["prompt_lens"] == [72, 160, 300, 520]
    assert t["check"]["new_tokens"] == 8
    for why in ("why", "engine_why", "warm_buckets_why"):
        assert len(t[why]) > 40 and "PLACEHOLDER" not in t[why]
    assert "PLACEHOLDER" not in t["check"]["logits_tolerance_why"]
    # between the largest bf16 reading and the smallest int8-weights one
    assert 0.252 < t["check"]["logits_tolerance"] < 0.530
    # the pool: no sequence of this traffic can outgrow it alone, and it
    # fills the chip beside 10.33 GB of weights
    e = t["engine"]
    pool = e["num_kv_blocks"] * e["kv_block_size"] * 640 * 2 * 5
    assert 3.0e9 < pool < 5.0e9
    assert e["max_model_len"] % e["kv_block_size"] == 0


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "perfbench", "reference",
                            "deepseek_v2.py")).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src
    assert 'default_matmul_precision("highest")' in src


def test_kernel_names_do_not_fold_into_an_older_kernel():
    from perfbench.harness.trace_reduce import kernel_of, stable_name
    for name in ("mla_latent_attn", "moe_grouped_swiglu", "moe_grouped_down"):
        assert kernel_of(name) is None
        assert stable_name(name + ".3", "bf16[256,128,512]").startswith(name)


# -- the two counts --------------------------------------------------------------
def test_latent_decode_cost_on_the_issues_own_step():
    # 256 rows at a mean context of 1,500, one layer's call: the issue
    # reckons 2.2 GB and 0.53 TFLOP over 5 layers
    flops, moved = latent_arith.latent_decode_cost(256 * 1500, 128, 576, 512)
    assert moved == 256 * 1500 * 576 * 2
    assert flops == 256 * 1500 * 2 * 128 * (576 + 512)
    assert 5 * moved == pytest.approx(2.2e9, rel=0.01)
    assert 5 * flops == pytest.approx(0.535e12, rel=0.01)
    assert flops / moved == pytest.approx(242, abs=1)     # the chip's ridge


def test_grouped_expert_cost_counts_only_touched_experts():
    # one decode step of the cell: 4 expert layers, every held expert
    # touched, 256 x 6 / 4 pairs a layer
    pairs, touched = 4 * 384, 4 * 40
    flops, moved = moe_arith.grouped_expert_cost(pairs, touched, 5120, 1536)
    assert flops == 6 * pairs * 5120 * 1536
    assert moved == (touched * 3 * 5120 * 1536 + pairs * 2 * 5120) * 2
    assert moved == pytest.approx(7.58e9, rel=0.01)       # 160 experts' bytes
    # a thin batch: 3 pairs that touch 2 experts read 2 experts, not 40
    _, thin = moe_arith.grouped_expert_cost(3, 2, 5120, 1536)
    assert thin == (2 * 3 * 5120 * 1536 + 3 * 2 * 5120) * 2
    assert moe_arith.grouped_expert_cost(0, 0, 5120, 1536) == (0.0, 0.0)


# -- the readers -----------------------------------------------------------------
SHAPE = {"layers": 5, "hidden": 5120, "heads": 128, "kv_lora_rank": 512,
         "qk_rope_head_dim": 64, "expert_layers": 4, "experts_held": 40,
         "expert_width": 1536}


LOAD = "serve.moe_load_max_over_mean"


def _run(ops):
    steps = [(1.0, 1.1, "decode", 256, 256 * 1000),
             (1.1, 1.2, "prefill", 1, 0),
             (1.2, 1.3, "decode", 256, 256 * 1200),
             (3.5, 3.6, "decode", 256, 256 * 9000)]       # after the stretch
    records = [("traced", 101.0, 103.0)]
    return {"job": "serve", "peaks": PEAKS["TPU v5 lite"], "shape": SHAPE,
            "trace": {"ops": ops},
            "spans": types.SimpleNamespace(records=records),
            "serve": {"steps": steps, "traced": (1.0, 3.0),
                      "stats_at_open": {"preemptions": 1, "model_counts": {
                          "counters": {}, "gauges": {LOAD: {
                              "last": 2.0, "sum": 10.0, "steps": 5}}}},
                      "stats_at_end": {
                          "preemptions": 1,
                          "kv_blocks": {"high_water": 900, "total": 2200},
                          "model_counts": {
                              "counters": {"serve.moe_pairs": 70000,
                                           "serve.moe_experts_touched": 2400,
                                           "serve.moe_pairs_dropped": 0},
                              "gauges": {LOAD: {"last": 1.7, "sum": 28.0,
                                                "steps": 15}}}}}}


def test_mla_decode_roofline_from_prefix_named_ops():
    run = _run({"mla_latent_attn_bf16_256_128_512": [10, 4e-3],
                "paged_decode_f32_1": [10, 9.0]})
    # 10 calls at the mean live context of the two traced decode steps
    flops, moved = latent_arith.latent_decode_cost(
        10 * 256 * 1100, 128, 576, 512)
    want = 100 * max(moved / 819e9, flops / 197e12) / 4e-3
    assert expert_reads.mla_decode_roofline(run) == pytest.approx(want)
    assert want < 100
    assert expert_reads.mla_decode_roofline(_run({})) is None
    gpt = _run({"mla_latent_attn_x": [1, 1.0]})
    gpt["shape"] = {"layers": 24, "heads": 16, "head_dim": 128}
    assert expert_reads.mla_decode_roofline(gpt) is None


def test_moe_expert_roofline_from_the_steps_own_counts(monkeypatch):
    spans = [("engine.step", 101.1, 101.2, {"moe_pairs": 1536,
                                            "moe_experts_touched": 160}),
             ("engine.step", 101.2, 101.3, {"moe_pairs": 1500,
                                            "moe_experts_touched": 158}),
             ("engine.step/dispatch", 101.2, 101.25, {}),
             ("engine.step", 100.9, 101.05, {"moe_pairs": 999,
                                             "moe_experts_touched": 99}),   # cut
             ("engine.step", 101.4, 101.5, {})]           # no expert layer
    fake = types.SimpleNamespace(
        spans_between=lambda a, b: [s for s in spans
                                    if s[2] > a and s[1] < b])
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    run = _run({"moe_grouped_swiglu_bf16_2784_1536": [8, 14e-3],
                "moe_grouped_down_bf16_2784_5120": [8, 7e-3]})
    assert expert_reads.traced_expert_counts(run) == (3036, 318)
    flops, moved = moe_arith.grouped_expert_cost(3036, 318, 5120, 1536)
    assert expert_reads.moe_expert_roofline(run) == pytest.approx(
        100 * moved / 819e9 / 21e-3)
    assert expert_reads.moe_expert_roofline(run) < 100
    # a program whose spans carry no counts (the parent): nothing to read
    monkeypatch.setattr(phase_reads, "_source", lambda: types.SimpleNamespace(
        spans_between=lambda a, b: [("engine.step", 101.1, 101.2, {})]))
    assert expert_reads.moe_expert_roofline(run) is None
    monkeypatch.setattr(phase_reads, "_source",
                        lambda: types.SimpleNamespace())
    assert expert_reads.moe_expert_roofline(run) is None


def test_moe_load_max_over_mean_over_the_windows_decode_steps(capsys):
    run = _run({})
    assert expert_reads.moe_load_max_over_mean(run) == pytest.approx(1.8)
    assert capsys.readouterr().out == ""          # a reader says nothing
    expert_reads.say_engine_counts(run)
    counts = json.loads(capsys.readouterr().out.split(
        "engine_counts: ", 1)[1])
    assert counts["serve.moe_pairs_dropped"] == 0
    assert counts["serve.moe_pairs"] == 70000
    assert counts["preemptions_since_open"] == 0
    assert counts["kv_blocks_high_water"] == 900
    run["serve"]["stats_at_open"] = {"steps": 3}          # an older program
    assert expert_reads.moe_load_max_over_mean(run) is None
    assert expert_reads.moe_load_max_over_mean(
        {"job": "train", "trace": None, "peaks": None}) is None


# -- the rehearsal ---------------------------------------------------------------
def test_rehearsal_manifest_is_valid():
    m = Manifest(os.path.join(DATA, "manifest-deepseek.json"), [DATA])
    assert validate(m) == []
    assert m.load_config("tiny-deepseek")["entry"] == "deepseek_v2:build"


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_through_the_same_job(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0", BENCH_RUN="ignored",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "tiny.reason", "--seed",
         "3000000019", "--seconds", "1", "--manifest",
         os.path.join(DATA, "manifest-deepseek.json"), "--root", DATA,
         "--trace", str(trace), "--rehearsal"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True, [x for x in lines if "check" in x]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(v["value"] is None for v in last["metrics"].values())
    routing = json.loads(next(x for x in lines if x.startswith(
        "routing_check: ")).split(": ", 1)[1])
    assert routing["ok"] and routing["tokens"] == 2 * (9 + 20 + 33 + 50
                                                       + 4 * 3)
    assert routing["max_margin"] <= routing["tie_eps"]
    if trace:
        # counters and spans are read on the CPU too; a device trace is not
        assert {"moe_load_max_over_mean.reason",
                "decode_batch_occupancy.reason",
                "prefill_time_share.reason", "step_plan_share.reason",
                "step_inputs_share.reason", "step_logits_copy_share.reason",
                "step_accept_share.reason"} <= set(last["metrics"])
        counts = json.loads(next(x for x in lines if x.startswith(
            "engine_counts: ")).split(": ", 1)[1])
        assert counts["serve.moe_pairs_dropped"] == 0
        assert counts["serve.moe_pairs"] > 0
        assert "mla_decode_roofline.reason" not in last["metrics"]
    else:
        assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
