"""The Nemotron-3-Super configuration and its cell: the manifest's
new entries held BY NAME (never by position or count, so the next append
breaks nothing), the files they name, the cut's parameter count, the state
kernel's and the latent experts' arithmetic against hand-worked numbers,
the new readers on a made-up run, and the cell's CPU rehearsal."""
import json
import os
import subprocess
import sys
import types

import pytest

from perfbench.harness import phase_reads, ssm_arith, ssm_reads
from perfbench.harness.manifest import REPO, Manifest, validate
from perfbench.harness.peaks import PEAKS

DATA = os.path.join(os.path.dirname(__file__), "data")
RUN = os.path.join(REPO, "perfbench", "run.py")
CONFIG = "nemotron3-super-ep8-l11"
CELL = "nemotron3-super-ep8-l11.statectx-backlog"
SERVE = "serving loop (inference/engine.py, scheduler.py, kv_cache.py)"
NEW = {
    "ssm_decode_roofline.statectx": ("%", "higher", "device_trace",
                                     "kernels (inference/ssm_state.py)"),
    "moe_expert_roofline.statectx": (
        "%", "higher", "device_trace",
        "kernels (inference/latent_attention.py, ops/grouped_matmul.py)"),
    "prefill_device_share.statectx": ("%", "lower", "program_counter",
                                      SERVE),
    "device_idle_share.statectx": ("%", "lower", "device_trace",
                                   "device (TPU v5e)"),
    "host_slack_share.statectx": ("%", "higher", "program_counter", SERVE),
    "decode_batch_occupancy.statectx": ("%", "higher", "program_counter",
                                        SERVE),
    "gqa_full_decode_roofline.statectx": (
        "%", "higher", "device_trace", "kernels (inference/gqa_attention.py)"),
    "moe_load_max_over_mean.statectx": (
        "ratio", "lower", "program_counter",
        "expert layer (nn/dropless_moe.py, ops/grouped_matmul.py)"),
    "device_starved_share.statectx": ("%", "lower", "program_span", SERVE),
    "step_host_only_share.statectx": ("%", "lower", "program_span", SERVE),
    "step_ahead_share.statectx": ("%", "higher", "program_counter", SERVE),
}
# the accepted readers of MiMo-V2's cell that also read this one
TWINS = ("prefill_device_share", "device_idle_share", "host_slack_share",
         "decode_batch_occupancy", "gqa_full_decode_roofline",
         "moe_load_max_over_mean", "device_starved_share",
         "step_host_only_share", "step_ahead_share")
CUT = {"num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEMEM*E",
       "n_routed_experts": 64, "vocab_size": 16384,
       "num_nextn_predict_layers": 0}
WIDTHS = ("hidden_size", "moe_intermediate_size", "moe_latent_size",
          "moe_shared_expert_intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads", "mamba_num_heads",
          "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
          "num_experts_per_tok", "expand")


def _catalog_row():
    """The published config.json of NVIDIA-Nemotron-3-Super-120B-A12B-BF16."""
    path = os.path.join(DATA, "published", "nemotron3-super.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


# -- the manifest: by name ------------------------------------------------------
def test_the_manifest_is_valid_with_the_new_entries(manifest):
    assert validate(manifest) == []
    cells = manifest.data["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert four == ["gpt3-xl-l16.train-hybrid4"]
    runs = 2 + 14 * len(cells)
    assert runs * (manifest.data["run_seconds"] + 60) + 180 * len(cells) \
        + 1200 <= 43200


def test_the_configuration_and_cell_entries(manifest):
    c = manifest.config_entry(CONFIG)
    assert c["file"] == "perfbench/configs/nemotron3-super-ep8-l11.json"
    assert c["source"] == ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-"
                           "Super-120B-A12B-BF16/blob/main/config.json")
    assert sorted(c["reduced"]) == sorted(CUT) and len(c["why"]) <= 200
    for key in c["reduced"]:
        assert key not in WIDTHS
    w = manifest.cell(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "statectx-backlog", 1)
    assert "128 rows" in w["why"] and len(w["why"]) <= 200
    serve = next(m for m in manifest.data["end_to_end"]
                 if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.08
    assert {"gpt3-xl.doc-backlog", "mimo-v2-flash-ep16-l7.mixedctx-backlog",
            "dots3-note-ep16-l5.agentctx-backlog"} <= set(serve["workloads"])
    reported = {m["name"] for m in manifest.metrics_for("end_to_end", CELL)}
    assert reported == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_per_layer_entry_and_its_reader(manifest, name):
    by_name = {x["name"]: x for x in manifest.data["per_layer"]}
    unit, better, source, layer = NEW[name]
    assert by_name[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "serve_tok_s", "workloads": [CELL]}
    read = manifest.load_module("layer_metrics", name).read
    empty = {"job": "train", "trace": None, "peaks": None, "serve": None,
             "spans": types.SimpleNamespace(records=[]), "shape": {},
             "window": {"t0": 0.0, "t1": 1.0, "seconds": 1.0}}
    if name.startswith(("ssm_", "moe_", "gqa_")):
        assert read(empty) is None


def test_the_cell_reports_what_it_lists(manifest):
    reported = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert reported == set(NEW) | {"compile_cache_hit_share",
                                   "recompiles_in_window"}
    by_name = {x["name"]: x for x in manifest.data["per_layer"]}
    for stem in TWINS:
        twin = by_name[f"{stem}.mixedctx"]
        assert by_name[f"{stem}.statectx"] == dict(
            twin, name=f"{stem}.statectx", workloads=[CELL])
    for x in manifest.data["per_layer"]:
        if x["name"] not in NEW:
            assert CELL not in x.get("workloads", [])


# -- the configuration file ------------------------------------------------------
def test_published_widths_and_the_stated_cut(manifest):
    c = manifest.load_config(CONFIG)
    row = _catalog_row()
    if row is None:
        pytest.skip("no published config.json in the test data")
    for key, value in row["config"].items():
        assert c[key] == CUT.get(key, value), key
    assert c["published"] == {k: row["config"][k] for k in CUT}
    # one whole period of the published pattern: layers 27-37
    assert row["config"]["hybrid_override_pattern"][27:38] == CUT[
        "hybrid_override_pattern"]


def _count(c):
    """The parameters of the cut, from the configuration's keys."""
    h = c["hidden_size"]
    heads, p, g, n = (c["mamba_num_heads"], c["mamba_head_dim"],
                      c["n_groups"], c["ssm_state_size"])
    d_inner = heads * p
    conv = d_inner + 2 * g * n
    mamba = (h * (d_inner + conv + heads) + conv * c["conv_kernel"] + conv
             + 3 * heads + d_inner + d_inner * h + h)
    d = c["head_dim"]
    attn = (2 * h * c["num_attention_heads"] * d
            + 2 * h * c["num_key_value_heads"] * d + h)
    latent, width = c["moe_latent_size"], c["moe_intermediate_size"]
    routed = c["n_routed_experts"] * c["ep_degree"]
    expert = (c["n_routed_experts"] * 2 * latent * width
              + 2 * h * c["moe_shared_expert_intermediate_size"]
              + 2 * h * latent + h * routed + routed + h)
    pattern = c["hybrid_override_pattern"]
    return ({"M": mamba, "*": attn, "E": expert},
            sum({"M": mamba, "*": attn, "E": expert}[k] for k in pattern)
            + 2 * c["vocab_size"] * h + h)


def test_the_share_and_the_parameter_count(manifest):
    c = manifest.load_config(CONFIG)
    assert c["entry"] == "nemotron_h:build"
    assert (c["ep_degree"], c["ep_rank"]) == (8, 0)
    assert c["n_routed_experts"] * c["ep_degree"] == 512
    assert c["vocab_size"] * 8 == 131072
    assert c["deployment"].startswith("8 chips share each layer")
    parts, total = _count(c)
    assert parts == {"M": 109_640_064, "*": 35_655_680, "E": 406_852_096}
    assert total == c["parameters"] == 2_752_338_304
    for key in ("no_rotary", "full_width_router_and_shared_expert",
                "no_latent_norm", "float32_state", "prediction_layer"):
        assert len(c["assumed"][key]) > 40
    for key in CUT:
        assert len(c["reduced"][key]) > 20
    for stem in ("routing_tie_eps", "routing_differ_share"):
        assert 0 < c[stem] < 1.5 and len(c[stem + "_why"]) > 40
        assert "PLACEHOLDER" not in c[stem + "_why"]


def test_the_count_is_the_models():
    """The count's formula, at the tiny size, against the program's own
    parameters (ep 4: four held of 16)."""
    import paddle_tpu as pt
    from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                              nemotron_h_tiny)
    t = nemotron_h_tiny(ep_degree=4)
    pt.seed(0)
    params = NemotronHForCausalLM(t).state_dict()
    keys = {"hidden_size": t.hidden_size, "mamba_num_heads":
            t.mamba_num_heads, "mamba_head_dim": t.mamba_head_dim,
            "n_groups": t.n_groups, "ssm_state_size": t.ssm_state_size,
            "conv_kernel": t.conv_kernel, "head_dim": t.head_dim,
            "num_attention_heads": t.num_heads,
            "num_key_value_heads": t.num_kv_heads,
            "moe_latent_size": t.moe_latent_size,
            "moe_intermediate_size": t.moe_intermediate_size,
            "moe_shared_expert_intermediate_size":
            t.moe_shared_expert_intermediate_size,
            "n_routed_experts": t.n_routed_experts // 4, "ep_degree": 4,
            "hybrid_override_pattern": t.hybrid_override_pattern,
            "vocab_size": t.vocab_size}
    assert _count(keys)[1] == sum(v.size for v in params.values())


def test_traffic_parameters_of_the_issue(manifest):
    t = manifest.load_traffic("statectx-backlog")
    assert (t["job"], t["kind"]) == ("serve", "backlog")
    assert (t["keep_waiting"], t["documents"], t["stratify_block"]) == (
        32, 512, 16)
    assert t["prompt"]["median"] == 2048 and t["prompt"]["min"] == 256 \
        and t["prompt"]["max"] == 8192
    assert t["prompt"]["sigma"] in (0.7, 0.4)        # or the fall-back
    assert t["output"] == {"dist": "lognormal", "median": 1024,
                           "sigma": 0.4, "min": 512, "max": 2048}
    e = t["engine"]
    assert (e["max_seqs"], e["max_model_len"], e["kv_block_size"]) == (
        128, 10240, 128)
    assert set(e["num_kv_blocks"]) == {"full", "state"}
    assert e["num_kv_blocks"]["state"] == e["max_seqs"] + 8
    assert set(t["warm_buckets"]) >= {256, 512, 1024, 2048, 4096, 8192}
    lens = t["check"]["prompt_lens"]
    assert len(lens) == 4 and not any(n % 128 == 0 for n in lens)
    for why in ("why", "engine_why", "warm_buckets_why"):
        assert len(t[why]) > 40 and "PLACEHOLDER" not in t[why]
    assert "PLACEHOLDER" not in t["check"]["logits_tolerance_why"]


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "perfbench", "reference",
                            "nemotron_h.py")).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src
    assert 'default_matmul_precision("highest")' in src
    assert "lax.scan(step" in src              # the sequential recurrence


# -- the arithmetic, by hand --------------------------------------------------------
def test_ssm_decode_cost_by_hand():
    # 128 rows through 5 Mamba layers: 640 states of 128 x 64 x 128 float32
    flops, moved = ssm_arith.ssm_decode_cost(640, 128, 64, 128, 8)
    state = 128 * 64 * 128 * 4
    assert state == 4 * 2 ** 20
    assert moved == 640 * (2 * state + (2 * 8192 + 128 + 2 * 1024) * 4)
    assert moved / 1e9 == pytest.approx(5.41, abs=0.01)
    assert flops == 640 * 4 * 128 * 64 * 128
    # memory-bound on a v5e: 6.6 ms against 0.014 ms
    assert moved / 819e9 == pytest.approx(6.6e-3, rel=1e-2)
    assert flops / 197e12 < moved / 819e9 / 100


def test_latent_expert_cost_by_hand():
    # a decode step: 128 tokens x 22 choices over 512 experts, 64 held:
    # 352 pairs a layer, every held expert touched, 5 layers
    flops, moved = ssm_arith.latent_expert_cost(5 * 352, 5 * 64, 1024, 2688)
    assert moved == (320 * 2 * 1024 * 2688 + 1760 * 2 * 1024) * 2
    assert moved / 1e9 == pytest.approx(3.53, abs=0.01)
    assert flops == 4 * 1760 * 1024 * 2688
    assert moved / 819e9 == pytest.approx(4.3e-3, rel=1e-2)


# -- the readers, on a made-up run --------------------------------------------------------
def _run(monkeypatch, ops, spans):
    fake = types.SimpleNamespace(spans_between=lambda a, b: [
        ("engine.step", t0, t1, at) for t0, t1, at in spans])
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    return {"job": "serve", "peaks": PEAKS["TPU v5 lite"],
            "trace": {"ops": ops},
            "spans": types.SimpleNamespace(records=[("traced", 10.0, 13.0)]),
            "shape": {"mamba_heads": 128, "mamba_head_dim": 64,
                      "ssm_state": 128, "ssm_groups": 8,
                      "expert_io_width": 1024, "expert_width": 2688},
            "serve": {"stats_at_open": None, "stats_at_end": None}}


def test_the_rooflines_from_the_traced_steps(monkeypatch):
    steps = [(10.5, 10.6, {"ssm_rows": 640, "moe_pairs": 1760,
                           "moe_experts_touched": 320}),
             (9.9, 10.1, {"ssm_rows": 10 ** 9}),       # cut: left out
             (11.0, 11.2, {"ssm_rows": 0, "moe_pairs": 0,
                           "moe_experts_touched": 0})]     # a prefill
    run = _run(monkeypatch, {
        "ssm_state_decode_f32_128_128_64": [5, 5 * 2e-3],
        "moe_grouped_relu2_bf16": [5, 5e-3],
        "moe_grouped_down_bf16": [5, 2e-3]}, steps)
    _, moved = ssm_arith.ssm_decode_cost(640, 128, 64, 128, 8)
    assert ssm_reads.ssm_decode_roofline(run) == pytest.approx(
        100 * moved / 819e9 / 10e-3)
    _, moved = ssm_arith.latent_expert_cost(1760, 320, 1024, 2688)
    assert ssm_reads.latent_expert_roofline(run) == pytest.approx(
        100 * moved / 819e9 / 7e-3)
    # the parent: no kernel of that name, no attribute: nothing
    run = _run(monkeypatch, {"gqa_full_decode_x": [5, 1e-3]}, steps[1:2])
    assert ssm_reads.ssm_decode_roofline(run) is None
    assert ssm_reads.latent_expert_roofline(run) is None
    run["shape"] = {"heads": 16}
    assert ssm_reads.ssm_decode_roofline(run) is None


# -- the rehearsal ---------------------------------------------------------------------
def test_rehearsal_manifest_is_valid():
    m = Manifest(os.path.join(DATA, "manifest-nemotron.json"), [DATA])
    assert validate(m) == []
    assert m.load_config("tiny-nemotron")["entry"] == "nemotron_h:build"
    assert {x["name"] for x in m.data["per_layer"]} >= set(NEW)


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_through_the_same_job(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0", BENCH_RUN="ignored",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "tiny.statectx", "--seed",
         "3500000041", "--seconds", "1", "--manifest",
         os.path.join(DATA, "manifest-nemotron.json"), "--root", DATA,
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
    counts = json.loads(next(x for x in lines if x.startswith(
        "engine_counts: ")).split(": ", 1)[1])
    assert counts["preemptions_since_open"] == 0
    assert counts["serve.ssm_state_rows"] > counts["serve.ssm_slots_reset"] > 0
    pools = json.loads(next(x for x in lines if x.startswith(
        "kv_pools: ")).split(": ", 1)[1])
    assert set(pools) == {"full", "state"}
    assert pools["state"]["total"] == 12 and pools["state"]["high_water"] <= 12
    if trace:
        assert {"decode_batch_occupancy.statectx",
                "prefill_device_share.statectx"} <= set(last["metrics"])
        assert "ssm_decode_roofline.statectx" not in last["metrics"]
    else:
        assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
