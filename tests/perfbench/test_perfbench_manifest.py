"""BENCHMARK.json against the benchmark's contract, the files it names, and
the plain reference against ``models/gpt.py``."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from perfbench.harness.manifest import (NAME_RE, REPO, UNIT_RE, Manifest,
                                        validate)

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_manifest_meets_the_contracts_static_rules(manifest):
    assert validate(manifest) == []


def test_test_manifest_is_valid_too():
    assert validate(Manifest(os.path.join(DATA, "manifest.json"),
                             [DATA])) == []


def test_names_and_units_within_the_allowed_characters(manifest):
    d = manifest.data
    names = ([c["name"] for c in d["configs"]]
             + [k for c in d["configs"] for k in c["reduced"]]
             + [x for w in d["workloads"]
                for x in (w["name"], w["config"], w["traffic"])]
             + [m["name"] for m in d["end_to_end"] + d["per_layer"]])
    assert all(NAME_RE.match(n) for n in names)
    assert all(UNIT_RE.match(m["unit"])
               for m in d["end_to_end"] + d["per_layer"])
    assert len(set(m["name"] for m in d["end_to_end"] + d["per_layer"])) \
        == len(d["end_to_end"]) + len(d["per_layer"])


def test_issue_names_are_there(manifest):
    d = manifest.data
    assert {m["name"] for m in d["end_to_end"]} == {
        "train_tok_s_chip", "tpot_ms_p90", "serve_tok_s", "setup_s"}
    assert not any("ttft" in m["name"] for m in d["end_to_end"])
    assert {c["name"] for c in d["configs"]} == {
        "gpt3-125m", "gpt3-xl", "gpt3-xl-l16"}
    assert [w["name"] for w in d["workloads"] if w["chips"] == 4] in (
        [], ["gpt3-xl-l16.train-hybrid4"])


def test_at_most_one_cell_in_four_asks_for_four_chips(manifest):
    cells = manifest.data["workloads"]
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


def _cells(m):
    return [w["name"] for w in m.data["workloads"]]


@pytest.mark.parametrize("cell", _cells(Manifest()))
def test_every_file_a_cell_names_exists_and_loads(manifest, cell):
    w = manifest.cell(cell)
    config = manifest.load_config(w["config"])
    traffic = manifest.load_traffic(w["traffic"])
    assert callable(manifest.load_entry(config["entry"]))
    assert callable(manifest.load_module("jobs", traffic["job"]).run)
    assert callable(manifest.load_module("kinds", traffic["kind"]).make)
    for key in ("source", "assumed", "reduced", "deployment"):
        assert key in config, key
    assert traffic["why"]
    e2e = {m["name"] for m in manifest.metrics_for("end_to_end", cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in manifest.metrics_for("end_to_end", cell):
        assert callable(manifest.load_module("end_to_end", m["name"]).read)
    layer = manifest.metrics_for("per_layer", cell)
    assert layer
    for m in layer:
        assert callable(manifest.load_module("layer_metrics",
                                             m["name"]).read)
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("name,layers,hidden,heads,head_dim,ffn", [
    ("gpt3-125m", 12, 768, 12, 64, 3072),
    ("gpt3-xl", 24, 2048, 16, 128, 8192),
    ("gpt3-xl-l16", 16, 2048, 16, 128, 8192)])
def test_published_widths(manifest, name, layers, hidden, heads, head_dim,
                          ffn):
    c = manifest.load_config(name)
    assert (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["head_dim"], c["intermediate_size"],
            c["max_position_embeddings"], c["vocab_size"]) == (
        layers, hidden, heads, head_dim, ffn, 2048, 50304)
    assert sorted(c["reduced"]) == manifest.config_entry(name)["reduced"]
    if name == "gpt3-xl-l16":
        assert list(c["reduced"]) == ["num_hidden_layers"]
        assert "A6" in c["reduced"]["num_hidden_layers"]


def test_serving_traffic_parameters_of_the_issue(manifest):
    chat = manifest.load_traffic("chat-poisson")
    doc = manifest.load_traffic("doc-backlog")
    assert chat["prompt"] == {"dist": "lognormal", "median": 256,
                              "sigma": 0.8, "min": 32, "max": 1024}
    assert chat["output"] == {"dist": "lognormal", "median": 96,
                              "sigma": 0.6, "min": 16, "max": 256}
    assert doc["prompt"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.5, "min": 512, "max": 1792}
    assert doc["output"] == {"dist": "lognormal", "median": 64,
                             "sigma": 0.4, "min": 32, "max": 128}
    assert doc["keep_waiting"] >= 32
    assert chat["engine"] == doc["engine"]
    assert chat["engine"]["max_seqs"] == 128
    assert chat["engine"]["num_kv_blocks"] % 64 == 0
    assert isinstance(chat["rate_rps"], (int, float))


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "perfbench", "reference", "gpt.py")).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src


def test_validate_catches_faults(tmp_path):
    d = json.load(open(os.path.join(DATA, "manifest.json")))
    d["workloads"][0]["chips"] = 2
    d["end_to_end"][0]["bound"] = 0.5
    d["per_layer"][0]["why"] = "not allowed"
    d["per_layer"][2]["moves"] = "serve_tok_s"     # tiny.train lacks it
    shutil.copytree(os.path.join(DATA, "configs"), tmp_path / "configs")
    p = tmp_path / "manifest.json"
    p.write_text(json.dumps(d))
    faults = "\n".join(validate(Manifest(str(p), [DATA])))
    for needle in ("chips 2", "bound 0.5", "keys", "does not report"):
        assert needle in faults, (needle, faults)


# -- a new cell arrives as files and manifest entries only -----------------
NEW_KIND = '''
import numpy as np
class Ones:
    def __init__(self, p, vocab):
        self.batch, self.seq, self.vocab = p["batch"], p["seq"], vocab
        self.tokens_per_step = self.batch * self.seq
        self.n = 0
    def next_batch(self):
        self.n += 1
        return np.full((self.batch, self.seq), self.n % self.vocab, np.int32)
def make(params, seed, vocab):
    return Ones(params, vocab)
'''
NEW_METRIC = '''
def read(run):
    return float(run["train"]["steps"])
'''


def test_a_throw_away_cell_needs_no_edit_of_the_harness(tmp_path):
    for sub in ("configs", "traffic", "kinds", "layer_metrics"):
        (tmp_path / sub).mkdir()
    cfg = json.load(open(os.path.join(DATA, "configs", "tiny-gpt.json")))
    cfg["num_hidden_layers"] = 1
    (tmp_path / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    tr = json.load(open(os.path.join(DATA, "traffic", "tiny-train.json")))
    tr.update(kind="constant_ids", batch=1, seq=64)
    (tmp_path / "traffic" / "throwaway-mix.json").write_text(json.dumps(tr))
    (tmp_path / "kinds" / "constant_ids.py").write_text(NEW_KIND)
    (tmp_path / "layer_metrics" / "steps_run.py").write_text(NEW_METRIC)
    m = json.load(open(os.path.join(DATA, "manifest.json")))
    m["configs"].append({"name": "throwaway", "source": "none",
                         "file": "configs/throwaway.json", "reduced": [],
                         "why": "test"})
    m["workloads"].append({"name": "throwaway.cell", "config": "throwaway",
                           "traffic": "throwaway-mix", "chips": 1,
                           "why": "test"})
    m["end_to_end"][0]["workloads"].append("throwaway.cell")
    m["per_layer"].append({"name": "steps_run", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "train step",
                           "moves": "train_tok_s_chip",
                           "workloads": ["throwaway.cell"]})
    for name in ("tiny-gpt.json", "tiny-gpt-hybrid.json"):
        shutil.copy(os.path.join(DATA, "configs", name),
                    tmp_path / "configs" / name)
    (tmp_path / "manifest.json").write_text(json.dumps(m))
    assert validate(Manifest(str(tmp_path / "manifest.json"),
                             [str(tmp_path), DATA])) == []
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", "throwaway.cell", "--seed", "5", "--seconds", "0.5",
         "--trace", "1", "--rehearsal",
         "--manifest", str(tmp_path / "manifest.json"),
         "--root", str(tmp_path), "--root", DATA],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert "steps_run" in last["metrics"]
    assert last["metrics"]["steps_run"] == {"value": None, "unit": "count"}


# -- the plain reference agrees with models/gpt.py --------------------------
@pytest.fixture(scope="module")
def tiny_pair():
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import GPTForCausalLM
    from perfbench.builders import gpt as builder
    cfg = json.load(open(os.path.join(DATA, "configs", "tiny-gpt.json")))
    cfg["dtype"] = "float32"
    system = builder.build(cfg, 3)
    system.cfg = system._gpt_config(fused_lm_loss=False)
    pt.seed(3)
    model = GPTForCausalLM(system.cfg)
    model.eval()
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg["vocab_size"], (2, 48)), jnp.int32)
    return system, model, ids


def test_reference_forward_logits_agree_in_float32(tiny_pair):
    import jax.numpy as jnp
    system, model, ids = tiny_pair
    params = model.state_dict()
    want = np.asarray(model.apply(params, ids))
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    got = np.asarray(system.reference_logits_fn()(
        system.reference_params(params), ids, pos))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_reference_training_loss_agrees_in_float32(tiny_pair):
    system, model, ids = tiny_pair
    params = model.state_dict()
    loss, _ = model.apply(params, ids, labels=ids)
    ref = system.reference_loss_fn()(system.reference_params(params), ids)
    assert float(ref) == pytest.approx(float(loss), abs=2e-5)
