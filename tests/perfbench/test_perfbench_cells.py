"""Each cell's job end to end at a tiny width, through the same code path
(``perfbench/run.py`` in a process of its own), on the CPU: the last line's
keys, and that no number is printed under a metric's name from a CPU."""
import json
import os
import subprocess
import sys

import pytest

from perfbench.harness.manifest import REPO, Manifest

DATA = os.path.join(os.path.dirname(__file__), "data")
RUN = os.path.join(REPO, "perfbench", "run.py")


def run_cell(cell, *extra, devices=1, rehearsal=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               BENCH_RUN="ignored")
    cmd = [sys.executable, RUN, "--workload", cell, "--seed", "3000000019",
           "--seconds", "1", "--manifest",
           os.path.join(DATA, "manifest.json"), "--root", DATA, *extra]
    if rehearsal:
        cmd.append("--rehearsal")
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=600)


CASES = [("tiny.train", 0, 1), ("tiny.train", 1, 1), ("tiny.chat", 0, 1),
         ("tiny.chat", 1, 1), ("tiny.backlog", 0, 1), ("tiny.hybrid", 0, 4)]


@pytest.fixture(scope="module")
def outputs():
    return {}


@pytest.mark.parametrize("cell,trace,devices", CASES)
def test_cell_runs_and_prints_the_contracts_last_line(outputs, cell, trace,
                                                      devices):
    out = run_cell(cell, "--trace", str(trace), devices=devices)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    outputs[(cell, trace)] = (lines, last)
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True, [x for x in lines if "checks" in x]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        last["device"])
    assert last["device"]["platform"] == "cpu"
    manifest = Manifest(os.path.join(DATA, "manifest.json"), [DATA])
    section = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in manifest.metrics_for(section, cell)}
    assert set(last["metrics"]) <= allowed and last["metrics"]
    if not trace:
        assert set(last["metrics"]) == allowed
    # a CPU prints no number under any metric's name
    assert all(v["value"] is None for v in last["metrics"].values())
    assert any("REHEARSAL" in x for x in lines)
    assert any(x.startswith("setup_parts: ") for x in lines)
    assert any(x.startswith("checks: ") for x in lines)


def test_a_device_trace_metric_is_left_out_where_nothing_was_traced(outputs):
    if ("tiny.chat", 1) not in outputs:
        pytest.skip("the traced chat case did not run")
    _, last = outputs[("tiny.chat", 1)]
    # a CPU trace has no device plane: the reader returns nothing
    assert "paged_decode_roofline.chat" not in last["metrics"]
    assert "decode_batch_occupancy.chat" in last["metrics"]


def test_no_tpu_is_an_error_without_a_result_line():
    out = run_cell("tiny.train", "--trace", "0", rehearsal=False)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(x.startswith("{") for x in out.stdout.splitlines())


def test_too_few_chips_is_an_error():
    out = run_cell("tiny.hybrid", "--trace", "0", devices=2)
    assert out.returncode != 0 and "asks for 4 chips" in out.stderr


def test_fails_where_only_the_benchmarks_own_files_are(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0")
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "gpt3-125m.train",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearsal"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert not any(x.startswith("{") for x in out.stdout.splitlines())
