#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it owns the chip.  It builds the cell's system from
``--seed``, warms up the cell's own shapes, checks the outputs against the
plain reference, measures for ``--seconds`` and prints, as the last line of
its standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and with ``--trace 1`` ``breakdown``).  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Earlier lines carry the parts of the
set-up time, the checks and the sample counts.

It fails, with no result line, when jax finds no TPU or fewer chips than the
cell asks for.  ``--rehearsal`` is the CPU rehearsal of the same code for
the tests: it says so and prints no value under any metric's name.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()          # process start, as near as Python gets

import argparse                    # noqa: E402
import contextlib                  # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench.harness import trace_reduce                      # noqa: E402
from perfbench.harness.compile_watch import CompileWatch        # noqa: E402
from perfbench.harness.manifest import Manifest                 # noqa: E402
from perfbench.harness.peaks import peaks_for                   # noqa: E402
from perfbench.harness.spans import Spans                       # noqa: E402

OUT_DIR = os.path.join(REPO, ".perfbench_out")


def say(msg: str) -> None:
    print(msg, flush=True)


class Ctx:
    """What a job is handed: the cell's data, the clock marks of the
    window, the spans, the compile counts and the profiler."""

    def __init__(self, manifest, cell, config, traffic, seed, seconds,
                 trace, rehearsal):
        self.manifest, self.cell = manifest, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.rehearsal = bool(trace), bool(rehearsal)
        self.trace_s = float(traffic.get("trace_s", 3.0))
        self.spans = Spans()
        self.watch = CompileWatch().install()
        self.setup_parts = {}
        self.setup_s = None
        self.compile = {}
        self.trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        self.traced = False
        self.system = None

    def open_window(self) -> None:
        self.setup_s = time.perf_counter() - _T0
        self._at_open = self.watch.snapshot()

    def close_window(self) -> None:
        now = self.watch.snapshot()
        self.compile = {
            "setup_requests": self._at_open["requests"],
            "setup_hits": self._at_open["hits"],
            "setup_compiles": self._at_open["compiles"],
            "setup_compile_seconds": self._at_open["compile_seconds"],
            "window_compiles": now["compiles"] - self._at_open["compiles"],
        }

    @contextlib.contextmanager
    def tracing(self):
        """The profiler, on for the stretch inside the ``with``: device
        operations and the harness's own spans, no Python tracer."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with self.spans.span("traced"):
                yield
        finally:
            jax.profiler.stop_trace()
            self.traced = True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="another manifest than BENCHMARK.json (tests)")
    ap.add_argument("--root", action="append", default=[],
                    help="a directory searched for configs/, traffic/, "
                         "kinds/, ... before perfbench/ (tests)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal: prints no value under a metric's "
                         "name and proves nothing about the chip")
    ap.add_argument("--dump-trace", default=None,
                    help="directory for a description of the trace and the "
                         "reduced lists (to cut a test fixture from)")
    args = ap.parse_args(argv)

    manifest = Manifest(args.manifest, args.root)
    cell = manifest.cell(args.workload)
    config = manifest.load_config(cell["config"])
    traffic = manifest.load_traffic(cell["traffic"])
    seconds = (args.seconds if args.seconds is not None
               else manifest.data["run_seconds"])

    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearsal and dev.platform != "tpu":
        raise SystemExit(f"perfbench: no TPU (jax found {dev.platform!r}); "
                         f"a CPU run measures nothing")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"perfbench: cell {cell['name']} asks for "
                         f"{cell['chips']} chips, jax found {len(devices)}")
    peaks = None if args.rehearsal else peaks_for(dev.device_kind)
    t_imports = time.perf_counter() - _T0

    ctx = Ctx(manifest, cell, config, traffic, args.seed, seconds,
              args.trace, args.rehearsal)
    ctx.setup_parts["imports_s"] = t_imports
    ctx.system = manifest.load_entry(config["entry"])(config, args.seed)
    job = manifest.load_module("jobs", traffic["job"])
    run = job.run(ctx)

    run.update(cell=cell["name"], chips=cell["chips"], peaks=peaks,
               setup_s=ctx.setup_s, compile=ctx.compile, spans=ctx.spans,
               shape=ctx.system.shape, traffic=traffic, config=config,
               trace=None)
    if ctx.traced:
        xplane = trace_reduce.newest_xplane(ctx.trace_dir)
        raw = trace_reduce.load_xplane(xplane)
        run["trace"] = trace_reduce.reduce(raw)
        if args.dump_trace:
            os.makedirs(args.dump_trace, exist_ok=True)
            stem = os.path.join(args.dump_trace, cell["name"])
            with open(stem + ".describe.txt", "w") as f:
                f.write(trace_reduce.describe_xplane(xplane))
            trace_reduce.save_raw(raw, stem + ".raw.json.gz")
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    # end-to-end values are always computed (per-layer readers use them);
    # which section is printed depends on --trace
    values = {}
    for section in ("end_to_end", "per_layer"):
        run[section] = values[section] = {}
        for m in manifest.metrics_for(section, cell["name"]):
            v = manifest.load_module(
                "end_to_end" if section == "end_to_end" else "layer_metrics",
                m["name"]).read(run)
            if v is not None:
                values[section][m["name"]] = {"value": float(v),
                                              "unit": m["unit"]}

    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    runtime_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                       default=0)
    # this runtime's peak does not see a program's scratch (PERF.md 7): the
    # compiler's plan of the largest program stands in where it is larger
    memory_peak = max(int(runtime_peak), int(run.get("planned_bytes") or 0))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if args.trace and run["trace"]:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]

    say("setup_parts: " + json.dumps(
        {k: round(v, 3) for k, v in ctx.setup_parts.items()}))
    say("compile: " + json.dumps(ctx.compile))
    say("checks: " + json.dumps(run.get("checks", {})))
    say("samples: " + json.dumps(run.get("samples", {})))
    say("memory: " + json.dumps({"runtime_peak_bytes": int(runtime_peak),
                                 "planned_bytes": run.get("planned_bytes")}))
    shown = "per_layer" if args.trace else "end_to_end"
    other = "end_to_end" if args.trace else "per_layer"
    say(f"{other}: " + json.dumps(
        None if args.rehearsal else values[other]))
    metrics = values[shown]
    if args.rehearsal:
        say("perfbench: REHEARSAL on " + dev.platform + " - every value "
            "below is 'not measured'")
        metrics = {k: {"value": None, "unit": v["unit"]}
                   for k, v in metrics.items()}
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]), "failed": int(run["failed"]),
            "metrics": metrics, "device": device}
    if args.trace and run["trace"] and not args.rehearsal:
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
