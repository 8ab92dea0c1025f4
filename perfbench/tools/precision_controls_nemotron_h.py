#!/usr/bin/env python3
"""Readings for the limits of a Nemotron-H cell's comparison: the cell's own
check (``jobs/serve.py``'s ``_reference_check``: prefill, then decode through
the state slots and the pages, against ``perfbench/reference/nemotron_h.py``)
repeated with the REFERENCE computed in lower precisions, so that each limit
can be set between what bf16 as served reads and what the nearest precision
below it reads.  Beside ``precision_controls.py``'s casts of the weights and
of the cached keys and values (imported), the control this model adds: the
recurrent state kept in bf16 from one step to the next (``kept_state``),
from the end of the prefill on, where the program keeps it in float32.

    python3 perfbench/tools/precision_controls_nemotron_h.py \
        --workload <cell> --seeds a,b,... [--served-seeds c,d,...]

One process; it owns the chip.  The engine is built once; for each further
seed the weights are drawn anew in place (the old ones are let go first).
Each control swaps the reference module's seams (``_f32``, ``cached``,
``kept_state``) and has the reference traced anew where the control is not
the last check's, runs the check with fresh seeded prompts under limits
opened wide, and prints one ``control <name> <seed>: {...}`` line with the
largest logit difference, the reference's report of the handed choices,
``state_step_error`` and ``fails_by``: the committed limits the reading lies
above.  ``--served-seeds``: further seeds with bf16 as served alone (the
limits want more of those than of the controls).  ``--new-tokens`` runs the
check over that many decode steps instead of the traffic file's; ``--dump
<dir>`` keeps each check's state readings (the builder's
``state_readings``).  By hand, once a configuration: not part of a run of
the benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench.harness.manifest import Manifest          # noqa: E402
from perfbench.tools.precision_controls import _e4m3, _int8   # noqa: E402

WIDE = {"routing_tie_eps": 1e9, "routing_differ_share": 1.0,
        "state_step_tolerance": 1e9}


def controls():
    """name -> (weights cast, cache cast, state cast), None: as served."""
    import jax.numpy as jnp
    from jax import lax
    f32 = lambda w: w.astype(jnp.float32)                 # noqa: E731
    per_channel = lambda q: (lambda w: q(w, tuple(range(w.ndim - 1)))  # noqa
                             if w.ndim >= 2 else f32(w))
    # a scale a token a key/value head: x is (tokens, heads, dim)
    per_token = lambda q: (lambda x, what: q(x, -1))      # noqa: E731
    return {
        "bf16_as_served": (None, None, None),
        "bf16_state": (None, None, lambda s: lax.reduce_precision(s, 8, 7)),
        "int8_weights_per_channel": (per_channel(_int8), None, None),
        "e4m3_weights_per_channel": (per_channel(_e4m3), None, None),
        "int8_pages_per_token": (None, per_token(_int8), None),
        "e4m3_pages_per_token": (None, per_token(_e4m3), None),
    }


def _reading(out, report, limits):
    r = report["routing"]
    above = {
        "logits_tolerance": not (out["logits_finite"]
                                 and out["check_complete"])
        or out["logits_max_abs_diff"] > limits["logits_tolerance"],
        "choices": not report["ok"],
        "routing_tie_eps": r["max_margin"] > limits["routing_tie_eps"],
        "routing_differ_share": r["tokens_differ"]
        > limits["routing_differ_share"] * r["tokens"],
        "state_step_tolerance": report["state"]["state_step_error"]
        > limits["state_step_tolerance"]}
    return {"logits_max_abs_diff": out["logits_max_abs_diff"],
            "routing_max_margin": r["max_margin"],
            "routing_differ": [r["tokens_differ"], r["tokens"]],
            "state_step_error": report["state"]["state_step_error"],
            "state_final_error": report["state"]["state_final_error"],
            "fails_by": [k for k, v in above.items() if v]}


def _redraw(system, engine, seed: int) -> None:
    """New weights from ``seed`` in the engine that is there."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM
    for a in jax.tree.leaves(engine._params):
        a.delete()
    system.seed = int(seed)
    pt.seed(system.seed % (2 ** 31 - 1))
    engine._params = NemotronHForCausalLM(
        system._model_config()).state_dict()
    jax.block_until_ready(engine._params)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="0",
                    help="comma-separated; every control runs on each")
    ap.add_argument("--only", default="")
    ap.add_argument("--served-seeds", default="",
                    help="comma-separated: further seeds on which bf16 as "
                         "served alone runs")
    ap.add_argument("--new-tokens", type=int, default=None)
    ap.add_argument("--dump", default="",
                    help="a directory: each check's state readings, a head "
                         "a column, as <control>_<seed>.npz")
    ap.add_argument("--manifest", default=None,
                    help="another manifest than BENCHMARK.json (tests)")
    ap.add_argument("--root", action="append", default=[],
                    help="a directory searched before perfbench/ (tests)")
    args = ap.parse_args(argv)
    only = [n for n in args.only.split(",") if n] or list(controls())
    seeds = ([(int(s), only) for s in args.seeds.split(",") if s]
             + [(int(s), ["bf16_as_served"])
                for s in args.served_seeds.split(",") if s])
    manifest = Manifest(args.manifest, args.root)
    cell = manifest.cell(args.workload)
    committed = manifest.load_config(cell["config"])
    config = dict(committed, **WIDE)
    traffic = manifest.load_traffic(cell["traffic"])
    limits = {k: float(committed[k]) for k in WIDE}
    limits["logits_tolerance"] = float(traffic["check"]["logits_tolerance"])
    traffic["check"] = dict(traffic["check"], logits_tolerance=1e9)
    if args.new_tokens:
        traffic["check"]["new_tokens"] = int(args.new_tokens)

    import jax
    import numpy as np
    serve = manifest.load_module("jobs", traffic["job"])
    system = manifest.load_entry(config["entry"])(config, seeds[0][0])
    engine = system.build_for_serving(traffic["engine"])
    jax.block_until_ready(engine._params)
    from perfbench.reference import nemotron_h as ref
    keep = ref._f32, ref.cached, ref.kept_state
    vocab = system.shape["vocab"]
    traced = None
    for i, (seed, names) in enumerate(seeds):
        if i:
            _redraw(system, engine, seed)
        rng = np.random.default_rng(np.random.SeedSequence(
            [int(seed), 0x6374]))
        for name, casts in controls().items():
            if name not in names:
                continue
            t0 = time.perf_counter()
            if name != traced:                 # trace it under these casts
                system.reference, traced = None, name
            ref._f32, ref.cached, ref.kept_state = (
                c or k for c, k in zip(casts, keep))
            try:
                out = serve._reference_check(engine, system, traffic, vocab,
                                             rng)
            finally:
                ref._f32, ref.cached, ref.kept_state = keep
            line = json.dumps(dict(_reading(out, system.check_report, limits),
                                   seconds=time.perf_counter() - t0))
            print(f"control {name} {seed}: {line}", flush=True)
            if args.dump:
                os.makedirs(args.dump, exist_ok=True)
                np.savez(os.path.join(args.dump, f"{name}_{seed}.npz"),
                         **system.state_readings)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
