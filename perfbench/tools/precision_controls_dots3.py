#!/usr/bin/env python3
"""``precision_controls.py`` for a dots3 cell: the readings that set the
limits of the cell's comparison, for ``perfbench/reference/dots3.py`` with
that tool's own casts (imported: int8 and e4m3 under a scale per output
channel or per token).

    python3 perfbench/tools/precision_controls_dots3.py --workload <cell> --seeds <n>,<n>,...

One process; it owns the chip.  The engine is built once, from the first
seed.  Each control swaps the reference module's casts (``_f32`` for
weights, ``cached`` for the latent rows and index keys a cache would hold),
runs the cell's own check (``jobs/serve.py``'s ``_reference_check``) with
fresh seeded prompts under limits opened wide, and prints one ``control
<name> <seed>: {...}`` line: the largest logit difference, the reference's
report of the handed choices, and ``fails_by``, the committed limits
(configuration file and ``logits_tolerance``) that the reading passes.
Under the committed limits the check fails exactly where a reading lies
above one: until then it computes what it computes under wide limits.
Every further seed draws the weights anew in the engine that is there (the
old ones are let go first: two copies do not fit beside the pool) and runs
the controls again (``--only bf16_as_served``: the check as served alone),
so that a limit rests on the readings of many seeds.  By hand, once a configuration: not
part of a run of the benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench.harness.manifest import Manifest          # noqa: E402
from perfbench.tools.precision_controls import _e4m3, _int8   # noqa: E402

WIDE = {"routing_tie_eps": 1e9, "routing_differ_share": 1.0,
        "index_tie_eps": 1e9, "index_differ_share": 1.0}


def controls():
    """name -> (weights cast or None, cache cast or None)."""
    import jax.numpy as jnp
    f32 = lambda w: w.astype(jnp.float32)                 # noqa: E731
    per_channel = lambda q: (lambda w: q(w, tuple(range(w.ndim - 1)))  # noqa
                             if w.ndim >= 2 else f32(w))

    def per_token(q, only=None):
        # a scale a token: x is (tokens, values)
        return lambda x, what: x if only not in (None, what) else q(x, -1)
    return {
        "bf16_as_served": (None, None),
        "int8_weights_per_channel": (per_channel(_int8), None),
        "e4m3_weights_per_channel": (per_channel(_e4m3), None),
        "int8_latent_rows_per_token": (None, per_token(_int8, "latent")),
        "int8_index_keys_per_token": (None, per_token(_int8, "index")),
        "int8_pages_per_token": (None, per_token(_int8)),
        "e4m3_pages_per_token": (None, per_token(_e4m3)),
    }


def _reading(out, report, limits):
    """The reading, and the committed limits it lies above (``fails_by``;
    ``choices`` where a handed choice is not a legal one at all)."""
    r, x = report["routing"], report["index"]
    above = {
        "logits_tolerance": not (out["logits_finite"]
                                 and out["check_complete"])
        or out["logits_max_abs_diff"] > limits["logits_tolerance"],
        "choices": not report["ok"],
        "routing_tie_eps": r["max_margin"] > limits["routing_tie_eps"],
        "routing_differ_share": r["tokens_differ"]
        > limits["routing_differ_share"] * r["tokens"],
        "index_tie_eps": x["max_margin"] > limits["index_tie_eps"],
        "index_differ_share": x["entries_differ"]
        > limits["index_differ_share"] * x["entries"]}
    return {"logits_max_abs_diff": out["logits_max_abs_diff"],
            "routing_max_margin": r["max_margin"],
            "routing_differ": [r["tokens_differ"], r["tokens"]],
            "index_max_margin": x["max_margin"],
            "index_differ": [x["entries_differ"], x["entries"]],
            "fails_by": [k for k, v in above.items() if v]}


def _redraw(system, engine, seed: int) -> None:
    """New weights from ``seed`` in the engine that is there."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.dots3 import Dots3ForCausalLM
    for a in jax.tree.leaves(engine._params):
        a.delete()
    system.seed = int(seed)
    pt.seed(system.seed % (2 ** 31 - 1))
    engine._params = Dots3ForCausalLM(system._model_config()).state_dict()
    jax.block_until_ready(engine._params)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="0",
                    help="comma-separated; every control runs on each")
    ap.add_argument("--only", default="")
    ap.add_argument("--manifest", default=None,
                    help="another manifest than BENCHMARK.json (tests)")
    ap.add_argument("--root", action="append", default=[],
                    help="a directory searched before perfbench/ (tests)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    manifest = Manifest(args.manifest, args.root)
    cell = manifest.cell(args.workload)
    committed = manifest.load_config(cell["config"])
    config = dict(committed, **WIDE)
    traffic = manifest.load_traffic(cell["traffic"])
    limits = {k: float(committed[k]) for k in WIDE}
    limits["logits_tolerance"] = float(traffic["check"]["logits_tolerance"])
    traffic["check"] = dict(traffic["check"], logits_tolerance=1e9)

    import jax
    import numpy as np
    serve = manifest.load_module("jobs", traffic["job"])
    system = manifest.load_entry(config["entry"])(config, seeds[0])
    engine = system.build_for_serving(traffic["engine"])
    jax.block_until_ready(engine._params)
    from perfbench.reference import dots3 as ref
    keep = ref._f32, ref.cached
    vocab = system.shape["vocab"]
    for i, seed in enumerate(seeds):
        if i:
            _redraw(system, engine, seed)
        rng = np.random.default_rng(np.random.SeedSequence(
            [int(seed), 0x6374]))
        for name, (weights, cache) in controls().items():
            if args.only and name not in args.only.split(","):
                continue
            ref._f32, ref.cached = weights or keep[0], cache or keep[1]
            try:
                out = serve._reference_check(engine, system, traffic, vocab,
                                             rng)
            finally:
                ref._f32, ref.cached = keep
            line = json.dumps(_reading(out, system.check_report, limits))
            print(f"control {name} {seed}: {line}", flush=True)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
