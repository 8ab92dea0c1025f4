#!/usr/bin/env python3
"""``precision_controls.py`` for a MiMo-V2 cell: that tool names GLM-5's
reference module and its two kinds of cached row, and no file of the
benchmark that is there is edited, so this one brings the same readings for
``perfbench/reference/mimo_v2.py`` with that tool's own casts (imported:
int8 and e4m3 under a scale per output channel or per token).

    python3 perfbench/tools/precision_controls_mimo_v2.py --workload <cell> --seed <n>

One process; it owns the chip.  The engine is built once; each control swaps
the reference module's casts (``_f32`` for weights, ``cached`` for the key
and value rows a cache would hold), runs the cell's own check with fresh
seeded prompts under limits opened wide, and prints one ``control <name>:
{...}`` line with the largest logit difference and the reference's report of
the handed choices.  By hand, once a configuration: not part of a run of the
benchmark.
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

WIDE = {"routing_tie_eps": 1e9, "routing_differ_share": 1.0}


def controls():
    """name -> (weights cast or None, cache cast or None)."""
    import jax.numpy as jnp
    f32 = lambda w: w.astype(jnp.float32)                 # noqa: E731
    per_channel = lambda q: (lambda w: q(w, tuple(range(w.ndim - 1)))  # noqa
                             if w.ndim >= 2 else f32(w))
    # a scale a token a key/value head: x is (tokens, heads, dim)
    per_token = lambda q: (lambda x, what: q(x, -1))      # noqa: E731
    return {
        "bf16_as_served": (None, None),
        "int8_weights_per_channel": (per_channel(_int8), None),
        "e4m3_weights_per_channel": (per_channel(_e4m3), None),
        "int8_pages_per_token": (None, per_token(_int8)),
        "e4m3_pages_per_token": (None, per_token(_e4m3)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="")
    ap.add_argument("--manifest", default=None,
                    help="another manifest than BENCHMARK.json (tests)")
    ap.add_argument("--root", action="append", default=[],
                    help="a directory searched before perfbench/ (tests)")
    args = ap.parse_args(argv)
    manifest = Manifest(args.manifest, args.root)
    cell = manifest.cell(args.workload)
    config = dict(manifest.load_config(cell["config"]), **WIDE)
    traffic = manifest.load_traffic(cell["traffic"])
    traffic["check"] = dict(traffic["check"], logits_tolerance=1e9)

    import jax
    import numpy as np
    serve = manifest.load_module("jobs", traffic["job"])
    system = manifest.load_entry(config["entry"])(config, args.seed)
    engine = system.build_for_serving(traffic["engine"])
    jax.block_until_ready(engine._params)
    from perfbench.reference import mimo_v2 as ref
    keep = ref._f32, ref.cached
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(args.seed), 0x6374]))
    for name, (weights, cache) in controls().items():
        if args.only and name not in args.only.split(","):
            continue
        ref._f32, ref.cached = weights or keep[0], cache or keep[1]
        try:
            out = serve._reference_check(engine, system, traffic,
                                         system.shape["vocab"], rng)
        finally:
            ref._f32, ref.cached = keep
        report = system.check_report
        print(f"control {name}: " + json.dumps({
            "logits_max_abs_diff": out["logits_max_abs_diff"],
            "routing_max_margin": report["routing"]["max_margin"],
            "routing_differ": [report["routing"]["tokens_differ"],
                               report["routing"]["tokens"]]}), flush=True)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
