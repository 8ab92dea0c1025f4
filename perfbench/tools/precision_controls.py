#!/usr/bin/env python3
"""Readings for the limits of a latent-attention cell's comparison: the
cell's own check (``jobs/serve.py``'s ``_reference_check``: prefill, then
paged decode, against the reference) repeated with the REFERENCE computed in
lower precisions, so that each limit can be set between what bf16 as served
reads and what the nearest precision below it reads (PERF.md section 4).

    python3 perfbench/tools/precision_controls.py --workload <cell> --seed <n>

One process; it owns the chip.  The engine is built once; each control swaps
the reference module's casts (``_f32`` for weights, ``cached`` for what a
cache would hold), runs the check with fresh seeded prompts under limits
opened wide, and prints one ``control <name>: {...}`` line with the largest
logit difference and the reference's report of the handed choices.  By hand,
once a configuration: not part of a run of the benchmark.
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

WIDE = {"routing_tie_eps": 1e9, "routing_differ_share": 1.0,
        "index_tie_eps": 1e9, "index_differ_share": 1.0}


def _scaled(x, axis, top, cast):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top + 1e-30
    return cast(x / scale) * scale


def _int8(x, axis):
    import jax.numpy as jnp
    return _scaled(x, axis, 127.0, lambda y: jnp.round(y))


def _e4m3(x, axis):
    """4 exponent and 3 mantissa bits under a scale that puts the largest
    value at 240.  ``lax.reduce_precision``, not a cast to a float8 type
    and back: XLA removes such a round trip (the first readings of this
    tool's e4m3 pages read like bf16 for that reason; PERF.md section 4)."""
    from jax import lax
    return _scaled(x, axis, 240.0, lambda y: lax.reduce_precision(y, 4, 3))


def controls():
    """name -> (weights cast or None, cache cast or None)."""
    import jax.numpy as jnp
    f32 = lambda w: w.astype(jnp.float32)                 # noqa: E731
    per_channel = lambda q: (lambda w: q(w, tuple(range(w.ndim - 1)))  # noqa
                             if w.ndim >= 2 else f32(w))
    per_token = lambda q, which: (lambda x, what: q(x, -1)  # noqa: E731
                                  if what in which else x)
    both = ("latent", "index")
    return {
        "bf16_as_served": (None, None),
        "bf16_as_served_again": (None, None),
        "int8_weights_per_channel": (per_channel(_int8), None),
        "e4m3_weights_per_channel": (per_channel(_e4m3), None),
        "int8_pages_per_token": (None, per_token(_int8, both)),
        "e4m3_pages_per_token": (None, per_token(_e4m3, both)),
        "e4m3_index_keys_only": (None, per_token(_e4m3, ("index",))),
        "int8_index_keys_only": (None, per_token(_int8, ("index",))),
        "int8_latent_rows_only": (None, per_token(_int8, ("latent",))),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    manifest = Manifest()
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
    from perfbench.reference import glm5 as ref
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
                               report["routing"]["tokens"]],
            "index_max_margin": report["index"]["max_margin"],
            "index_differ": [report["index"]["entries_differ"],
                             report["index"]["entries"]]}), flush=True)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
