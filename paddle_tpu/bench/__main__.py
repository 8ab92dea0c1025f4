"""``python -m paddle_tpu.bench`` — run the scenario matrix.

Each selected scenario emits one validated row, printed to stdout as
JSONL (stdout carries only rows; diagnostics go to stderr).  No file is
written.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

from . import scenarios
from .runner import run_scenarios


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.bench",
        description="run the scenario matrix and print one row per "
                    "scenario")
    ap.add_argument("--all", action="store_true",
                    help="run every registered scenario")
    ap.add_argument("--scenario", action="append", default=[],
                    metavar="NAME", help="run one scenario (repeatable)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-sized smoke shapes (default)")
    ap.add_argument("--full", action="store_true",
                    help="the real BASELINE shapes (TPU-sized)")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name in scenarios.names():
            doc = (scenarios.get(name).__doc__ or "").strip()
            print(f"{name:<22} {doc.splitlines()[0] if doc else ''}")  # noqa: print
        return 0
    names = list(args.scenario) if args.scenario else None
    if not args.all and not names:
        ap.error("pick --all or at least one --scenario NAME "
                 "(see --list)")
    mode = "full" if args.full else "smoke"
    rows = run_scenarios(names, mode=mode)
    for row in rows:
        sys.stdout.write(json.dumps(row) + "\n")
    sys.stdout.flush()
    want = len(names) if names else len(scenarios.names())
    return 0 if len(rows) == want else 1


if __name__ == "__main__":
    raise SystemExit(main())
