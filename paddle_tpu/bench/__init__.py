"""The scenario matrix (ISSUE 13): a CPU smoke that prints rows.

Every workload family of the matrix (GPT pretrain fused/unfused, MoE,
long-context sequence-parallel, ResNet/MNIST vision, serve-mode decode)
runs under one measurement discipline and emits ONE schema-versioned
row to stdout.  Nothing here keeps a history or judges a number: the
benchmark is ``BENCHMARK.json`` + ``perfbench/``, its record
``PERF_LEDGER.jsonl`` (``PERF.md``).

Layout::

    schema.py     row schema: fingerprint, phase breakdown, validate
    harness.py    phase-timed step loop, compile window, bytes-on-wire
    scenarios.py  the registered workload matrix
    runner.py     scenario → row assembly

Entry point::

    python -m paddle_tpu.bench (--all | --scenario NAME) [--smoke]
"""
from __future__ import annotations

from . import harness, schema
from .schema import (KNOWN_SCHEMA_VERSIONS, METRICS, PHASES,
                     SCHEMA_VERSION, fingerprint_key, metric_value,
                     new_row, validate_row)

__all__ = [
    "schema", "harness",
    "SCHEMA_VERSION", "KNOWN_SCHEMA_VERSIONS", "PHASES", "METRICS",
    "new_row", "validate_row", "fingerprint_key", "metric_value",
    "run_scenarios",
]


def run_scenarios(*args, **kwargs):
    """Lazy forward to :func:`runner.run_scenarios` (the runner imports
    jax-heavy scenario code; keep ``import paddle_tpu.bench`` light)."""
    from .runner import run_scenarios as _run
    return _run(*args, **kwargs)
