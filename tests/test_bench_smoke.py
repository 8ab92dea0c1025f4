"""CPU smoke coverage for the bench.py entry points (the reference keeps its
op_tester harness compiling even without GPUs — same doctrine here): the
bench helpers that only run inside bench.main()'s on-TPU branch get
tiny-shape CPU executions so a regression surfaces in the suite, not as a
failed one-shot run on the chip.
Covered directly: _bench_resnet50, _bench_bert_base, _sweep_seqlen_ab,
_bench_slice_estimate (the 1.3B/6.7B slice methodology), _bench_config (the
headline path).  _bench_flash_ab / _sweep_block_sizes / _bench_1p3b_fullstep
are thin compositions of the same _build/_timed_steps/flash_attention pieces.

The real-config artifacts (benchmarks/*.json) must NOT be written by these
smoke shapes — that gating is asserted here too.
"""
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import bench  # noqa: E402


def _artifact_mtimes():
    d = REPO / "benchmarks"
    return {p.name: p.stat().st_mtime for p in d.glob("*.json")}


def test_bench_resnet_smoke_writes_no_artifact(monkeypatch):
    # the override makes _write_artifact willing to record from CPU, so
    # what this actually asserts is the CONFIG-level gate (smoke depth/hw
    # never produce an artifact), not the CPU-platform gate
    monkeypatch.setenv("BENCH_ALLOW_CPU_ARTIFACTS", "1")
    before = _artifact_mtimes()
    img_s = bench._bench_resnet50(B=2, hw=32, steps=2, warmup=1, depth=18)
    assert img_s > 0
    assert _artifact_mtimes() == before, (
        "smoke config must not overwrite the hardware resnet50.json")


def test_bench_bert_smoke_writes_no_artifact(monkeypatch):
    from paddle_tpu.models.bert import bert_tiny
    monkeypatch.setenv("BENCH_ALLOW_CPU_ARTIFACTS", "1")
    before = _artifact_mtimes()
    seq_s = bench._bench_bert_base(B=2, S=64, steps=2, warmup=1,
                                   cfg_factory=bert_tiny)
    assert seq_s > 0
    assert _artifact_mtimes() == before, (
        "smoke config must not overwrite the hardware bert_base.json")


def test_bench_seqlen_ab_smoke():
    before = _artifact_mtimes()
    results = bench._sweep_seqlen_ab(bh=2, d=8, seqlens=(128,), steps=1,
                                     artifact=False)
    assert results["128"]["flash"] is not None
    assert results["128"]["xla"] is not None
    assert _artifact_mtimes() == before


def test_bench_slice_estimate_smoke():
    """Drives the shared slice-differencing helper (the 1.3B/6.7B
    methodology) on a tiny config; no artifact recorded."""
    from paddle_tpu.models import gpt_tiny
    before = _artifact_mtimes()
    tok_s, mfu = bench._bench_slice_estimate(gpt_tiny, (1, 2), B=2, S=64,
                                             tag="smoke-slice")
    assert tok_s > 0 and mfu is None    # a CPU has no peak to divide by
    assert _artifact_mtimes() == before


def test_bench_fused_block_ab_smoke():
    """ISSUE 7: the fused-block A/B helper runs on tiny CPU shapes, the
    fused leg honors the compile contract, and no artifact is written."""
    from paddle_tpu.models import gpt_tiny
    before = _artifact_mtimes()
    rows = bench._bench_fused_block_ab(
        B=2, S=64, steps=2, warmup=1, artifact=False,
        cfg_factory=lambda **kw: gpt_tiny(max_position_embeddings=64, **kw))
    assert rows["fused_block"]["step_ms"] > 0
    assert rows["fused_block"]["compiles"] == 1
    assert rows["fused_block"]["retraces"] == 0
    assert rows["fused_block"]["storms"] == 0
    assert _artifact_mtimes() == before


def test_bench_fused_ce_ab_smoke():
    from paddle_tpu.models import gpt_tiny
    before = _artifact_mtimes()
    rows = bench._bench_fused_ce_ab(
        B=2, S=128, steps=2, warmup=1, artifact=False, op_memory=False,
        cfg_factory=lambda **kw: gpt_tiny(max_position_embeddings=128,
                                          hidden_dropout=0.0,
                                          attention_dropout=0.0, **kw))
    assert rows["fused_ce"]["step_ms"] > 0
    assert _artifact_mtimes() == before


def test_fused_ce_op_memory_smoke():
    """The op-level memory measurement must show the fused CE saving
    temp bytes once the chunked scan engages (small-shape rendering of
    the fused_ce_ab.json evidence)."""
    out = bench._fused_ce_op_memory(B=1, S=256, H=64, V=4096, chunk=128)
    assert out["temp_bytes_saved"] > 0, out


@pytest.mark.slow
def test_bench_gpt_smoke():
    """The headline path main() takes on CPU (gpt_tiny smoke)."""
    from paddle_tpu.models import gpt_tiny
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    tok_s, mfu = bench._bench_config(cfg, B=2, S=128, steps=2, warmup=1,
                                     tag="suite-smoke")
    assert tok_s > 0 and mfu is None
