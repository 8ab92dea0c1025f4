"""The metric arithmetic, on plain numbers."""
import numpy as np
import pytest

from perfbench.harness import arith, reads
from perfbench.harness.peaks import PEAKS, UnknownDevice, peaks_for


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
@pytest.mark.parametrize("n", [2, 7, 100])
def test_percentile_is_numpy_linear(q, n):
    v = list(np.random.RandomState(n).rand(n) * 100)
    assert arith.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_percentile_edges():
    assert arith.percentile([], 90) is None
    assert arith.percentile([3.0], 90) == 3.0
    assert arith.percentile([1.0, 2.0], 90) == pytest.approx(1.9)


def test_gaps_counted_only_inside_the_window():
    times = [0.5, 0.9, 1.1, 1.3, 1.6, 2.1, 2.4]
    assert arith.gaps_inside(times, 1.0, 2.0) == pytest.approx([0.2, 0.3])
    assert arith.gaps_inside(times, 0.0, 3.0) == pytest.approx(
        list(np.diff(times)))
    assert arith.gaps_inside([1.5], 1.0, 2.0) == []


def test_tpot_needs_eight_gaps_and_is_a_mean_per_request():
    long_req = [1.0 + 0.1 * i for i in range(10)]          # 9 gaps of 0.1
    short_req = [1.0 + 0.2 * i for i in range(8)]          # 7 gaps only
    uneven = [1.0, 1.1, 1.4, 1.5, 1.8, 1.9, 2.2, 2.3, 2.6]  # mean 0.2
    got = arith.tpot_per_request([long_req, short_req, uneven], 0.0, 10.0)
    assert got == pytest.approx([0.1, 0.2])
    # a request that straddles the window's edge counts its inside gaps only
    assert arith.tpot_per_request([long_req], 1.35, 10.0) == []


def test_rate_over_chips():
    assert arith.rate(16384 * 30, 30.0, 4) == pytest.approx(4096.0)
    assert arith.rate(100, 4.0) == 25.0
    with pytest.raises(ValueError):
        arith.rate(1, 0.0)


def test_train_flops_match_the_programs_count():
    from paddle_tpu.observability.mfu import flops_per_token
    assert arith.train_flops_per_token(125_000_000, 12, 768, 2048) == \
        flops_per_token(125_000_000, 12, 768, 2048)
    assert arith.train_flops_per_token(10, 1, 1, 1, causal=False) == 72.0


def test_flash_needs_counts_seven_matmuls():
    flops, moved = arith.flash_needs(1, 1, 1, batch=2, heads=3, seq=128,
                                     head_dim=64)
    matmul = 2 * 2 * 3 * 128 * 128 * 64 / 2
    assert flops == pytest.approx(7 * matmul)
    assert moved == pytest.approx(12 * 2 * 3 * 128 * 64 * 2)
    assert arith.flash_needs(1, 0, 0, 2, 3, 128, 64)[0] == \
        pytest.approx(2 * matmul)


def test_kv_bytes_per_token_gpt3_xl():
    assert arith.kv_bytes_per_token(24, 16, 128) == 2 * 24 * 2048 * 2


def test_roofline_share_says_which_bound_and_never_clamps():
    share, bound = arith.roofline_share(197e12, 0.0, 2.0, 197e12, 819e9)
    assert (share, bound) == (50.0, "compute")
    share, bound = arith.roofline_share(0.0, 819e9, 0.5, 197e12, 819e9)
    assert (share, bound) == (200.0, "memory")        # a fault must show
    assert arith.roofline_share(1, 1, 0.0, 1, 1)[0] is None


def test_peaks_known_and_unknown():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert PEAKS["TPU v5 lite"]["hbm_bytes_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")


def _serve_run():
    reqs = [
        {"due": 1.0, "submitted": 1.02, "prompt_len": 100, "out_len": 12,
         "first": 1.5, "times": [1.5 + 0.1 * i for i in range(12)],
         "failed": False},
        {"due": 0.2, "submitted": 0.2, "prompt_len": 50, "out_len": 3,
         "first": 0.5, "times": [0.5, 0.9, 1.2], "failed": False},
        {"due": 2.9, "submitted": 2.95, "prompt_len": 70, "out_len": 5,
         "first": 3.2, "times": [3.2], "failed": False},
    ]
    steps = [(0.4, 0.5, "prefill", 1, 0), (0.8, 0.9, "decode", 1, 51),
             (1.4, 1.5, "prefill", 1, 0), (1.5, 1.6, "decode", 2, 152),
             (1.6, 1.7, "decode", 4, 300), (2.95, 3.2, "prefill", 1, 0)]
    return {"job": "serve", "window": {"t0": 1.0, "t1": 3.0, "seconds": 2.0},
            "serve": {"requests": reqs, "steps": steps, "max_seqs": 8,
                      "traced": None}, "trace": None, "peaks": None}


def test_served_tokens_counts_prompts_prefilled_and_tokens_emitted_inside():
    run = _serve_run()
    # request 0: prompt 100 + 12 tokens; request 1: 1 token at 1.2; request
    # 2: first token after the window
    assert reads.served_tokens(run) == 100 + 12 + 1


def test_ttft_from_due_time_and_generator_lateness(capsys):
    run = _serve_run()
    assert reads.ttft_ms(run, 50.0, "x") == pytest.approx(400.0)  # 500, 300
    assert reads.gen_late_ms(run, 100.0, "x") == pytest.approx(50.0)
    assert "samples_behind: x n=2" in capsys.readouterr().out


def test_occupancy_and_prefill_share_inside_the_window():
    run = _serve_run()
    assert reads.decode_occupancy(run) == pytest.approx(100 * 3 / 8)
    # prefill 1.4-1.5 inside, 2.95-3.2 clipped at 3.0
    assert reads.prefill_time_share(run) == pytest.approx(100 * 0.15 / 2)
    assert reads.tpot_ms(run, 90.0, "t") == pytest.approx(100.0)


def test_readers_return_nothing_for_the_other_job():
    run = {"job": "train", "trace": None, "peaks": None}
    for fn in (reads.served_tokens, reads.decode_occupancy,
               reads.prefill_time_share, reads.paged_decode_roofline,
               reads.idle_share):
        assert fn(run) is None


def test_paged_decode_roofline_from_live_lengths():
    run = _serve_run()
    run["serve"]["traced"] = (1.0, 3.0)
    run["peaks"] = PEAKS["TPU v5 lite"]
    run["shape"] = {"layers": 24, "heads": 16, "head_dim": 128}
    # two decode steps traced (48 kernel calls), mean live 226 tokens
    run["trace"] = {"kernels": {"paged_decode": [48, 1e-3]}}
    moved = 2 * 226 * arith.kv_bytes_per_token(24, 16, 128)
    assert reads.paged_decode_roofline(run) == pytest.approx(
        100 * moved / 819e9 / 1e-3)
