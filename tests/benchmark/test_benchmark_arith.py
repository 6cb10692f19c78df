"""FLOP, byte, MFU and percentile arithmetic on hand-worked shapes."""
import pytest

from benchmark import flops, peaks, stats

GPT = {"hidden_size": 2048, "num_hidden_layers": 24,
       "num_attention_heads": 16, "head_dim": 128,
       "intermediate_size": 8192, "vocab_size": 50304,
       "hidden_act": "gelu_tanh"}
MISTRAL = {"hidden_size": 4096, "num_hidden_layers": 32,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 128, "intermediate_size": 14336,
           "vocab_size": 32000, "hidden_act": "silu"}
V5E = peaks.peaks_for("TPU v5 lite")


def test_matmul_params_gpt3_xl():
    per_layer = 4 * 2048 * 2048 + 2 * 2048 * 8192
    assert flops.matmul_params(GPT) == 24 * per_layer + 50304 * 2048
    assert flops.matmul_params(GPT) == 1310982144


def test_matmul_params_gqa_and_gated_mlp():
    attn = 4096 * 4096 * 2 + 2 * 4096 * 1024
    mlp = 3 * 4096 * 14336
    assert flops.matmul_params(MISTRAL) == 32 * (attn + mlp) + 32000 * 4096


def test_train_flops_per_token():
    attn = 3 * 24 * (2 * 1024 * 1024 * 2048) / 1024
    assert flops.train_flops_per_token(GPT, 1024) == \
        6 * 1310982144 + attn


def test_mfu_is_a_share_of_the_chips_peak():
    per_tok = flops.train_flops_per_token(GPT, 1024)
    got = flops.mfu(per_tok * 16000, 1, V5E["bf16_flops"])
    assert got == pytest.approx(per_tok * 16000 / 197e12)
    assert flops.mfu(197e12 * 4, 4, 197e12) == 1.0
    assert 0.6 < got < 0.7


def test_flash_step_work_and_which_bound_binds():
    f, b = flops.flash_step_work(GPT, 4, 1024)
    assert f == 3 * 24 * 4 * 2 * 1024 * 1024 * 2048
    assert b == 24 * 12 * (4 * 1024 * 2048 * 2)
    t, bound = flops.roofline_seconds(f, b, V5E)
    assert bound == "compute" and t == pytest.approx(f / 197e12)
    f8, b8 = flops.flash_step_work(GPT, 4, 128)
    assert flops.roofline_seconds(f8, b8, V5E)[1] == "memory"


def test_flash_bytes_count_kv_heads_once():
    _, b = flops.flash_step_work(MISTRAL, 1, 4096)
    q_like, kv_like = 4096 * 4096 * 2, 4096 * 1024 * 2
    assert b == 32 * 6 * (q_like + kv_like)


def test_serve_flops_and_paged_bytes():
    assert flops.serve_flops(GPT, 1000) == 2 * 1310982144 * 1000
    assert flops.paged_decode_bytes(GPT, 500) == 500 * 16 * 128 * 2 * 2 * 24
    assert flops.paged_decode_bytes(MISTRAL, 1) == 8 * 128 * 2 * 2 * 32


def test_peaks_table():
    assert V5E["bf16_flops"] == 197e12 and V5E["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (100, 5.0),
                                    (95, 4.8), (25, 2.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 95) is None


def test_spread_is_iqr_over_median():
    import statistics
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx(
        (q[2] - q[0]) / statistics.median(xs))


def test_a_stall_inside_the_window_lowers_the_rate():
    steady = [i * 0.1 for i in range(100)]            # 10 events a second
    stalled = [t for t in steady if not 3.0 <= t < 6.0]
    assert stats.rate_over_window(steady, 0.0, 10.0) == pytest.approx(10.0)
    assert stats.rate_over_window(stalled, 0.0, 10.0) == pytest.approx(7.0)
    # events outside the window do not count
    assert stats.rate_over_window(steady + [11.0, -1.0], 0.0, 10.0) == \
        pytest.approx(10.0)


def test_a_stall_moves_the_tail():
    gaps = [0.03] * 99
    assert stats.percentile(gaps, 95) == pytest.approx(0.03)
    assert stats.percentile(gaps + [3.0] * 10, 95) == pytest.approx(3.0)
