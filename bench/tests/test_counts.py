"""Count functions and the peaks table against hand counts."""
import pytest

from bench import counts, peaks

QWEN3_4B = {  # huggingface.co/Qwen/Qwen3-4B, config.json
    "hidden_size": 2560, "num_attention_heads": 32, "num_key_value_heads": 8,
    "head_dim": 128, "intermediate_size": 9728, "vocab_size": 151936,
    "num_hidden_layers": 36,
}


def test_peaks_table_has_v5e_with_source():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in peaks.SOURCE


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_gram_factors_cost_by_hand():
    # 66 rows, d = 2^20, 120 sketch tables of width 256
    c = counts.gram_factors_cost(66, 1 << 20, 120)
    assert c["flops"] == 2 * 66 * 66 * 2**20 + 2 * 120 * 66 * 2**20
    assert c["bytes"] == 4 * (66 * 2**20 + 66 * 66 + 120 * 66 * 256)


def test_gram_factors_roofline_is_memory_bound_at_d_1m():
    c = counts.gram_factors_cost(66, 1 << 20, 120)
    t, bound = counts.roofline_seconds(c, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(c["bytes"] / 819e9)
    assert t == pytest.approx(3.48e-4, rel=1e-2)  # 285 MB at 819 GB/s


def test_qwen3_4b_params_by_hand():
    p = counts.dense_params(QWEN3_4B)
    assert p["layer"]["q"] == pytest.approx(10.49e6, rel=1e-3)
    assert p["layer"]["k"] == p["layer"]["v"] == pytest.approx(2.62e6,
                                                               rel=1e-3)
    assert p["layer"]["o"] == pytest.approx(10.49e6, rel=1e-3)
    assert p["layer"]["mlp"] == pytest.approx(74.71e6, rel=1e-4)
    assert p["per_layer"] == pytest.approx(100.93e6, rel=1e-4)
    assert p["embedding"] == pytest.approx(389.0e6, rel=1e-3)


def test_qwen3_4b_flops_per_token_by_hand():
    cfg = dict(QWEN3_4B, num_hidden_layers=6)
    matmul = 6 * 100_925_440 + 388_956_160
    attn = 12 * 6 * 32 * 128 * 1024
    assert counts.dense_flops_per_token(cfg, 1024) == 6 * matmul + attn
