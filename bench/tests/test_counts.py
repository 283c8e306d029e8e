"""Operation and byte counts against shapes worked by hand."""

import pytest

import counts

GPT = {"d_model": 512, "num_heads": 8, "num_kv_heads": 8, "head_dim": 64,
       "d_ff": 2048, "spike_T": 4, "num_layers": 8, "vocab_size": 64,
       "weight_bits": 5}
VIT = {"dim": 768, "num_heads": 12, "T": 4, "depth": 8, "image_size": 32,
       "patch_size": 4, "mlp_ratio": 4, "in_channels": 3, "num_classes": 10}


def test_layer_weights():
    # Q, K, V, O: 4 x 512 x 512; FFN: 2 x 512 x 2048
    assert counts.lm_layer_weights(GPT) == 4 * 512 * 512 + 2 * 512 * 2048


def test_layer_ops_count_live_positions_only():
    w = 4 * 512 * 512 + 2 * 512 * 2048
    # crossbars: 2 ops x T=4 x weights; SSA: 2 ops x T x 8 heads x live x 64 x 2
    assert counts.lm_layer_ops(GPT, 1) == 2 * 4 * w + 2 * 4 * 8 * 1 * 64 * 2
    extra = counts.lm_layer_ops(GPT, 101) - counts.lm_layer_ops(GPT, 1)
    assert extra == 2 * 4 * 8 * 100 * 64 * 2


def test_position_ops_add_the_head():
    assert counts.lm_position_ops(GPT, 10) == (
        8 * counts.lm_layer_ops(GPT, 10) + 2 * 512 * 64)


def test_step_bytes_spikes_are_bits_weights_five_bits():
    w = 4 * 512 * 512 + 2 * 512 * 2048
    per_pos_bits = 2 * 4 * 8 * 64  # K and V, T=4 steps, 8 heads x 64
    # two slots: one sees 10 positions (9 cached), one sees 1 (none cached)
    got = counts.lm_step_layer_bytes(GPT, [10, 1])
    assert got == pytest.approx((w * 5 + 9 * per_pos_bits + 2 * per_pos_bits) / 8)


def test_least_time_names_its_bound():
    peaks = {"int8_ops": 393e12, "hbm_bytes_s": 819e9}
    t, bound = counts.least_time_s(393e12, 1.0, peaks)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = counts.least_time_s(1.0, 819e9, peaks)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_vit_image_ops():
    n, d, f = 64, 768, 3072
    crossbar = 2 * 4 * n * (4 * d * d + 2 * d * f)
    ssa = 2 * 4 * 12 * n * n * 64 * 2
    head = 2 * n * 48 * d + 2 * d * 10
    assert counts.vit_image_ops(VIT) == 8 * (crossbar + ssa) + head
    # about 29 G operations per image, as worked out from the paper's widths
    assert 28e9 < counts.vit_image_ops(VIT) < 31e9
