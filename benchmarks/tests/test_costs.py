"""The operations and bytes functions against hand counts."""

import pytest

from benchmarks.lib import costs, device


def test_gpt2_training_flops_by_hand():
    cfg = {"n_embd": 4, "n_layer": 2, "vocab_size": 10, "n_inner": 16}
    # weights a token meets: per layer qkv 4*12 + proj 4*4 + mlp 2*4*16
    per_layer = 48 + 16 + 128
    weights = 2 * per_layer + 10 * 4
    # attention: QK^T and PV, 2 flops * E * (S/2 keys on average) each
    attention = 2 * (2 * 2 * 4 * 8 / 2)
    want = 6 * weights + 3 * attention
    assert costs.gpt2_train_flops_per_token(cfg, 8) == pytest.approx(want)


def test_wenzhong_one_chip_is_about_four_and_a_half_gflop_a_token():
    cfg = {"n_embd": 3072, "n_layer": 5, "vocab_size": 50304,
           "n_inner": 12288}
    got = costs.gpt2_train_flops_per_token(cfg, 1024)
    assert got == pytest.approx(6 * (5 * 12 * 3072 ** 2 + 50304 * 3072)
                                + 3 * 5 * 2 * 3072 * 1024)
    assert 4.3e9 < got < 4.7e9


def test_decode_attention_bytes_by_hand():
    # 3 cached tokens, 2 kv heads of 4, bf16, 5 layers: K and V
    assert costs.decode_attention_bytes(3, 2, 4, 2, 5) == 2 * 3 * 2 * 4 * 2 * 5


def test_an_unknown_device_has_no_peak():
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("cpu")
    with pytest.raises(KeyError):
        device.peaks("_source")
