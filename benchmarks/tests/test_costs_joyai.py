"""The two byte counts of `joyai_reason_saturated` against hand counts
(the numbers of ISSUE 26)."""

import pytest

from benchmarks.lib import costs_joyai


def test_one_expert_is_nine_point_four_megabytes():
    # three matrices of 2048 x 768 in bf16
    assert costs_joyai.moe_expert_bytes(2048, 768, 2) == 9_437_184


def test_moe_decode_bytes_at_full_occupancy():
    # 64 lanes x 8 picks touch 256 (1 - (1 - 8/256)^64) = 222 experts of
    # a layer; four expert layers: 888 experts read once, 8.4 GB
    touched = 256 * (1 - (1 - 8 / 256) ** 64)
    assert touched == pytest.approx(222.4, abs=0.1)
    got = costs_joyai.moe_decode_bytes(4 * 222, 2048, 768, 2)
    assert got == 4 * 222 * 9_437_184 == 8_380_219_392


def test_moe_decode_bytes_by_hand():
    # 3 experts touched, hidden 4, width 2, float32: 3 x (3 * 4 * 2 * 4)
    assert costs_joyai.moe_decode_bytes(3, 4, 2, 4) == 288


def test_mla_decode_attention_bytes_by_hand():
    # 3 cached tokens, a row of 4 + 2 values, bf16, 5 layers
    assert costs_joyai.mla_decode_attention_bytes(3, 4, 2, 2, 5) == \
        3 * 6 * 2 * 5


def test_a_cached_token_is_5760_bytes_over_the_five_layers():
    assert costs_joyai.mla_decode_attention_bytes(1, 512, 64, 2, 5) == 5760
    # 64 lanes x ~1.7 k tokens: about 0.6 GB a tick
    assert costs_joyai.mla_decode_attention_bytes(
        64 * 1700, 512, 64, 2, 5) == 626_688_000
