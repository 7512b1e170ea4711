"""`lib/costs_kimi.py` on hand-worked cases at the published widths."""

import json
import os

import pytest

from benchmarks.lib import costs_kimi

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "kimi-linear-48b-a3b.json")) as f:
        return json.load(f)


def test_the_cut_has_one_latent_layer_four_kda_and_four_of_experts(cfg):
    assert costs_kimi.layer_counts(cfg) == (1, 4, 4)
    assert costs_kimi.kda_channels(cfg) == 4096
    # 32 heads x 128 x 128 x 4 B; 3 inputs x 12,288 channels x 2 B
    assert costs_kimi.delta_state_bytes(cfg) == 2_097_152
    assert costs_kimi.conv_state_bytes(cfg) == 73_728
    assert costs_kimi.latent_row_bytes(cfg) == 1152
    assert costs_kimi.cached_row_bytes(cfg) == 1280


def test_a_live_lanes_tick_moves_both_states_in_and_out(cfg):
    # 4 layers x 2 (in, out) x (2,097,152 + 73,728) B = 17.4 MB
    assert costs_kimi.kda_decode_bytes(1, cfg) == \
        4 * 2 * (2_097_152 + 73_728) == 17_367_040
    # 64 live lanes: 1.11 GB a tick, 1.36 ms at 819 GB/s
    assert costs_kimi.kda_decode_bytes(64, cfg) / PEAKS["hbm_bytes_per_s"] \
        == pytest.approx(1.357e-3, rel=1e-3)


def test_a_lane_at_20000_tokens_reads_its_rows_once(cfg):
    # 20,000 x 576 values x 2 B in the ONE latent layer = 23.0 MB
    assert costs_kimi.mla_decode_bytes(20000, cfg) == 20000 * 1152 \
        == 23_040_000
    # 64 lanes at a mean 15,000: 1.11 GB a tick, 1.35 ms
    assert costs_kimi.mla_decode_bytes(64 * 15000, cfg) / 819e9 == \
        pytest.approx(1.350e-3, rel=1e-3)


def test_the_delta_rules_window_is_bound_by_its_bytes(cfg):
    # a token a layer: 6 rows of 4,096 values x 2 B = 49,152 B, 60.0 ns;
    # 32 heads x 7 x 128 x 128 = 3,670,016 operations, 18.6 ns
    s, bound = costs_kimi.kda_prefill_floor_s(1, cfg, PEAKS)
    assert bound == "bytes"
    assert s == pytest.approx(4 * 49_152 / 819e9)
    assert 32 * 7 * 128 * 128 == 3_670_016
    assert 3_670_016 / 197e12 == pytest.approx(18.63e-9, rel=1e-3)
    # a whole 2,048-token window, four layers: 0.49 ms
    s, _ = costs_kimi.kda_prefill_floor_s(2048, cfg, PEAKS)
    assert s == pytest.approx(0.4916e-3, rel=1e-3)


def test_the_window_at_8192(cfg):
    # the 2,048 queries of the window at s = 8,192 read 8,193 .. 10,240
    assert costs_kimi.full_prefill_pairs(8192, 2048) == \
        2048 * 8192 + 2048 * 2049 // 2 == 18_875_392
    # 32 heads x (2 x 192 + 2 x 128) = 20,480 FLOP a pair, one layer
    assert costs_kimi.mla_prefill_flops(18_875_392, cfg) == \
        20_480 * 18_875_392 == 386_568_028_160
    assert 386_568_028_160 / PEAKS["bf16_flops_per_s"] == \
        pytest.approx(1.962e-3, rel=1e-3)
    # a last window of 100 real queries at s = 4,096
    assert costs_kimi.full_prefill_pairs(4096, 100) == 409_600 + 5050


def test_a_windows_experts_are_bound_by_their_tables(cfg):
    # 128 held tables of 3 x 2,304 x 1,024 x 2 B = 14.2 MB, four layers:
    # 7.25 GB a window, 8.85 ms; 2,048 tokens x 8 picks / 2 = 8,192 held
    # assignments x 6 x 2,304 x 1,024 FLOP x 4 layers = 0.46 TFLOP, 2.35 ms
    s, bound = costs_kimi.moe_prefill_floor_s(2048, 1, cfg, PEAKS)
    assert bound == "bytes"
    assert s == pytest.approx(4 * 128 * 14_155_776 / 819e9)
    assert s == pytest.approx(8.849e-3, rel=1e-3)
    ops = 4 * 8192 * 6 * 2304 * 1024 / 197e12
    assert ops == pytest.approx(2.355e-3, rel=1e-3) and ops < s
    # two windows holding 100 tokens between them read the tables twice
    s2, _ = costs_kimi.moe_prefill_floor_s(100, 2, cfg, PEAKS)
    assert s2 == pytest.approx(2 * s)


def test_what_the_small_cache_holds(cfg):
    # a lane at 15,000 tokens: 118 blocks x 128 x 1,280 B = 19.3 MB of
    # rows in ONE layer + 4 x 2,170,880 B = 8.7 MB of state, against
    # 96.7 MB of rows in all five layers: 29 %
    share = costs_kimi.cache_bytes_share(118, 1, 128, cfg)
    rows = 118 * 128 * 1280
    assert rows == 19_333_120
    assert share == pytest.approx((rows + 4 * 2_170_880) / (5 * rows))
    assert share == pytest.approx(0.2898, rel=1e-3)
    # at 36,864 tokens (288 blocks): 47.2 + 8.7 = 55.9 MB a lane
    assert 288 * 128 * 1280 + 4 * 2_170_880 == 55_869_440
    # a short lane's states outweigh the rows the other four layers
    # would hold: the share passes 100 only under 14 blocks, 1,700
    # tokens a lane, which no lane of the mix is (prompts start at 2,048)
    assert costs_kimi.cache_bytes_share(13, 1, 128, cfg) > 1.0 > \
        costs_kimi.cache_bytes_share(14, 1, 128, cfg)
