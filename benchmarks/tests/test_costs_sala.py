"""`lib/costs_sala.py` on hand-worked cases at the published widths."""

import json
import os

import pytest

from benchmarks.lib import costs_sala

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "minicpm-sala.json")) as f:
        return json.load(f)


def test_layers_of_each_kind(cfg):
    assert costs_sala.layer_counts(cfg) == (4, 12)


def test_a_tick_moves_sixteen_lanes_of_state_in_and_out(cfg):
    # 12 layers x 32 heads x 128 x 128 x 4 B = 25,165,824 B a lane
    assert costs_sala.state_bytes_a_lane(cfg) == 25_165_824
    # in and out: 50.3 MB a lane, 805 MB for 16 lanes, 0.98 ms at 819 GB/s
    assert costs_sala.linear_decode_bytes(1, cfg) == 50_331_648
    moved = costs_sala.linear_decode_bytes(16, cfg)
    assert moved == 805_306_368
    assert abs(moved / PEAKS["hbm_bytes_per_s"] - 0.983e-3) < 1e-6


def test_a_cached_token_and_its_pooled_share(cfg):
    # 4 layers x 2 heads x 128 x (K + V) x 2 B; a 2,048 B key row per
    # 16 tokens
    assert costs_sala.cached_token_bytes(cfg) == 4096
    assert costs_sala.pooled_token_bytes(cfg) == 128


@pytest.mark.parametrize("context,attended", [
    (1, 1), (8192, 8192),               # dense up to dense_len
    (8193, 63 * 64 + 1),                # 63 full blocks and the own one
    (8256, 64 * 64), (24576, 4096), (20000, 63 * 64 + 32)])
def test_tokens_a_query_reads(cfg, context, attended):
    assert costs_sala.attended_tokens(context, cfg) == attended


def test_a_lane_reads_4096_chosen_rows_a_tick(cfg):
    # 4,096 tokens x 4,096 B = 16.8 MB of chosen rows a lane, and the
    # pooled keys of its 20,480 cached tokens, 2.6 MB
    b = costs_sala.sparse_decode_bytes(4096, 20480, cfg)
    assert b == 4096 * 4096 + 20480 * 128 == 19_398_656
    # dense would read 20,480 x 4,096 B = 83.9 MB
    assert costs_sala.sparse_decode_bytes(20480, 0, cfg) == 83_886_080


def test_sparse_prefill_operations(cfg):
    # a query that reads 4,096 tokens: 4 x 32 x 128 FLOP a token a layer
    assert costs_sala.sparse_prefill_flops(4096, cfg) == \
        4 * 32 * 128 * 4096 * 4
    # a window of 2,048 queries starting at 8,192: all past dense_len
    chosen = costs_sala.window_chosen_tokens(8192, 2048, cfg)
    assert chosen == sum(63 * 64 + (t % 64) + 1 for t in range(8192, 10240))
    # the first window is dense: 1 + 2 + ... + 2048
    assert costs_sala.window_chosen_tokens(0, 2048, cfg) == 2048 * 2049 // 2


def test_a_median_prompts_linear_bytes(cfg):
    # 14,189 tokens x 12 layers x (q, k, v, o) 4 x 4096 x 2 B = 5.58 GB:
    # 6.81 ms at 819 GB/s; the recurrence's 4 x 128 x 128 x 32 FLOP a
    # token a layer is 1.81 ms at 197 TFLOP/s, so bytes bound
    floor = costs_sala.linear_prefill_floor_s(14189, cfg, PEAKS)
    assert abs(floor - 14189 * 12 * 32768 / 819e9) < 1e-12
    assert abs(floor - 6.812e-3) < 1e-5
    ops = 14189 * 12 * 4 * 128 * 128 * 32 / 197e12
    assert ops < floor and abs(ops - 1.812e-3) < 1e-5
