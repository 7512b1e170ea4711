"""`lib/costs_trinity.py` on hand-worked cases at the published widths."""

import json
import os

import pytest

from benchmarks.lib import costs_trinity

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs",
                           "trinity-large-preview.json")) as f:
        return json.load(f)


def test_the_cut_has_four_window_layers_one_full_and_four_of_experts(cfg):
    assert costs_trinity.layers(cfg, costs_trinity.SLIDING) == 4
    assert costs_trinity.layers(cfg, costs_trinity.FULL) == 1
    assert costs_trinity.expert_layers(cfg) == 4
    # 8 KV heads x 128 x (K + V) x 2 B
    assert costs_trinity.kv_row_bytes(cfg) == 4096


@pytest.mark.parametrize("context,keys", [
    (1, 1), (4096, 4096), (4097, 4096), (33792, 4096)])
def test_keys_a_window_layers_query_reads(cfg, context, keys):
    assert costs_trinity.window_tokens(context, cfg) == keys


def test_a_tick_reads_the_window_at_any_context_past_it(cfg):
    # one lane at any context past 4,095: 4,096 x 4,096 B = 16.8 MB a
    # window layer, four of them; the full layer reads the lane
    one = costs_trinity.window_decode_bytes(4096, cfg)
    assert one == 4 * 4096 * 4096 == 4 * 16_777_216
    assert costs_trinity.full_decode_bytes(20000, cfg) == 20000 * 4096
    # 16 lanes past the window: 1.07 GB of window rows a tick, 1.3 ms
    assert 16 * one / PEAKS["hbm_bytes_per_s"] == pytest.approx(1.311e-3,
                                                                rel=1e-3)


def test_the_window_at_8192(cfg):
    # the 2,048 queries of the window at s = 8,192: every one reads
    # 4,096 keys in a window layer, 8,193 .. 10,240 in the full layer
    assert costs_trinity.window_prefill_pairs(8192, 2048, cfg) == \
        2048 * 4096 == 8_388_608
    assert costs_trinity.full_prefill_pairs(8192, 2048) == \
        2048 * 8192 + 2048 * 2049 // 2 == 18_875_392
    # the first window: a triangle in both
    assert costs_trinity.window_prefill_pairs(0, 2048, cfg) == \
        costs_trinity.full_prefill_pairs(0, 2048) == 2048 * 2049 // 2
    # the third: 4,097 .. 6,144 cached, all cut to 4,096 but none
    assert costs_trinity.window_prefill_pairs(4096, 2048, cfg) == \
        2048 * 4096
    # 4 x 48 x 128 FLOP a pair a layer
    assert costs_trinity.attn_flops(8_388_608, 4, cfg) == \
        4 * 48 * 128 * 8_388_608 * 4 == 824_633_720_832
    assert costs_trinity.attn_flops(18_875_392, 1, cfg) / \
        PEAKS["bf16_flops_per_s"] == pytest.approx(2.355e-3, rel=1e-3)


def test_a_windows_experts_are_bound_by_their_tables(cfg):
    # 32 held tables of 3 x 3,072 x 3,072 x 2 B = 56.6 MB, four layers:
    # 7.25 GB a window, 8.85 ms; 2,048 tokens x 4 picks / 8 = 1,024 held
    # assignments x 6 x 3,072^2 FLOP x 4 layers = 0.23 TFLOP, 1.2 ms
    s, bound = costs_trinity.moe_prefill_floor_s(2048, 1, cfg, PEAKS)
    assert bound == "bytes"
    assert s == pytest.approx(4 * 32 * 56_623_104 / 819e9)
    assert s == pytest.approx(8.85e-3, rel=1e-3)
    assert costs_trinity.held_share(cfg) == 0.125
    ops = 4 * 1024 * 6 * 3072 * 3072 / 197e12
    assert ops == pytest.approx(1.177e-3, rel=1e-3) and ops < s
    # at a deployment's eightfold load the products would bind as much
    s8, _ = costs_trinity.moe_prefill_floor_s(8 * 2048, 1, cfg, PEAKS)
    assert s8 == pytest.approx(max(s, 8 * ops))


def test_what_the_ring_saves(cfg):
    # a lane at 33,792 tokens: 264 lane-long blocks x 1 layer + 48 ring
    # blocks x 4 layers = 456 block-layers against 264 x 5 = 1,320
    assert costs_trinity.ring_bytes_share(264, 48, cfg) == \
        pytest.approx(456 / 1320)
    # 239 MB a lane against 692 MB (ISSUE 41)
    assert 456 * 524_288 == 239_075_328 and 1320 * 524_288 == 692_060_160
    # a lane shorter than the ring holds the same of both kinds
    assert costs_trinity.ring_bytes_share(10, 10, cfg) == 1.0
