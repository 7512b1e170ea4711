"""`lib/costs_sdar.py` on hand-worked cases at the published widths."""

import json
import os

import pytest

from benchmarks.lib import costs_sdar

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


def test_a_tokens_row(cfg):
    # 4 KV heads x 128 x (K + V) x 2 B
    assert costs_sdar.kv_row_bytes(cfg) == 2048
    assert costs_sdar.block_length(cfg) == 4


def test_a_ticks_attention_reads_each_lane_once_for_its_four_queries(cfg):
    # 64 lanes at a cursor of 1,400: each block's four queries share ONE
    # read of 1,404 tokens x 2,048 B a layer, six layers
    attended = 64 * (1400 + 4)
    b = costs_sdar.block_decode_bytes(attended, cfg)
    assert b == attended * 2048 * 6 == 1_104_150_528
    # 1.35 ms at 819 GB/s: the issue's "64 lanes' K/V at ~1.4k tokens (1.4)"
    assert abs(b / PEAKS["hbm_bytes_per_s"] - 1.348e-3) < 1e-6


@pytest.mark.parametrize("position,keys", [
    (0, 4), (3, 4), (4, 8), (2047, 2048), (2048, 2052)])
def test_keys_a_query_reads(cfg, position, keys):
    assert costs_sdar.visible_keys(position, cfg) == keys


def test_a_windows_operations(cfg):
    # a whole first window: 512 blocks, the queries of block b read
    # 4 (b + 1) keys each: 4 x 4 x (1 + ... + 512)
    pairs = costs_sdar.window_visible_pairs(0, 2048, cfg)
    assert pairs == 16 * 512 * 513 // 2 == 2_101_248
    # against causal attention's 2048 x 2049 / 2 = 2,098,176: the
    # diagonal moved to the block's end adds 1.5 keys a query
    assert pairs - 2048 * 2049 // 2 == 2048 * 3 // 2
    # 4 x 32 x 128 FLOP a pair a layer: 0.207 TFLOP, 1.05 ms at the peak
    flops = costs_sdar.block_prefill_flops(pairs, cfg)
    assert flops == 4 * 32 * 128 * pairs * 6
    assert abs(flops / PEAKS["bf16_flops_per_s"] - 1.0485e-3) < 1e-6
    # a second window's queries read the first window whole; a partial
    # window counts its real queries only
    assert costs_sdar.window_visible_pairs(2048, 4, cfg) == 4 * 2052
    assert costs_sdar.window_visible_pairs(0, 8, cfg) == 4 * 4 + 4 * 8


def test_a_ticks_experts(cfg):
    # 3 x 2048 x 768 x 2 B = 9.4 MB an expert; a tick of 256 rows
    # touches all 128 in each of six layers: 7.25 GB, 8.85 ms
    # (the accepted `moe_decode_roofline_share.longchat` reads the cell
    # through `costs_qwen3next`, off this configuration's own keys)
    from benchmarks.lib import costs_qwen3next
    assert costs_qwen3next.expert_bytes(cfg) == 9_437_184
    b = costs_qwen3next.moe_decode_bytes(6 * 128, cfg)
    assert b == 768 * 9_437_184 == 7_247_757_312
    assert abs(b / PEAKS["hbm_bytes_per_s"] - 8.8495e-3) < 1e-6
