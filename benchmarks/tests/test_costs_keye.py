"""`lib/costs_keye.py` on hand-worked cases at the published widths."""

import json
import os

import pytest

from benchmarks.lib import costs_keye, costs_qwen3next

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("context,selected,scored", [
    (1, 1, 0), (2048, 2048, 0),         # nothing to choose within topk
    (2049, 2048, 2049), (33280, 2048, 33280)])
def test_tokens_a_query_reads_and_scores(cfg, context, selected, scored):
    assert costs_keye.selected_tokens(context, cfg) == selected
    assert costs_keye.scored_tokens(context, cfg) == scored


def test_a_tokens_rows(cfg):
    # 4 KV heads x 128 x (K + V) x 2 B; 64 x 2 B of indexer key
    assert costs_keye.kv_row_bytes(cfg) == 2048
    assert costs_keye.index_row_bytes(cfg) == 128


def test_a_tick_reads_the_chosen_rows_and_every_indexer_key(cfg):
    # one lane at 20,000 tokens of context: 2,048 x 2,048 B of K/V and
    # 20,000 x 128 B of indexer keys a layer, four layers
    b = costs_keye.indexed_decode_bytes(2048, 20000, cfg)
    assert b == 4 * (2048 * 2048 + 20000 * 128) == 27_017_216
    # dense attention would read 20,000 x 2,048 B a layer: 6.1 times
    assert 4 * 20000 * 2048 / b > 6
    # sixteen such lanes: 432 MB, 0.53 ms at 819 GB/s
    moved = costs_keye.indexed_decode_bytes(16 * 2048, 16 * 20000, cfg)
    assert abs(moved / PEAKS["hbm_bytes_per_s"] - 0.528e-3) < 1e-6


def test_a_windows_operations(cfg):
    # the first window selects nothing: 1 + 2 + ... + 2048 tokens read,
    # no pair scored
    assert costs_keye.window_selected_tokens(0, 2048, cfg) == \
        2048 * 2049 // 2
    assert costs_keye.window_scored_pairs(0, 2048, cfg) == 0
    # a window at 8,192: every query reads 2,048 and scores its context
    assert costs_keye.window_selected_tokens(8192, 2048, cfg) == 2048 * 2048
    pairs = costs_keye.window_scored_pairs(8192, 2048, cfg)
    assert pairs == sum(range(8193, 10241)) == 18_875_392
    # 2 x 16 x 64 FLOP a pair a layer: 0.155 TFLOP, 0.78 ms at the peak
    flops = costs_keye.index_score_flops(pairs, cfg)
    assert flops == 2 * 16 * 64 * pairs * 4
    assert abs(flops / PEAKS["bf16_flops_per_s"] - 0.785e-3) < 1e-6
    # 4 x 32 x 128 FLOP a chosen token a query a layer: 0.275 TFLOP
    read = costs_keye.indexed_prefill_flops(2048 * 2048, cfg)
    assert read == 4 * 32 * 128 * 2048 * 2048 * 4
    assert abs(read / PEAKS["bf16_flops_per_s"] - 1.395e-3) < 1e-6
    # a partial last window counts its real queries only
    assert costs_keye.window_scored_pairs(8192, 3, cfg) == 8193 + 8194 + 8195


def test_an_experts_bytes_are_the_accepted_functions(cfg):
    # 3 x 2048 x 768 x 2 B = 9.4 MB an expert; 81 touched a layer in four
    assert costs_qwen3next.expert_bytes(cfg) == 9_437_184
    assert costs_qwen3next.moe_decode_bytes(4 * 81, cfg) == 324 * 9_437_184
