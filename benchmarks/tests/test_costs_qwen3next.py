"""`lib/costs_qwen3next.py` by hand arithmetic at the published widths,
and `lib/trace_lines.scope_seconds_in` on a small made-up trace."""

import json
import os
import re

from benchmarks.lib import costs_qwen3next as costs
from benchmarks.lib import manifest, trace_lines

with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                       "qwen3-next-80b-a3b.json")) as f:
    CFG = json.load(f)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_layer_counts_and_states():
    assert costs.layer_counts(CFG) == (1, 3)
    assert costs.conv_channels(CFG) == 2048 + 2048 + 4096
    # [32, 128, 128] float32; 3 inputs of 8192 channels in bf16
    assert costs.delta_state_bytes(CFG) == 2_097_152
    assert costs.conv_state_bytes(CFG) == 49_152
    # 64 live lanes x 3 layers x (2 x 2,097,152 + 2 x 49,152) B a tick
    assert costs.gdn_decode_bytes(64, CFG) == 64 * 3 * (
        2 * 2_097_152 + 2 * 49_152) == 824_180_736


def test_prefill_floor_is_bound_by_bytes():
    # a token a layer: (8192 + 2 x 4096) x 2 B = 32,768 B -> 40.0 ns at
    # 819 GB/s; 6 x 128 x 128 x 32 = 3,145,728 operations -> 16.0 ns
    floor, bound = costs.gdn_prefill_floor_s(2048, CFG, PEAKS)
    assert bound == "bytes"
    assert abs(floor - 2048 * 3 * 32768 / 819e9) < 1e-12
    assert 3_145_728 / 197e12 < 32768 / 819e9
    slow = dict(PEAKS, bf16_flops_per_s=1e12)
    assert costs.gdn_prefill_floor_s(1, CFG, slow)[1] == "operations"


def test_attention_and_expert_bytes():
    # K and V, 2 heads of 256, bf16, one full layer
    assert costs.cached_token_bytes(CFG) == 2048
    assert costs.attn_decode_bytes(480_000, CFG) == 480_000 * 2048
    # 3 x 2048 x 512 x 2 B
    assert costs.expert_bytes(CFG) == 6_291_456
    assert costs.moe_decode_bytes(4 * 183, CFG) == 732 * 6_291_456


def test_window_attention_operations():
    # a window of 3 real queries at position 10 reads 11 + 12 + 13 keys
    keys = 36
    assert costs.attn_prefill_flops(10, 3, CFG) == 4.0 * 16 * 256 * keys
    # the first whole window: 2048 x 2049 / 2 keys
    assert costs.attn_prefill_flops(0, 2048, CFG) == \
        4.0 * 16 * 256 * 2048 * 2049 // 2


def test_scope_seconds_inside_one_programs_runs():
    """Operations under a scope are counted only inside the runs of the
    program asked for; overlapping ones once."""
    obs = {"trace": {"devices": {"d0": [["x", 0.0, 1.0]]}},
           "trace_window": (0.0, 10.0),
           "scope_ops": [
               ["jit(decode_fn)/fstpu_short_conv/mul", 1.0, 0.2],
               ["%ragged-dot-none.3", 1.3, 0.3],
               ["while fstpu_short_conv", 1.1, 0.4],       # overlaps both
               ["jit(window_fn)/fstpu_short_conv/mul", 3.0, 0.5],
               ["fstpu_other", 1.0, 1.0]],
           "trace_attrs": {"modules": [["jit_decode_fn(1)", 0.9, 1.0],
                                        ["jit_window_fn(2)", 2.9, 1.0],
                                        ["jit_decode_fn(1)", 9.5, 1.0]]}}
    conv = ("fstpu_short_conv",)
    got = trace_lines.scope_seconds_in(obs, conv, trace_lines.DECODE)
    assert got[1] == 1 and abs(got[0] - 0.5) < 1e-9        # 1.0 .. 1.5
    got = trace_lines.scope_seconds_in(
        obs, conv + ("%ragged-dot",), trace_lines.DECODE)
    assert abs(got[0] - 0.6) < 1e-9                        # 1.0 .. 1.6
    got = trace_lines.scope_seconds_in(obs, conv, trace_lines.WINDOW)
    assert got[1] == 1 and abs(got[0] - 0.5) < 1e-9
    assert trace_lines.scope_seconds_in(
        obs, "fstpu_nothing", trace_lines.DECODE) is None
    assert trace_lines.scope_seconds_in(
        obs, conv, re.compile("jit_assign_fn")) is None
