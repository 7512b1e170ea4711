"""The re-pointed device readers on a trace made by hand in the shape the
engine has had since PR 27: one decode tick is kept in flight, so a
`serving/decode` span holds the NEXT tick's dispatch and THIS tick's
fetch. Here each span covers the last 30 % of one tick and the first
40 % of the next. The readers take the tick from the module line and a
kernel's seconds from inside that line's runs; each test also states
what the span-bound arithmetic they replace would have read on the same
trace (every number can be checked on paper)."""

import pytest

from benchmarks.lib import (costs, costs_joyai, manifest, trace_lines,
                            xplane, xplane_attrs)

TICK, TICKS, LAYERS = 0.020, 10, 16
T0 = 0.100
KERNEL_EVERY, KERNEL = 0.0012, 0.0004
TICKS_TOTAL = "fstpu_serving_decode_ticks_total"
ATTENDED = "fstpu_serving_kv_tokens_attended_total"
TOUCHED = "fstpu_moe_experts_touched_total"


def _trace():
    """Ten ticks of 20 ms back to back on the module line. A tick is 16
    layers: a 0.4 ms paged-attention kernel every 1.2 ms from 0.3 ms
    in, and between two kernels an expert block of 0.6 ms (a scope of
    0.2 ms around a 0.4 ms `ragged-dot`) and a 0.1 ms latent read. A
    `serving/decode` span runs from 70 % into tick k to 40 % into tick
    k + 1."""
    ops, texts, modules, host = [], [], [], [["bench/traced", 0.0, 1.0]]
    for k in range(TICKS):
        a = T0 + k * TICK
        modules.append(["jit_decode_fn", a, TICK])
        for layer in range(LAYERS):
            s = a + 0.0003 + layer * KERNEL_EVERY
            ops.append(["fstpu_decode_attention.6 bf16[32,1,32,128]",
                        s, KERNEL])
            texts.append(["%fstpu_decode_attention.6 = bf16[32,1,32,128] "
                          "custom-call() jit(decode_fn)/"
                          "fstpu_decode_attention/pallas_call", s, KERNEL])
            e = s + KERNEL
            texts.append(["%fusion.9 = bf16[512,2048] fusion() "
                          "jit(decode_fn)/fstpu_moe_experts/gather", e,
                          0.0002])
            texts.append(["%ragged-dot-none.3 = bf16[512,768] custom-call()"
                          " ragged-dot-none:", e + 0.0002, 0.0004])
            texts.append(["%fusion.4 = bf16[64,32,512] fusion() "
                          "jit(decode_fn)/fstpu_mla_decode_attention/dot",
                          e + 0.0006, 0.0001])
            ops.append(["fusion.9 bf16[512,2048]", e, 0.0002])
            ops.append(["ragged-dot-none.3 bf16[512,768]", e + 0.0002,
                        0.0004])
            ops.append(["fusion.4 bf16[64,32,512]", e + 0.0006, 0.0001])
        if k + 1 < TICKS:
            host.append(["serving/decode", a + 0.7 * TICK, 0.7 * TICK])
    return ops, texts, modules, host


@pytest.fixture(scope="module")
def obs():
    ops, texts, modules, host = _trace()
    return {"trace": {"devices": {"/device:TPU:0": ops}, "host": host},
            "trace_window": (0.0, 1.0),
            "trace_attrs": {"spans": [], "modules": modules},
            "scope_ops": texts,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "stats_open": {TICKS_TOTAL: 1000.0, ATTENDED: 5e6,
                           TOUCHED: 0.0},
            "stats_close": {TICKS_TOTAL: 1100.0, ATTENDED: 9e6,
                            TOUCHED: 85200.0}}


def read(name, obs):
    return manifest.reader(name)(obs)


def _under_spans(trace, events):
    """The arithmetic the readers had before: seconds of `events`
    inside each `serving/decode` span, the spans that hold any."""
    per = []
    for a, b in xplane.spans(trace, "serving/decode", 0.0, 1.0):
        inside = sum(d for _, s, d in events if a <= s and s + d <= b)
        if inside:
            per.append(inside)
    return per


@pytest.mark.parametrize("cell", ["serve", "chat"])
def test_the_tick_is_the_module_lines_not_the_spans(obs, cell):
    assert read(f"decode_step_device_ms.{cell}", obs) == pytest.approx(20.0)
    # the device under one `serving/decode` span: 6 ms of this tick
    # and 8 ms of the next (less the idle slivers between operations),
    # a part of a tick: the reading fell 17.85 -> 10.26 ms in the doc
    # cell when PR 27 kept a tick in flight, and no tick got shorter
    spans = xplane.spans(obs["trace"], "serving/decode", 0.0, 1.0)
    assert len(spans) == 9
    busy = xplane_attrs.Busy(obs["trace"], 0.0, 1.0)
    under = [busy.seconds(a, b) for a, b in spans]
    assert all(0.5 * TICK < u < 0.7 * TICK for u in under)


def test_the_paged_kernels_share_is_taken_over_whole_ticks(obs):
    obs = dict(obs, config={
        "num_key_value_heads": 8, "head_dim": 128, "num_hidden_layers": 16,
        "program": {"dtype": "bfloat16"},
        "engine_args": {"kv_dtype": "fp32"}})
    # 40,000 real cached tokens a tick: 3.2 ms at 819 GB/s; the kernel
    # runs 16 x 0.4 = 6.4 ms in every tick: a true 50 %
    least = costs.decode_attention_bytes(40000, 8, 128, 2, 16) / 819e9
    share = read("decode_attn_roofline_share.doc", obs)
    assert share == pytest.approx(100 * least / 0.0064)
    assert share == pytest.approx(50.0, abs=0.1)
    # a span holds the kernel's last 4 calls of one tick and the first 7
    # of the next, 11 of 16: the old arithmetic read 16 / 11 = 1.45
    # times the true share (PERF.md: x1.44-1.57 on the chip)
    kernel = [e for e in xplane.first_device(obs["trace"])
              if e[0].startswith("fstpu_decode_attention")]
    old = _under_spans(obs["trace"], kernel)
    assert old == [pytest.approx(11 * KERNEL)] * 9
    assert 100 * least / old[0] == pytest.approx(share * 16 / 11)
    assert 100 * least / old[0] > 72


def test_the_experts_and_the_latent_reads_shares_over_whole_ticks(obs):
    obs = dict(obs, config={
        "hidden_size": 2048, "moe_intermediate_size": 768,
        "kv_lora_rank": 512, "qk_rope_head_dim": 64,
        "num_hidden_layers": 5,
        "program": {"dtype": "bfloat16", "param_dtype": "bfloat16"}})
    # 852 experts touched a tick x 9.4 MB = 8.04 GB: 9.8 ms at 819
    # GB/s; scope and ragged dots run 16 x 0.6 = 9.6 ms a tick
    needed = costs_joyai.moe_decode_bytes(852, 2048, 768, 2) / 819e9
    assert read("moe_decode_roofline_share.reason", obs) == \
        pytest.approx(100 * needed / 0.0096)
    # the latent read: 16 x 0.1 ms a tick under its scope
    latent = costs_joyai.mla_decode_attention_bytes(40000, 512, 64, 2, 5) \
        / 819e9
    assert read("mla_decode_attn_roofline_share.reason", obs) == \
        pytest.approx(100 * latent / 0.0016)
    # under a span: 10 of the scope's 16 operations and 11 of the 16
    # ragged dots, 6.4 of 9.6 ms: the old arithmetic read x1.5
    experts = [e for e in obs["scope_ops"]
               if "fstpu_moe_experts" in e[0] or "%ragged-dot-none" in e[0]]
    old = _under_spans(obs["trace"], experts)
    assert old[0] == pytest.approx(10 * 0.0002 + 11 * 0.0004)
    # a run that is cut by the window's edge is left out with its
    # operations: seven whole ticks, the same seconds a tick
    cut = dict(obs, trace_window=(0.125, 0.285))
    taken = trace_lines.scope_seconds_in(
        cut, costs_joyai.EXPERT_SCOPES, trace_lines.DECODE)
    assert taken == (pytest.approx(7 * 0.0096), 7)


def test_a_padded_prompt_token_over_windows_and_whole_prompts():
    """Two windows of 2,048 (40 ms each, enqueued back to back: their
    spans end long before their runs) and one whole-prompt prefill of
    the 512 bucket (10 ms) whose span waits for its first token."""
    modules = [["jit_window_fn", 0.10, 0.04], ["jit_window_fn", 0.14, 0.04],
               ["jit_prefill_fn", 0.30, 0.01],
               ["jit_prefill_fn", 0.50, 0.01]]
    spans = [["serving/prefill/window", 0.09, 0.002,
              {"request_id": "r", "window": 0, "tokens": 2048}],
             ["serving/prefill/window", 0.092, 0.002,
              {"request_id": "r", "window": 1, "tokens": 1000}],
             ["serving/prefill", 0.28, 0.04,
              {"request_id": "s", "bucket": 512, "prompt_tokens": 300}]]
    obs = {"trace": {"devices": {"/device:TPU:0": [["f", 0.1, 0.3]]},
                     "host": []},
           "trace_window": (0.0, 1.0),
           "trace_attrs": {"spans": spans, "modules": modules},
           "mix": {"engine_args": {"buckets": [512, 2048]}}}
    # 90 ms over 2 x 2,048 + 512 padded tokens; the run at 0.50 finds
    # no span and is left out of both sums
    assert read("prefill_device_us_per_token.serve", obs) == \
        pytest.approx(1e6 * 0.09 / 4608)
    assert trace_lines.window_spans(obs) == [(0, 2048), (1, 1000)]
    # under the window's spans the device did nothing at all
    obs["trace_attrs"] = {"spans": spans, "modules": modules[:2]}
    assert read("prefill_device_us_per_token.serve", obs) == \
        pytest.approx(1e6 * 0.08 / 4096)


def test_the_split_lists_a_scopes_longest_operations_beside_its_total(obs):
    """`tools/split_scopes.py` on the same trace: a tick by scope, and
    what the scope's seconds are made of."""
    import io

    from benchmarks.tools import split_scopes
    out = io.StringIO()
    split_scopes.split(obs, "jit_decode_fn", None, 2, out)
    said = out.getvalue().splitlines()
    assert said[0].strip().startswith("jit_decode_fn: 10 runs, median 20.000")
    # the kernel's scope 6.4 ms, the experts' 3.2 and the ragged dots
    # 6.4 (by their own name), the latent read 1.6 ms a tick
    totals = {line.split(":")[0].strip(): float(line.split(":")[1].split()[0])
              for line in said if " ms a run (" in line}
    assert totals == {"fstpu_decode_attention": pytest.approx(6.4),
                      "%ragged-dot": pytest.approx(6.4),
                      "fstpu_moe_experts": pytest.approx(3.2),
                      "fstpu_mla_decode_attention": pytest.approx(1.6)}
    at = next(i for i, line in enumerate(said)
              if line.strip().startswith("fstpu_moe_experts:"))
    assert said[at + 1].split()[:2] == ["fusion.9", "bf16[512,2048]"]
    assert "16.0 calls, 0.200 ms each" in said[at + 1]
    out = io.StringIO()
    split_scopes.split(obs, "jit_window_fn", None, 2, out)
    assert "no run in the traced window" in out.getvalue()
