"""The windowed block-causal read's share of its roofline. Bound:
operations. The least time is `costs_sdar.block_prefill_flops` over the
visible (query, key) pairs of the real queries of the traced windows
(`serving/prefill/window` spans; a query reads every earlier block and
the whole of its own, 4 x 32 x 128 FLOP a pair a layer) over the
published bf16 peak; the time taken is the device seconds under the
scope `fstpu_block_prefill_attention` inside the window program's runs
in the traced window, scaled to the windows whose spans were seen."""
from benchmarks.lib import costs_sdar, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    taken = trace_lines.scope_seconds_in(
        obs, costs_sdar.PREFILL_SCOPE, trace_lines.WINDOW)
    if not spans or not taken or not taken[0]:
        return None
    cfg = obs["config"]
    width = max(obs["mix"]["engine_args"]["buckets"])
    pairs = sum(costs_sdar.window_visible_pairs(w * width, n, cfg)
                for w, n in spans)
    needed = costs_sdar.block_prefill_flops(pairs, cfg) / \
        obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * needed / (taken[0] * len(spans) / taken[1])
