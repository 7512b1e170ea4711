"""The full layers' windowed read's share of its roofline. Bound:
operations. As `window_prefill_attn_roofline_share.mixedlen` with every
key up to the query visible (query `i` of a window at `start` reads
`start + i + 1`), under the scope `fstpu_full_prefill_attention`."""
from benchmarks.lib import costs_trinity, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    taken = trace_lines.scope_seconds_in(
        obs, costs_trinity.FULL_PREFILL_SCOPE, trace_lines.WINDOW)
    if not spans or not taken or not taken[0]:
        return None
    cfg = obs["config"]
    width = max(obs["mix"]["engine_args"]["buckets"])
    pairs = sum(costs_trinity.full_prefill_pairs(w * width, n)
                for w, n in spans)
    needed = costs_trinity.attn_flops(
        pairs, costs_trinity.layers(cfg, costs_trinity.FULL), cfg) / \
        obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * needed / (taken[0] * len(spans) / taken[1])
