"""The latent layers' windowed read's share of its roofline. Bound:
operations. The least time is `costs_kimi.mla_prefill_flops` over the
visible (query, key) pairs of the traced windows' REAL queries (query
`i` of a window at `start` reads `start + i + 1` keys; 32 heads x (2 x
192 + 2 x 128) FLOP a pair) over the published bf16 peak; the time
taken is the device seconds under the scope
`fstpu_mla_prefill_attention` inside the window program's runs in the
traced window, scaled to the windows whose spans were seen."""
from benchmarks.lib import costs_kimi, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    taken = trace_lines.scope_seconds_in(
        obs, costs_kimi.MLA_PREFILL_SCOPE, trace_lines.WINDOW)
    if not spans or not taken or not taken[0]:
        return None
    width = max(obs["mix"]["engine_args"]["buckets"])
    pairs = sum(costs_kimi.full_prefill_pairs(w * width, n)
                for w, n in spans)
    needed = costs_kimi.mla_prefill_flops(pairs, obs["config"]) / \
        obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * needed / (taken[0] * len(spans) / taken[1])
