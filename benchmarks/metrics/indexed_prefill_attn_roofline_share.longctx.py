"""The windowed read of the chosen tokens' share of its roofline. Bound:
operations. The least time is `costs_keye.indexed_prefill_flops` over
the tokens the real queries of the traced windows read
(`serving/prefill/window` spans; a query reads min(context, 2,048)
tokens, 4 x 32 x 128 FLOP a token a layer) over the published bf16
peak; the time taken is the device seconds under the scope
`fstpu_indexed_prefill_attention` inside the window program's runs in
the traced window, scaled to the windows whose spans were seen."""
from benchmarks.lib import costs_keye, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    taken = trace_lines.scope_seconds_in(
        obs, "fstpu_indexed_prefill_attention", trace_lines.WINDOW)
    if not spans or not taken or not taken[0]:
        return None
    cfg = obs["config"]
    width = max(obs["mix"]["engine_args"]["buckets"])
    selected = sum(costs_keye.window_selected_tokens(w * width, n, cfg)
                   for w, n in spans)
    needed = costs_keye.indexed_prefill_flops(selected, cfg) / \
        obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * needed / (taken[0] * len(spans) / taken[1])
