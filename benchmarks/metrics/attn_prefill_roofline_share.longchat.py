"""The full layer's windowed read's share of its roofline. Bound:
operations. The least time is `costs_qwen3next.attn_prefill_flops` over
the real queries of the traced windows (`serving/prefill/window` spans:
window index and real tokens; query `i` of a window at `start` reads
`start + i + 1` keys) over the published bf16 peak; the time taken is
the device seconds under the scope `fstpu_gated_attention_prefill`
inside the window program's runs in the traced window, scaled to the
windows whose spans were seen."""
from benchmarks.lib import costs_qwen3next, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    taken = trace_lines.scope_seconds_in(
        obs, "fstpu_gated_attention_prefill", trace_lines.WINDOW)
    if not spans or not taken or not taken[0]:
        return None
    cfg = obs["config"]
    width = max(obs["mix"]["engine_args"]["buckets"])
    needed = sum(costs_qwen3next.attn_prefill_flops(w * width, n, cfg)
                 for w, n in spans) / obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * needed / (taken[0] * len(spans) / taken[1])
