"""The sparse prefill read's share of its roofline. Bound: operations.
The least time is `costs_sala.sparse_prefill_flops` over the tokens the
real queries of the traced windows read (`serving/prefill/window`
spans: window index and real tokens; a query reads what
`costs_sala.attended_tokens` says at its position) over the published
bf16 peak; the time taken is the device seconds under the scope
`fstpu_sparse_prefill_attention` over the traced window, scaled to the
windows whose spans were seen (window program runs over spans: a span
ends before its program does)."""
from benchmarks.lib import costs_sala, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    runs = trace_lines.module_runs(obs, trace_lines.WINDOW)
    taken = trace_lines.scope_seconds(obs, "fstpu_sparse_prefill_attention")
    if not spans or not runs or not taken:
        return None
    cfg = obs["config"]
    width = max(obs["mix"]["engine_args"]["buckets"])
    chosen = sum(costs_sala.window_chosen_tokens(w * width, n, cfg)
                 for w, n in spans)
    needed = costs_sala.sparse_prefill_flops(chosen, cfg) / \
        obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * needed / (taken * len(spans) / len(runs))
