"""The indexer's scores' share of their roofline in a prefill window.
Bound: operations. The least time is `costs_keye.index_score_flops` over
the pairs the real queries of the traced windows have to score
(`serving/prefill/window` spans: window index and real tokens; a query
past 2,048 tokens of context scores every key up to itself, 2 x 16 x 64
FLOP a pair a layer) over the published bf16 peak; the time taken is the
device seconds under the scope `fstpu_index_score` inside the window
program's runs in the traced window, scaled to the windows whose spans
were seen."""
from benchmarks.lib import costs_keye, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    taken = trace_lines.scope_seconds_in(
        obs, "fstpu_index_score", trace_lines.WINDOW)
    if not spans or not taken or not taken[0]:
        return None
    cfg = obs["config"]
    width = max(obs["mix"]["engine_args"]["buckets"])
    pairs = sum(costs_keye.window_scored_pairs(w * width, n, cfg)
                for w, n in spans)
    needed = costs_keye.index_score_flops(pairs, cfg) / \
        obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * needed / (taken[0] * len(spans) / taken[1])
