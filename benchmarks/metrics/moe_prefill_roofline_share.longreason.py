"""The routed experts' share of their roofline in a prefill window. The
least time is `costs_kimi.moe_prefill_floor_s` over the traced windows
(`serving/prefill/window` spans): the larger of the 128 held tables'
bytes, each read once a window a layer, and of 6 x 2,304 x 1,024 FLOP a
held assignment; the time taken is the device seconds under
`costs_qwen3next.EXPERT_SCOPES` (the scope `fstpu_moe_experts` and the
`ragged-dot*` calls by name) inside the window program's runs in the
traced window, scaled to the windows whose spans were seen: the same
whichever lowering runs the products."""
from benchmarks.lib import costs_kimi, costs_qwen3next, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    taken = trace_lines.scope_seconds_in(
        obs, costs_qwen3next.EXPERT_SCOPES, trace_lines.WINDOW)
    if not spans or not taken or not taken[0]:
        return None
    needed, _ = costs_kimi.moe_prefill_floor_s(
        sum(n for _, n in spans), len(spans), obs["config"], obs["peaks"])
    return 100.0 * needed / (taken[0] * len(spans) / taken[1])
