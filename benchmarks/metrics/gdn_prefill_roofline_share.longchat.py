"""The linear layers' prefill form's share of its roofline. The least
time is `costs_qwen3next.gdn_prefill_floor_s` over the real tokens of
the traced windows (`serving/prefill/window` spans): the larger of the
bytes floor (q, k, v, z in and o out) and of the recurrence's
operations, whatever chunk the program picks — at the published widths
the BYTES bind (40 ns a token a layer against 16 ns); the time taken is
the device seconds under the scopes `fstpu_gated_delta_prefill` and
`fstpu_short_conv` inside the window program's runs in the traced
window, scaled to the windows whose spans were seen."""
from benchmarks.lib import costs_qwen3next, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    taken = trace_lines.scope_seconds_in(
        obs, ("fstpu_gated_delta_prefill", "fstpu_short_conv"),
        trace_lines.WINDOW)
    if not spans or not taken or not taken[0]:
        return None
    needed, _ = costs_qwen3next.gdn_prefill_floor_s(
        sum(n for _, n in spans), obs["config"], obs["peaks"])
    return 100.0 * needed / (taken[0] * len(spans) / taken[1])
