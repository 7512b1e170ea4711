"""The KDA layers' prefill form's share of its roofline. The least time
is `costs_kimi.kda_prefill_floor_s` over the real tokens of the traced
windows (`serving/prefill/window` spans): the larger of the bytes floor
(q, k, v, the gate's 4,096 values and the output gate in, o out) and of
the recurrence's operations (7 x 128 x 128 a token a head), whatever
chunk, anchoring or kernel the program picks: at the published widths
the BYTES bind (60 ns a token a layer against 19 ns); the time taken is
the device seconds under the scope `fstpu_gated_delta_prefill` inside
the window program's runs in the traced window, scaled to the windows
whose spans were seen."""
from benchmarks.lib import costs_kimi, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    taken = trace_lines.scope_seconds_in(
        obs, costs_kimi.KDA_PREFILL_SCOPE, trace_lines.WINDOW)
    if not spans or not taken or not taken[0]:
        return None
    needed, _ = costs_kimi.kda_prefill_floor_s(
        sum(n for _, n in spans), obs["config"], obs["peaks"])
    return 100.0 * needed / (taken[0] * len(spans) / taken[1])
