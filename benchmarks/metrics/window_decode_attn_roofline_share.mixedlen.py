"""The window layers' tick read's share of its roofline. Bound: bytes.
The least time a tick is `costs_trinity.window_decode_bytes` at the
window's mean attended keys a tick (delta of
`fstpu_serving_kv_window_tokens_attended_total` over delta of the
ticks: min(cursor + 1, 4,096) a live lane, 4,096 B a key a layer) over
the published HBM bytes/s; the time taken a tick is the device seconds
under the scope `fstpu_window_decode_attention` (the live blocks' table
and mask, the read through it) inside the decode program's runs in the
traced window, over those runs."""
from benchmarks.lib import costs_trinity, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    attended = obsutil.counter_delta(
        obs, "fstpu_serving_kv_window_tokens_attended_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, costs_trinity.WINDOW_DECODE_SCOPE, trace_lines.DECODE))
    if not ticks or attended is None or not taken:
        return None
    needed = costs_trinity.window_decode_bytes(attended / ticks,
                                               obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
