"""The KDA layers' decode step's share of its roofline. Bound: bytes.
The least time a tick is `costs_kimi.kda_decode_bytes` at the window's
mean live lanes a tick (delta of the occupied slot ticks over delta of
the ticks): every live lane's delta state and convolution state in and
out, a KDA layer, over the published HBM bytes/s; the time taken a tick
is the device seconds under the scopes `fstpu_gated_delta_decode` and
`fstpu_short_conv` inside the decode program's runs in the traced
window, over those runs."""
from benchmarks.lib import costs_kimi, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    lanes = obsutil.counter_delta(
        obs, "fstpu_serving_occupied_slot_ticks_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, costs_kimi.KDA_DECODE_SCOPES, trace_lines.DECODE))
    if not ticks or lanes is None or not taken:
        return None
    needed = costs_kimi.kda_decode_bytes(lanes / ticks, obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
