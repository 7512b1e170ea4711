"""The full layers' tick read's share of its roofline. Bound: bytes.
The least time a tick is `costs_trinity.full_decode_bytes` at the
window's mean real cached tokens a tick (delta of
`fstpu_serving_kv_tokens_attended_total` over delta of the ticks,
4,096 B a token a full layer) over the published HBM bytes/s; the time
taken a tick is the device seconds under the scope
`fstpu_full_decode_attention` inside the decode program's runs in the
traced window, over those runs. Read only where the program counts a
window layer's keys apart (a model of full layers alone has the paged
kernel's own entry)."""
from benchmarks.lib import costs_trinity, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    cached = obsutil.counter_delta(
        obs, "fstpu_serving_kv_tokens_attended_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, costs_trinity.FULL_DECODE_SCOPE, trace_lines.DECODE))
    if not ticks or cached is None or not taken:
        return None
    needed = costs_trinity.full_decode_bytes(cached / ticks, obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
