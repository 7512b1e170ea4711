"""The block tick's attention read's share of its roofline. Bound:
bytes. The least time a tick is `costs_sdar.block_decode_bytes` at the
window's mean attended tokens a tick (delta of
`fstpu_serving_kv_tokens_attended_total`, `cursor + L` a live lane, over
delta of the ticks: 2,048 B of K/V a token a layer, read ONCE for the
block's `L` queries) over the published HBM bytes/s; the time taken a
tick is the device seconds under the scope
`fstpu_block_decode_attention` inside the decode program's runs in the
traced window, over those runs."""
from benchmarks.lib import costs_sdar, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    attended = obsutil.counter_delta(
        obs, "fstpu_serving_kv_tokens_attended_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, costs_sdar.DECODE_SCOPE, trace_lines.DECODE))
    if not ticks or attended is None or not taken:
        return None
    needed = costs_sdar.block_decode_bytes(attended / ticks, obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
