"""The linear layers' decode step's share of its roofline. Bound:
bytes. The least time a tick is `costs_sala.linear_decode_bytes` at the
window's mean live lanes a tick (delta of the occupied slot ticks over
delta of the ticks): every live lane's float32 state in and out, over
the published HBM bytes/s; the time taken a tick is the device seconds
under the scope `fstpu_lightning_decode` over the traced window, over
the decode program's runs in it."""
from benchmarks.lib import costs_sala, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    lanes = obsutil.counter_delta(
        obs, "fstpu_serving_occupied_slot_ticks_total")
    taken = trace_lines.scope_seconds(obs, "fstpu_lightning_decode")
    runs = trace_lines.module_runs(obs, trace_lines.DECODE)
    if not ticks or lanes is None or not taken or not runs:
        return None
    needed = costs_sala.linear_decode_bytes(lanes / ticks, obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / \
        (taken / len(runs))
