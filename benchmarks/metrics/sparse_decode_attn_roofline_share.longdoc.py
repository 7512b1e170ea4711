"""The sparse decode read's share of its roofline. Bound: bytes. The
least time a tick is `costs_sala.sparse_decode_bytes` at the window's
mean attended and cached tokens a tick (deltas of the two sparse
counters over delta of the ticks) over the published HBM bytes/s; the
time taken a tick is the device seconds under the scope
`fstpu_sparse_decode_attention` (the pooled keys' gather and the choice
inside it, the chosen slabs' gather, the attention) over the traced
window, over the decode program's runs in it."""
from benchmarks.lib import costs_sala, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    attended = obsutil.counter_delta(
        obs, "fstpu_sparse_tokens_attended_total")
    cached = obsutil.counter_delta(obs, "fstpu_sparse_tokens_cached_total")
    taken = trace_lines.scope_seconds(obs, "fstpu_sparse_decode_attention")
    runs = trace_lines.module_runs(obs, trace_lines.DECODE)
    if not ticks or attended is None or cached is None or not taken \
            or not runs:
        return None
    needed = costs_sala.sparse_decode_bytes(
        attended / ticks, cached / ticks, obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / \
        (taken / len(runs))
