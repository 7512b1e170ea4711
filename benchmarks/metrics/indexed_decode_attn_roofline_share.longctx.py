"""The tick's indexed read's share of its roofline. Bound: bytes. The
least time a tick is `costs_keye.indexed_decode_bytes` at the window's
mean selected and scored tokens a tick (deltas of the two index
counters over delta of the ticks: 2,048 B of K/V a selected token and
128 B of indexer key a scored one, a layer) over the published HBM
bytes/s; the time taken a tick is the device seconds under the scopes
`fstpu_index_score`, `fstpu_index_topk` and
`fstpu_indexed_decode_attention` (the lane's indexer keys gathered and
scored, the choice, the chosen rows' gather, the attention) inside the
decode program's runs in the traced window, over those runs."""
from benchmarks.lib import costs_keye, obsutil, trace_lines

SCOPES = ("fstpu_index_score", "fstpu_index_topk",
          "fstpu_indexed_decode_attention")


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    selected = obsutil.counter_delta(
        obs, "fstpu_index_tokens_selected_total")
    scored = obsutil.counter_delta(obs, "fstpu_index_tokens_scored_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, SCOPES, trace_lines.DECODE))
    if not ticks or selected is None or scored is None or not taken:
        return None
    needed = costs_keye.indexed_decode_bytes(
        selected / ticks, scored / ticks, obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
