"""The full layer's decode read's share of its roofline. Bound: bytes.
The least time a tick is `costs_qwen3next.attn_decode_bytes` at the
window's mean real cached tokens a tick (delta of
`fstpu_serving_kv_tokens_attended_total` over delta of the ticks: K and
V of every live lane's context, 2,048 B a token) over the published HBM
bytes/s; the time taken a tick is the device seconds under the scope
`fstpu_gated_attention_decode` (the live blocks' gather and the
attention) inside the decode program's runs in the traced window, over
those runs."""
from benchmarks.lib import costs_qwen3next, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    tokens = obsutil.counter_delta(
        obs, "fstpu_serving_kv_tokens_attended_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, "fstpu_gated_attention_decode", trace_lines.DECODE))
    if not ticks or tokens is None or not taken:
        return None
    needed = costs_qwen3next.attn_decode_bytes(tokens / ticks, obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
