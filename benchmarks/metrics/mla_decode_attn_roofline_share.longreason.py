"""The latent decode attention's share of its roofline where ONE layer
of the model is latent. Bound: bytes. The least time is
`costs_kimi.mla_decode_bytes` at the window's mean real cached tokens a
tick (delta of the engine's attended-tokens counter, which counts a
lane's tokens once, over delta of its ticks; the row's padding left
out): 576 values x 2 B a token x the latent layers, over the published
HBM bytes/s; the time taken a tick is the device seconds of the
operations under the scope `fstpu_mla_decode_attention` inside the
decode program's runs in the traced window, over those runs.
(`mla_decode_attn_roofline_share.reason` multiplies by
`num_hidden_layers`: every layer of its model is latent.)"""
from benchmarks.lib import costs_kimi, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    attended = obsutil.counter_delta(
        obs, "fstpu_serving_kv_tokens_attended_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, costs_kimi.MLA_DECODE_SCOPE, trace_lines.DECODE))
    if not ticks or attended is None or not taken:
        return None
    needed = costs_kimi.mla_decode_bytes(attended / ticks, obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
