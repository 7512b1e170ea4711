"""The latent decode attention's share of its roofline. Bound: bytes.
The least time is `costs_joyai.mla_decode_attention_bytes` at the
window's mean real cached tokens a tick (delta of the engine's
attended-tokens counter over delta of its ticks; the bucket's and the
row's padding left out) over the published HBM bytes/s; the time taken
a tick is the device seconds of the operations under the scope
`fstpu_mla_decode_attention` inside the decode program's runs in the
traced window, over those runs."""
from benchmarks.lib import costs_joyai, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    attended = obsutil.counter_delta(
        obs, "fstpu_serving_kv_tokens_attended_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, costs_joyai.MLA_DECODE_SCOPE, trace_lines.DECODE))
    if not ticks or attended is None or not taken:
        return None
    cfg = obs["config"]
    needed = costs_joyai.mla_decode_attention_bytes(
        attended / ticks, cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
        costs_joyai.DTYPE_BYTES[cfg["program"]["dtype"]],
        cfg["num_hidden_layers"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
