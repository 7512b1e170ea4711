"""The paged decode attention's share of its roofline. Bound: bytes.
The least time is `costs.decode_attention_bytes` at the window's mean
real cached tokens a tick (delta of the engine's attended-tokens counter
over delta of its ticks; padding left out) over the published HBM
bytes/s; the time taken a tick is the device seconds of the operations
named `fstpu_decode_attention*` (the Mosaic kernel, a call a layer)
inside the decode program's runs in the traced window, over those
runs."""
from benchmarks.lib import costs, obsutil, trace_lines

KERNEL = "fstpu_decode_attention"
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    attended = obsutil.counter_delta(
        obs, "fstpu_serving_kv_tokens_attended_total")
    taken = trace_lines.seconds_a_run(trace_lines.kernel_seconds_in(
        obs, KERNEL, trace_lines.DECODE))
    if not ticks or attended is None or not taken:
        return None
    cfg = obs["config"]
    kv_bytes = 1 if cfg["engine_args"]["kv_dtype"] == "int8" \
        else DTYPE_BYTES[cfg["program"]["dtype"]]
    needed = costs.decode_attention_bytes(
        attended / ticks, cfg["num_key_value_heads"], cfg["head_dim"],
        kv_bytes, cfg["num_hidden_layers"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
