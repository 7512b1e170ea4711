"""The paged decode attention's share of its roofline. Bound: bytes.
The least time is `costs.decode_attention_bytes` at the window's mean
real cached tokens a tick (delta of the engine's attended-tokens counter
over delta of its ticks; padding left out) over the published HBM
bytes/s; the time taken is the mean, over the traced ticks, of the
device time of the operations named `fstpu_decode_attention*` inside
one `serving/decode` span."""
import statistics

from benchmarks.lib import costs, obsutil, xplane

KERNEL = "fstpu_decode_attention"
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def read(obs):
    t = obsutil.traced(obs)
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    attended = obsutil.counter_delta(
        obs, "fstpu_serving_kv_tokens_attended_total")
    if t is None or not ticks or attended is None:
        return None
    trace, lo, hi = t
    kernel = sorted((s, s + d) for n, s, d in xplane.first_device(trace)
                    if n.startswith(KERNEL))
    per_tick = []
    for a, b in xplane.spans(trace, "serving/decode", lo, hi):
        inside = sum(e - s for s, e in kernel if a <= s and e <= b)
        if inside:
            per_tick.append(inside)
    if not per_tick:
        return None
    cfg = obs["config"]
    kv_bytes = 1 if cfg["engine_args"]["kv_dtype"] == "int8" \
        else DTYPE_BYTES[cfg["program"]["dtype"]]
    needed = costs.decode_attention_bytes(
        attended / ticks, cfg["num_key_value_heads"], cfg["head_dim"],
        kv_bytes, cfg["num_hidden_layers"])
    least = needed / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / statistics.mean(per_tick)
