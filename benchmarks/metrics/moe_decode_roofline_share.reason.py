"""The routed experts' share of their roofline in a decode tick. Bound:
bytes. The least time is `costs_joyai.moe_decode_bytes` at the window's
mean experts touched a tick (delta of `fstpu_moe_experts_touched_total`
over delta of the ticks: every expert layer's touched experts, each
read once) over the published HBM bytes/s; the time taken is the mean,
over the traced ticks, of the device time, inside one `serving/decode`
span, of the operations under the scope `fstpu_moe_experts` (the sort,
the rows' gather, the unsort and weighted sum) and of the grouped
matmuls themselves: XLA:TPU lowers `jax.lax.ragged_dot` to a custom
call `ragged-dot-none*` whose `op_name` it drops (my chip run, PR 26:
`tf_op=ragged-dot-none:`), so those are matched by their own name; the
experts are the decode program's only ragged dots."""
from benchmarks.lib import costs_joyai, obsutil, scopes


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    touched = obsutil.counter_delta(obs, "fstpu_moe_experts_touched_total")
    taken = scopes.seconds_per_span(
        obs, ("fstpu_moe_experts", "%ragged-dot-none"), "serving/decode")
    if not ticks or touched is None or not taken:
        return None
    cfg = obs["config"]
    needed = costs_joyai.moe_decode_bytes(
        touched / ticks, cfg["hidden_size"], cfg["moe_intermediate_size"],
        costs_joyai.DTYPE_BYTES[cfg["program"]["param_dtype"]])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
