"""The routed experts' share of their roofline in a decode tick. Bound:
bytes. The least time is `costs_joyai.moe_decode_bytes` at the window's
mean experts touched a tick (delta of `fstpu_moe_experts_touched_total`
over delta of the ticks: every expert layer's touched experts, each
read once) over the published HBM bytes/s; the time taken a tick is the
device seconds of the operations under `costs_joyai.EXPERT_SCOPES` (the
scope `fstpu_moe_experts` and the `ragged-dot-none*` calls: the experts
are the decode program's only ragged dots) inside the decode program's
runs in the traced window, over those runs."""
from benchmarks.lib import costs_joyai, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    touched = obsutil.counter_delta(obs, "fstpu_moe_experts_touched_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, costs_joyai.EXPERT_SCOPES, trace_lines.DECODE))
    if not ticks or touched is None or not taken:
        return None
    cfg = obs["config"]
    needed = costs_joyai.moe_decode_bytes(
        touched / ticks, cfg["hidden_size"], cfg["moe_intermediate_size"],
        costs_joyai.DTYPE_BYTES[cfg["program"]["param_dtype"]])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
