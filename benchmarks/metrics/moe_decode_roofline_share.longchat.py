"""The routed experts' share of their roofline in a decode tick. Bound:
bytes. The least time is `costs_qwen3next.moe_decode_bytes` at the
window's mean HELD experts touched a tick (delta of
`fstpu_moe_experts_touched_total` over delta of the ticks: every
layer's touched experts, each read once) over the published HBM
bytes/s; the time taken a tick is the device seconds of the operations
under the scope `fstpu_moe_experts` (the sort, the rows' gather, the
unsort and weighted sum) and of the grouped matmuls themselves
(`ragged-dot*` custom calls, matched by name: XLA:TPU drops their
`op_name`) inside the decode program's runs in the traced window, over
those runs."""
from benchmarks.lib import costs_qwen3next, obsutil, trace_lines


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    touched = obsutil.counter_delta(obs, "fstpu_moe_experts_touched_total")
    taken = trace_lines.seconds_a_run(trace_lines.scope_seconds_in(
        obs, costs_qwen3next.EXPERT_SCOPES, trace_lines.DECODE))
    if not ticks or touched is None or not taken:
        return None
    needed = costs_qwen3next.moe_decode_bytes(touched / ticks, obs["config"])
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / taken
