"""Device seconds of the experts (the scopes `fstpu_moe_route`,
`fstpu_moe_experts`, `fstpu_moe_shared`, and the grouped matmuls
themselves: XLA:TPU lowers `jax.lax.ragged_dot` to a custom call
`ragged-dot*` whose `op_name` it drops, so those are matched by their
own name) over the device's busy seconds, in the traced window."""
from benchmarks.lib import costs_qwen3next, trace_lines


def read(obs):
    return trace_lines.share_of_busy(obs, costs_qwen3next.MOE_SCOPES)
