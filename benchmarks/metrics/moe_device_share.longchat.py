"""Device seconds of the experts (the scopes `fstpu_moe_route`,
`fstpu_moe_experts`, `fstpu_moe_shared`, and the grouped matmuls
themselves: XLA:TPU lowers `jax.lax.ragged_dot` to a custom call
`ragged-dot*` whose `op_name` it drops, so those are matched by their
own name) over the device's busy seconds, in the traced window."""
from benchmarks.lib import trace_qwen3next, trace_sala


def read(obs):
    return trace_sala.share_of_busy(obs, trace_qwen3next.MOE_SCOPES)
