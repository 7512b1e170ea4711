"""Experts with at least one assignment in a decode tick's layer, over
the experts there are: deltas of the engine's `fstpu_moe_experts_touched
_total` over `n_routed_experts` x `fstpu_moe_layer_ticks_total`. At 64
live lanes and 8 of 256 picks a token, independent picks would touch
256 (1 - (1 - 8/256)^64) = 222 of 256, 86.9 %."""
from benchmarks.lib import obsutil


def read(obs):
    layer_ticks = obsutil.counter_delta(obs, "fstpu_moe_layer_ticks_total")
    touched = obsutil.counter_delta(obs, "fstpu_moe_experts_touched_total")
    if not layer_ticks or touched is None:
        return None
    return 100.0 * touched / (obs["config"]["n_routed_experts"] *
                              layer_ticks)
