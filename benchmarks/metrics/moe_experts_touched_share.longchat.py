"""Experts held here with at least one assignment in a decode tick's
layer, over the experts held: deltas of `fstpu_moe_experts_touched_total`
over `num_experts` (the count held) x `fstpu_moe_layer_ticks_total`. At
64 live lanes and 10 of 512 picks a token, independent picks would
touch 256 (1 - (1 - 10/512)^64) = 183 of the 256 held, 71.7 %."""
from benchmarks.lib import obsutil


def read(obs):
    layer_ticks = obsutil.counter_delta(obs, "fstpu_moe_layer_ticks_total")
    touched = obsutil.counter_delta(obs, "fstpu_moe_experts_touched_total")
    if not layer_ticks or touched is None or \
            "fstpu_moe_assignments_held_total" not in obs["stats_open"]:
        return None
    return 100.0 * touched / (obs["config"]["num_experts"] * layer_ticks)
