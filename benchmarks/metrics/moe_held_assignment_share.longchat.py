"""Assignments of a decode tick's live lanes that landed on an expert
HELD here over all of them: deltas of `fstpu_moe_assignments_held_total`
over `fstpu_moe_assignments_total`. About half says the router routes
over all its published outputs and the chip computes its own share."""
from benchmarks.lib import obsutil


def read(obs):
    held = obsutil.counter_delta(obs, "fstpu_moe_assignments_held_total")
    total = obsutil.counter_delta(obs, "fstpu_moe_assignments_total")
    if not total or held is None:
        return None
    return 100.0 * held / total
