"""Occupied over total slot ticks, deltas of the engine's counters."""
from benchmarks.lib import obsutil


def read(obs):
    total = obsutil.counter_delta(obs, "fstpu_serving_slot_ticks_total")
    if not total:
        return None
    return 100.0 * obsutil.counter_delta(
        obs, "fstpu_serving_occupied_slot_ticks_total") / total
