"""Median host-clock time between consecutive fetched losses."""
import statistics


def read(obs):
    ends = obs.get("step_ends") or []
    steps = [b - a for a, b in zip(ends, ends[1:])]
    return 1e3 * statistics.median(steps) if steps else None
