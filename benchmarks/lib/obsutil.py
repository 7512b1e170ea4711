"""Small helpers the metric readers share. A reader that finds nothing
to read returns None and the harness leaves the metric out."""

from __future__ import annotations

from benchmarks.lib import reduce as R
from benchmarks.lib import xplane


def traced(obs):
    """(trace, lo, hi) on the trace's clock, or None without a trace."""
    if obs.get("trace") is None or not obs["trace"]["devices"]:
        return None
    lo, hi = obs["trace_window"]
    return obs["trace"], lo, hi


def idle_share(obs):
    t = traced(obs)
    if t is None:
        return None
    trace, lo, hi = t
    return 100.0 * (1.0 - xplane.busy_seconds(trace, lo, hi) / (hi - lo))


def counter_delta(obs, name: str):
    """Delta over the window of one of the engine's `/metrics`
    counters."""
    if "stats_open" not in obs or name not in obs["stats_open"]:
        return None
    return obs["stats_close"][name] - obs["stats_open"][name]


def hbm_peak_gb(obs):
    return obs["memory_peak_bytes"] / 1e9 if obs.get("memory_peak_bytes") \
        else None


def records_due(obs):
    return R.due_in_window(obs["records"], *obs["window"])
