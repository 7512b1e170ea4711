"""Occupied over total slot ticks, deltas of the engine's counters over
the window."""
from benchmarks.lib.serving import lane_occupancy as read  # noqa: F401
