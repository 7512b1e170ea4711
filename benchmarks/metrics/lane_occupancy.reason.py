"""Occupied over total slot ticks, deltas of the engine's counters (as
`lane_occupancy.doc`)."""
from benchmarks.lib import manifest

read = manifest.reader("lane_occupancy.doc")
