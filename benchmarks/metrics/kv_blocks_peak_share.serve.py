"""Highest `blocks_used / blocks_total` polled through the window."""
from benchmarks.lib.serving import kv_blocks_peak_share as read  # noqa: F401
