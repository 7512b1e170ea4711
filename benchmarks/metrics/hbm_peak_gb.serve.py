"""`memory_stats()` peak on the chip (misses program scratch)."""
from benchmarks.lib.obsutil import hbm_peak_gb as read  # noqa: F401
