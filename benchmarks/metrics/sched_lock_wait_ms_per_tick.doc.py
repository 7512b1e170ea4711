"""As `sched_lock_wait_ms_per_tick.chat`, in the document cell (32 lanes, 64 callers, scanned layers)."""
from benchmarks.lib import manifest

read = manifest.reader("sched_lock_wait_ms_per_tick.chat")
