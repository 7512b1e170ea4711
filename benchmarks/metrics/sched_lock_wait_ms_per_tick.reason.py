"""As `sched_lock_wait_ms_per_tick.chat`, in the JoyAI cell (64 lanes, 96 callers, unrolled layers)."""
from benchmarks.lib import manifest

read = manifest.reader("sched_lock_wait_ms_per_tick.chat")
