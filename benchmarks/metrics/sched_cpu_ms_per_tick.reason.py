"""As `sched_cpu_ms_per_tick.chat`, in the JoyAI cell (64 lanes, 96 callers, unrolled layers)."""
from benchmarks.lib import manifest

read = manifest.reader("sched_cpu_ms_per_tick.chat")
