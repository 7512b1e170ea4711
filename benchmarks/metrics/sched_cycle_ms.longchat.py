"""As `sched_cycle_ms.chat`, in the long-chat cell (64 lanes, 96 callers, unrolled layers)."""
from benchmarks.lib import manifest

read = manifest.reader("sched_cycle_ms.chat")
