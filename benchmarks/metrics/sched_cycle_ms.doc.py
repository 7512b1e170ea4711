"""As `sched_cycle_ms.chat`, in the document cell (32 lanes, 64 callers, scanned layers)."""
from benchmarks.lib import manifest

read = manifest.reader("sched_cycle_ms.chat")
