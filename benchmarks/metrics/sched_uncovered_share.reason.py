"""As `sched_uncovered_share.chat`, in the JoyAI cell (64 lanes, 96 callers, unrolled layers)."""
from benchmarks.lib import manifest

read = manifest.reader("sched_uncovered_share.chat")
