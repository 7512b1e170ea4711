"""As `sched_taken_share.chat`, in the document cell (32 lanes, 64 callers, scanned layers)."""
from benchmarks.lib import manifest

read = manifest.reader("sched_taken_share.chat")
