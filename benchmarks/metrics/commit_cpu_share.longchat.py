"""As `commit_cpu_share.chat`, in the long-chat cell (64 lanes, 96 callers, unrolled layers)."""
from benchmarks.lib import manifest

read = manifest.reader("commit_cpu_share.chat")
