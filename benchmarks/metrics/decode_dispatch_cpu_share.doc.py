"""As `decode_dispatch_cpu_share.chat`, in the document cell (32 lanes, 64 callers, scanned layers)."""
from benchmarks.lib import manifest

read = manifest.reader("decode_dispatch_cpu_share.chat")
