"""As `decode_dispatch_ms.chat`, in the JoyAI cell (64 lanes, 96 callers, unrolled layers)."""
from benchmarks.lib import manifest

read = manifest.reader("decode_dispatch_ms.chat")
