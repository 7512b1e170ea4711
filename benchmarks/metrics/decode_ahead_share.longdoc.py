"""Decode ticks enqueued one ahead over all decode ticks, counter deltas
over the window (as `decode_ahead_share.chat`). A prompt's windows
are an admission each: the tick after one is not ahead."""
from benchmarks.lib import manifest

read = manifest.reader("decode_ahead_share.chat")
