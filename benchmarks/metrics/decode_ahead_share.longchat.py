"""As `decode_ahead_share.chat`, in the long-chat cell: decode ticks
enqueued one ahead over all decode ticks, counter deltas over the window.
A prompt's windows are an admission each: the tick after one is not ahead."""
from benchmarks.lib import manifest

read = manifest.reader("decode_ahead_share.chat")
