"""Decode ticks enqueued while the previous tick's tokens were still
unfetched, over all decode ticks: counter deltas over the window. The
share of ticks whose host part ran under the device's."""
from benchmarks.lib.serving import decode_ahead_share as read  # noqa: F401
