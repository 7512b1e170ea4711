"""Decode ticks enqueued while the previous tick's tokens were still
unfetched, over all decode ticks: counter deltas over the window. The
share of ticks whose host part ran under the device's."""
from benchmarks.lib import obsutil


def read(obs):
    ticks = obsutil.counter_delta(obs, "fstpu_serving_decode_ticks_total")
    ahead = obsutil.counter_delta(
        obs, "fstpu_serving_decode_ticks_ahead_total")
    if ahead is None or not ticks:
        return None
    return 100.0 * ahead / ticks
