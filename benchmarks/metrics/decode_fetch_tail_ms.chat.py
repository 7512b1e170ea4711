"""From the end of the last device operation inside a `serving/decode`
span to the end of its `serving/decode/fetch` child: what the copy back
and the wake-up of the scheduler thread cost after the device is done,
median."""
import statistics

from benchmarks.lib import obsutil, xplane_attrs


def read(obs):
    t = obsutil.traced(obs)
    if t is None:
        return None
    trace, lo, hi = t
    busy = xplane_attrs.Busy(trace, lo, hi)
    tails = []
    for (a, b), (_, fetched) in xplane_attrs.children(
            trace, "serving/decode", "serving/decode/fetch", lo, hi):
        done = busy.last_end(a, b)
        if done is not None:
            tails.append(fetched - done)
    return 1e3 * statistics.median(tails) if tails else None
