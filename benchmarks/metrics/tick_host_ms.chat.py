"""The host's share of a token gap: over consecutive `serving/decode`
spans with no `serving/prefill` between them, start to next start less
the device-busy time inside the first; median."""
import statistics

from benchmarks.lib import obsutil, xplane, xplane_attrs


def read(obs):
    t = obsutil.traced(obs)
    if t is None:
        return None
    trace, lo, hi = t
    ticks = sorted(xplane.spans(trace, "serving/decode", lo, hi))
    prefills = [a for a, _ in xplane.spans(trace, "serving/prefill", lo, hi)]
    busy = xplane_attrs.Busy(trace, lo, hi)
    host = [c - a - busy.seconds(a, b)
            for (a, b), (c, _) in zip(ticks, ticks[1:])
            if not any(a <= p < c for p in prefills)]
    return 1e3 * statistics.median(host) if host else None
