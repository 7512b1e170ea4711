"""`serving/prefill` span time over `serving/prefill` + `serving/decode`
span time, from the trace's host spans."""
from benchmarks.lib import obsutil, xplane


def read(obs):
    t = obsutil.traced(obs)
    if t is None:
        return None
    trace, lo, hi = t
    pre = sum(b - a for a, b in xplane.spans(trace, "serving/prefill", lo, hi))
    dec = sum(b - a for a, b in xplane.spans(trace, "serving/decode", lo, hi))
    return 100.0 * pre / (pre + dec) if pre + dec else None
