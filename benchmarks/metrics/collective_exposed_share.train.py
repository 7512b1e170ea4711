"""Time device 0's operation line is held by a collective (its wait or
its own run: the line is serial, so no compute runs meanwhile), over
the traced window. Nothing to read on one chip."""
from benchmarks.lib import obsutil, xplane


def read(obs):
    t = obsutil.traced(obs)
    if t is None or obs["chips"] == 1:
        return None
    trace, lo, hi = t
    return 100.0 * xplane.op_seconds(trace, xplane.COLLECTIVE, lo, hi) \
        / (hi - lo)
