"""Device-idle time inside one `train/log` span (the Trainer fetching
the logged metrics of the step it just dispatched: the device runs dry
between that step's end and the next dispatch), median over the traced
steps."""
import statistics

from benchmarks.lib import obsutil, xplane, xplane_attrs


def read(obs):
    t = obsutil.traced(obs)
    if t is None:
        return None
    trace, lo, hi = t
    spans = xplane.spans(trace, "train/log", lo, hi)
    if not spans:
        return None
    busy = xplane_attrs.Busy(trace, lo, hi)
    return 1e3 * statistics.median(
        (b - a) - busy.seconds(a, b) for a, b in spans)
