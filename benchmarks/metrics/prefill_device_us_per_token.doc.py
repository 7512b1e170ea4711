"""Device time under the traced `serving/prefill` spans over the sum of
their `bucket` attributes: what a padded prompt token costs the device,
whatever mix of bucket widths the traced seconds held."""
from benchmarks.lib import obsutil, xplane_attrs


def read(obs):
    t = obsutil.traced(obs)
    attrs = xplane_attrs.of(obs)
    if t is None or attrs is None:
        return None
    trace, lo, hi = t
    spans = [(a, b, at["bucket"]) for a, b, at in xplane_attrs.spans_with(
        attrs, "serving/prefill", lo, hi) if "bucket" in at]
    if not spans:
        return None
    busy = xplane_attrs.Busy(trace, lo, hi)
    return 1e6 * sum(busy.seconds(a, b) for a, b, _ in spans) / \
        sum(w for _, _, w in spans)
