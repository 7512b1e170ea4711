"""One `serving/commit` span, the per-lane loop after the fetch (cursor
updates, token append, timeline event, stream sync, release), median."""
from benchmarks.lib import xplane_attrs


def read(obs):
    return xplane_attrs.median_span_ms(obs, "serving/commit")
