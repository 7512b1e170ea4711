"""One `serving/decode/dispatch` span, the upload of the tick's host
arrays and the enqueue of the decode program, median."""
from benchmarks.lib import xplane_attrs


def read(obs):
    return xplane_attrs.median_span_ms(obs, "serving/decode/dispatch")
