"""Device seconds under attention's four scopes (the window layers'
and the full layers' read, in a tick and in a prefill window) over the
device's busy seconds, in the traced window: how much of the chip the
context's products are. The rest is projections, experts, the head."""
from benchmarks.lib import costs_trinity, trace_lines


def read(obs):
    return trace_lines.share_of_busy(obs, costs_trinity.MIXER_SCOPES)
