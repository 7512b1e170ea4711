"""Device seconds under the two mixers' six scopes (linear prefill and
decode; the sparse layers' pooling, choice, decode and prefill reads)
over the device's busy seconds, in the traced window: how much of the
chip the new mechanisms are. The rest is projections, MLPs, the head."""
from benchmarks.lib import costs_sala, trace_lines


def read(obs):
    return trace_lines.share_of_busy(obs, costs_sala.MIXER_SCOPES)
