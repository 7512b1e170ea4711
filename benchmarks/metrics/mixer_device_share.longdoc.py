"""Device seconds under the two mixers' six scopes (linear prefill and
decode; the sparse layers' pooling, choice, decode and prefill reads)
over the device's busy seconds, in the traced window: how much of the
chip the new mechanisms are. The rest is projections, MLPs, the head."""
from benchmarks.lib import trace_sala


def read(obs):
    return trace_sala.share_of_busy(obs, trace_sala.SCOPES)
