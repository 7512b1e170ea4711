"""Device seconds under the two mixers' five scopes (the delta rule's
prefill form and step, the short convolution, the latent read in a tick
and in a prefill window) over the device's busy seconds, in the traced
window: how much of the chip the context's products and the two caches
are. The rest is projections, experts, the head."""
from benchmarks.lib import costs_kimi, trace_lines


def read(obs):
    return trace_lines.share_of_busy(obs, costs_kimi.MIXER_SCOPES)
