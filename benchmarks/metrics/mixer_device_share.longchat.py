"""Device seconds under the two mixers' five scopes (the delta rule's
prefill and decode forms, the short convolution, the full layer's
decode and windowed reads) over the device's busy seconds, in the
traced window: how much of the chip the new mixers are. The rest is
projections, experts, the head."""
from benchmarks.lib import costs_qwen3next, trace_lines


def read(obs):
    return trace_lines.share_of_busy(obs, costs_qwen3next.MIXER_SCOPES)
