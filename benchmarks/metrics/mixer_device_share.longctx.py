"""Device seconds under the attention's four scopes (the indexer's
scores, its top-2,048, the windowed read and the tick's read of the
chosen tokens) over the device's busy seconds, in the traced window:
how much of the chip the selection and what it reads are. The rest is
projections, experts, the head."""
from benchmarks.lib import trace_lines

SCOPES = ("fstpu_index_score", "fstpu_index_topk",
          "fstpu_indexed_prefill_attention",
          "fstpu_indexed_decode_attention")


def read(obs):
    return trace_lines.share_of_busy(obs, SCOPES)
