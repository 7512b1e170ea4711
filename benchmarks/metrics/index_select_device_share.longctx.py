"""Device seconds under `fstpu_index_score` and `fstpu_index_topk` (the
indexer's scores over every cached key, and the top-2,048 a query) over
the device's busy seconds, in the traced window: what choosing costs,
windows and ticks together."""
from benchmarks.lib import trace_lines


def read(obs):
    return trace_lines.share_of_busy(
        obs, ("fstpu_index_score", "fstpu_index_topk"))
