"""Device seconds under `fstpu_sparse_pool` and `fstpu_sparse_select`
(keeping the pooled keys; scores over them, the max-pool to blocks, the
top-k) over the device's busy seconds, in the traced window."""
from benchmarks.lib import trace_lines


def read(obs):
    return trace_lines.share_of_busy(
        obs, ("fstpu_sparse_pool", "fstpu_sparse_select"))
