"""Tokens a sparse layer's decode query reads over the tokens cached
when it chose: deltas of `fstpu_sparse_tokens_attended_total` over
`fstpu_sparse_tokens_cached_total` (host arithmetic on the cursors)."""
from benchmarks.lib import obsutil


def read(obs):
    cached = obsutil.counter_delta(obs, "fstpu_sparse_tokens_cached_total")
    attended = obsutil.counter_delta(
        obs, "fstpu_sparse_tokens_attended_total")
    if not cached or attended is None:
        return None
    return 100.0 * attended / cached
