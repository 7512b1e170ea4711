"""Gaps between consecutive streamed tokens at the client, pooled over
every request, 95th percentile of those whose later token fell in the
window."""
from benchmarks.lib import reduce as R


def read(obs):
    gaps = R.token_gaps(obs["records"], *obs["window"])
    return 1e3 * R.percentile(gaps, 0.95) if gaps else None
