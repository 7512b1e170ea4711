"""Gaps between consecutive streamed tokens at the client, pooled over
every request, median of those whose later token fell in the window:
the tick as a reader of the stream feels it."""
from benchmarks.lib import reduce as R


def read(obs):
    gaps = R.token_gaps(obs["records"], *obs["window"])
    return 1e3 * R.percentile(gaps, 0.50) if gaps else None
