"""From the instant a request was DUE to its first token at the client,
90th percentile over the requests due in the window; a failed or
unanswered request is infinite."""
from benchmarks.lib import obsutil
from benchmarks.lib import reduce as R


def read(obs):
    due = obsutil.records_due(obs)
    return 1e3 * R.percentile(R.ttfts(due), 0.90) if due else None
