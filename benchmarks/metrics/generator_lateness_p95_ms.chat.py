"""How late the load generator sent: sent - due, 95th percentile."""
from benchmarks.lib import obsutil
from benchmarks.lib import reduce as R


def read(obs):
    late = [r["sent"] - r["due"] for r in obsutil.records_due(obs)
            if r["sent"] is not None]
    return 1e3 * R.percentile(late, 0.95) if late else None
