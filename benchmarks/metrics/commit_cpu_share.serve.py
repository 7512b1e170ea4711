"""As `decode_dispatch_cpu_share.serve`, for `serving/commit`: the
per-lane loop after the fetch (token append, timeline event, stream
sync, release). It declares no wait, so what its wall holds beyond its
CPU was taken from the thread."""
from benchmarks.lib import sched


def read(obs):
    return sched.cpu_share(obs, "commit")
