"""Commit forwards over block forwards, from deltas of the engine's two
counters: the forwards that reveal nothing and only write a finished
block's K/V (one of `denoise_steps + 1`: a third here), which a program
that folded the commit into the next block's first forward would not
spend (PERF.md section 7)."""
from benchmarks.lib import obsutil


def read(obs):
    forwards = obsutil.counter_delta(
        obs, "fstpu_serving_block_forwards_total")
    commits = obsutil.counter_delta(
        obs, "fstpu_serving_block_commit_forwards_total")
    if not forwards or commits is None:
        return None
    return 100.0 * commits / forwards
