"""The thread's CPU seconds inside `serving/decode/dispatch` over the
spans' wall seconds, counter deltas over the window, %: low = the span
waits (the GIL, a blocking enqueue), high = it works (argument handling,
uploads)."""
from benchmarks.lib import sched


def read(obs):
    return sched.cpu_share(obs, "dispatch")
