"""Device time of the window program (`jit_window_fn` on the trace's
module line) over the tokens its runs were wide: what a padded prompt
token costs the device, from the traced window's own window runs."""
from benchmarks.lib import trace_sala


def read(obs):
    runs = trace_sala.module_runs(obs, trace_sala.WINDOW)
    if not runs:
        return None
    width = max(obs["mix"]["engine_args"]["buckets"])
    return 1e6 * sum(runs) / (len(runs) * width)
