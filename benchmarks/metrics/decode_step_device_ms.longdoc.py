"""Device time of one run of the decode program (`jit_decode_fn` on the
trace's module line), median over the traced window's ticks: not
span-bound, a tick runs while the host is elsewhere."""
import statistics

from benchmarks.lib import trace_sala


def read(obs):
    runs = trace_sala.module_runs(obs, trace_sala.DECODE)
    return 1e3 * statistics.median(runs) if runs else None
