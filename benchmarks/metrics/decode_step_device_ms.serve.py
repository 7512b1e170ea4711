"""Device time of one run of the decode program (`jit_decode_fn` on the
trace's module line), median over the traced window's ticks: not
span-bound, a tick runs while the host is elsewhere. Beside
`sched_cycle_ms.serve` it says who paces the tick."""
from benchmarks.lib.trace_lines import decode_step_ms as read  # noqa: F401
