"""Device time of one run of the decode program (`jit_decode_fn` on the
trace's module line), median over the traced window's ticks: in the
open loop the tick is the token gap."""
from benchmarks.lib.trace_lines import decode_step_ms as read  # noqa: F401
