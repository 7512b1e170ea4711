"""The scheduler thread's cycle: median start-to-start of consecutive
`serving/lock_wait` spans in the traced window, over cycles that hold a
`serving/decode/dispatch` and no `serving/prefill` / `serving/assign`
(pure decode cycles). Beside `decode_step_device_ms.serve` it says who paces
the tick: a cycle longer than the device's tick is the host's."""
from benchmarks.lib import sched

read = sched.cycle_ms
