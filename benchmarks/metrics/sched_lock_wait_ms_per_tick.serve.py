"""The scheduler's wait for its own lock, MEAN a decode tick, ms:
`fstpu_serving_lock_wait_seconds_total` over
`fstpu_serving_decode_ticks_total`, deltas over the window. The span's
median (2 us) is blind to a wait that happens once in many ticks."""
from benchmarks.lib import sched

read = sched.lock_wait_ms_per_tick
