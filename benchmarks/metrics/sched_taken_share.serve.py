"""Of the scheduler thread's wall seconds over the window, what was
neither its CPU nor a wait it declared (the lock, the device's tokens,
the idle condition), %: (wall - CPU - wait) / wall of the
`fstpu_serving_scheduler_*_seconds_total` counters. The GIL, preemption,
or a call that blocks where none was declared; a lower bound, since a
GIL loss inside a declared wait counts as declared."""
from benchmarks.lib import sched

read = sched.taken_share
