"""The scheduler thread's CPU seconds over the window
(`fstpu_serving_scheduler_cpu_seconds_total`) over its decode ticks, ms:
what the host's work for a tick costs when nothing is taken from the
thread (admissions' CPU in): the floor of the host's cycle."""
from benchmarks.lib import sched

read = sched.cpu_ms_per_tick
