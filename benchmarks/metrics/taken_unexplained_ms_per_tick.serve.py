"""What is taken from the scheduler thread over the window (wall - CPU
- declared wait of `fstpu_serving_scheduler_*_seconds_total`) LESS the
handler threads' CPU seconds, over its decode ticks, ms: what the
server's own handlers cannot account for, an upper bound on the clients'
part (they share the benchmark's process) and on preemption. A
difference: negative where the handlers' work fits inside the
scheduler's declared waits (a cell the device paces)."""
from benchmarks.lib import delivery

read = delivery.taken_unexplained_ms_per_tick
