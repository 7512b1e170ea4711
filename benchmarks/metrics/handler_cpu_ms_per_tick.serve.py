"""The handler threads' CPU seconds over the window
(`fstpu_serving_handler_admit_cpu_seconds_total` +
`..._handler_stream_cpu_seconds_total`) over its decode ticks, ms: what
admission and delivery cost the process a tick, beside
`sched_cpu_ms_per_tick`. Sums over a window; one stream's reading means
nothing where the CPU clock steps by 10 ms."""
from benchmarks.lib import delivery

read = delivery.handler_cpu_ms_per_tick
