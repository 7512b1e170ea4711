"""The whole process's CPU seconds over the window
(`fstpu_serving_process_cpu_seconds_total`, `time.process_time()`) over
its decode ticks, ms: the scheduler's CPU, the handlers' and the rest
(the runtime's threads; the benchmark's clients)."""
from benchmarks.lib import delivery

read = delivery.process_cpu_ms_per_tick
