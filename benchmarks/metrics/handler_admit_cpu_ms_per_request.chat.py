"""A handler thread's CPU seconds from a POST's entry to the return of
`submit()` (`fstpu_serving_handler_admit_cpu_seconds_total`: body read,
JSON, encode, the submit) over the requests admitted in the window, ms:
the prompts arrive as text."""
from benchmarks.lib import delivery

read = delivery.admit_cpu_ms_per_request
