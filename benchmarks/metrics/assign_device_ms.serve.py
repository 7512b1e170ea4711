"""Device time of one run of the engine's assign program (`assign_fn`,
which installs a prefilled request in its lane of the KV pool and of
the per-lane state), from the trace's module line, median."""
from benchmarks.lib.trace_lines import assign_ms as read  # noqa: F401
