"""A short profiler trace around part of the window, with one marker
span so the reduction can place the window on the trace's clock."""

from __future__ import annotations

import os
import shutil
import time

from benchmarks.lib import xplane


class Traced:
    """`with Traced(dir): ...` traces the body. `pc_minus_trace` is the
    offset that takes a trace-clock second to the harness's
    `time.perf_counter` clock."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.pc_minus_trace = None
        self._pc_enter = None

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # spans, not every Python call
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._marker = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
        self._pc_enter = time.perf_counter()
        self._marker.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._marker.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return False

    def load(self) -> tuple:
        """(trace, (lo, hi) of the marker on the trace clock)."""
        trace = xplane.load(xplane.find_xplane(self.dir))
        lo, hi = xplane.window(trace)
        self.pc_minus_trace = self._pc_enter - lo
        return trace, (lo, hi)
