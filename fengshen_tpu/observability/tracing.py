"""Trace spans: named timed sections, profiler-integrated when possible.

``span("serving/decode")`` wraps a host-side section. Inside it:

- when ``jax.profiler`` is importable, the section is annotated with
  ``TraceAnnotation`` so it shows up named on the TensorBoard trace
  the Trainer's ``--profile_steps`` captures;
- always, the wall time is recorded into the
  ``fstpu_span_seconds{span=...}`` histogram of the target registry —
  so `/metrics` carries p50/p95 section timings even where no profiler
  run is active — and the calling thread's CPU time into the
  ``fstpu_span_cpu_seconds_total{span=...}`` counter beside it: a
  section whose wall time is far above its CPU time was waiting (for
  the device, a lock, the GIL), one where they are close was working.

``with span(...) as s:`` yields the span itself; `s.seconds` and
`s.cpu_seconds` are filled when the section ends, also on an
exception.

Spans nest: the recorded label is the "/"-joined stack ("fit/step"
inside ``span("fit")`` + ``span("step")``), kept per-thread so the
serving engine thread and the main thread never interleave stacks.

Keyword attributes (``span("serving/prefill", bucket=512)``) go to the
``TraceAnnotation`` only: the profiler stores them as the event's
stats beside the plain name, and the histogram's label never carries
them (a request id there would be one time series per request).

The profiler hook degrades to timing-only when jax (or jax.profiler) is
missing or broken — the registry side is pure stdlib.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from fengshen_tpu.observability.registry import (MetricsRegistry,
                                                 get_registry)

SPAN_METRIC = "fstpu_span_seconds"
SPAN_CPU_METRIC = "fstpu_span_cpu_seconds_total"

#: sentinel: profiler integration not yet resolved. Tests (and callers
#: that want timing-only spans) may set this to None to force the
#: fallback; set it back to _UNRESOLVED to re-probe.
_UNRESOLVED = object()
_TRACE_ANNOTATION = _UNRESOLVED

_local = threading.local()

#: `time.thread_time()` is a system call (0.4 us on a plain Linux host,
#: 5.8 us under gVisor, where it also advances in steps of 10 ms),
#: `time.perf_counter()` is not. A reading within this many seconds of
#: the thread's last is extrapolated from it: the thread cannot have
#: been off the CPU for longer than that unnoticed, and nested or
#: adjacent spans then share one call.
_CPU_REUSE_S = 100e-6


def _trace_annotation_cls():
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is _UNRESOLVED:
        try:
            from jax.profiler import TraceAnnotation
            _TRACE_ANNOTATION = TraceAnnotation
        except Exception:  # noqa: BLE001 — no jax: timing-only spans
            _TRACE_ANNOTATION = None
    return _TRACE_ANNOTATION


def current_span_stack() -> tuple:
    """The calling thread's open spans, outermost first."""
    return tuple(getattr(_local, "stack", ()))


def thread_times() -> tuple:
    """(`time.perf_counter()`, the calling thread's CPU seconds), the
    second within `_CPU_REUSE_S` of the truth and never behind an
    earlier reading of the same thread."""
    now = time.perf_counter()
    # [wall and CPU seconds at the last call of the clock, last reading]
    anchor = getattr(_local, "cpu_anchor", None)
    if anchor is None:
        cpu = time.thread_time()
    elif now - anchor[0] < _CPU_REUSE_S:
        anchor[2] = cpu = anchor[1] + (now - anchor[0])
        return now, cpu
    else:
        cpu = max(time.thread_time(), anchor[2])
    _local.cpu_anchor = [now, cpu, cpu]
    return now, cpu


def _children(registry: MetricsRegistry, label: str) -> tuple:
    """(histogram child, CPU counter child) of `label` on `registry`,
    resolved once per registry and label: an exit then costs two
    observations and no lookup by name."""
    pair = registry.span_children.get(label)
    if pair is None:
        pair = registry.span_children[label] = (
            registry.histogram(
                SPAN_METRIC,
                "wall seconds spent inside span(), labelled by the "
                "nested span path", labelnames=("span",)).labels(label),
            registry.counter(
                SPAN_CPU_METRIC,
                "the calling thread's CPU seconds inside span(), same "
                "labels", labelnames=("span",)).labels(label))
    return pair


class span:
    """Time a section; annotate the profiler trace when available.
    `attrs` reach the trace event only, never the histogram's label."""

    __slots__ = ("seconds", "cpu_seconds", "_name", "_registry", "_attrs",
                 "_label", "_annotation", "_t0", "_c0")

    def __init__(self, name: str,
                 registry: Optional[MetricsRegistry] = None, **attrs):
        self._name = name
        self._registry = registry
        self._attrs = attrs
        self.seconds = self.cpu_seconds = 0.0

    def __enter__(self) -> "span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self._name)
        self._label = label = "/".join(stack)
        cls = _trace_annotation_cls()
        self._annotation = None
        if cls is not None:
            try:
                annotation = cls(label, **self._attrs)
                annotation.__enter__()
                self._annotation = annotation
            except Exception:  # noqa: BLE001 — profiler refused: time anyway
                pass
        self._t0, self._c0 = thread_times()
        return self

    def __exit__(self, *exc) -> bool:
        now, cpu_now = thread_times()
        self.seconds = now - self._t0
        self.cpu_seconds = cpu_now - self._c0
        if self._annotation is not None:
            try:
                self._annotation.__exit__(None, None, None)
            except Exception:  # noqa: BLE001 — never mask the body's error
                pass
        _local.stack.pop()
        histogram, cpu = _children(
            self._registry if self._registry is not None
            else get_registry(), self._label)
        histogram.observe(self.seconds)
        cpu.inc(self.cpu_seconds)
        return False
