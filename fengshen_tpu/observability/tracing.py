"""Trace spans: named timed sections, profiler-integrated when possible.

``span("serving/decode")`` wraps a host-side section. Inside it:

- when ``jax.profiler`` is importable, the section is annotated with
  ``TraceAnnotation`` so it shows up named on the TensorBoard trace
  the Trainer's ``--profile_steps`` captures;
- always, the wall time is recorded into the
  ``fstpu_span_seconds{span=...}`` histogram of the target registry —
  so `/metrics` carries p50/p95 section timings even where no profiler
  run is active.

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
from contextlib import contextmanager
from typing import Optional

from fengshen_tpu.observability.registry import (MetricsRegistry,
                                                 get_registry)

SPAN_METRIC = "fstpu_span_seconds"

#: sentinel: profiler integration not yet resolved. Tests (and callers
#: that want timing-only spans) may set this to None to force the
#: fallback; set it back to _UNRESOLVED to re-probe.
_UNRESOLVED = object()
_TRACE_ANNOTATION = _UNRESOLVED

_local = threading.local()


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


@contextmanager
def span(name: str, registry: Optional[MetricsRegistry] = None, **attrs):
    """Time a section; annotate the profiler trace when available.
    `attrs` reach the trace event only, never the histogram's label."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    stack.append(name)
    label = "/".join(stack)
    cls = _trace_annotation_cls()
    annotation = None
    if cls is not None:
        try:
            annotation = cls(label, **attrs)
            annotation.__enter__()
        except Exception:  # noqa: BLE001 — profiler refused: time anyway
            annotation = None
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        if annotation is not None:
            try:
                annotation.__exit__(None, None, None)
            except Exception:  # noqa: BLE001 — never mask the body's error
                pass
        stack.pop()
        reg = registry if registry is not None else get_registry()
        reg.histogram(
            SPAN_METRIC,
            "wall seconds spent inside span(), labelled by the nested "
            "span path", labelnames=("span",),
        ).labels(label).observe(dt)
