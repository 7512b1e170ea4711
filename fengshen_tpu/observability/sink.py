"""JsonlSink: the one structured-event writer.

Subsumes the event dialects that grew per-subsystem — the Trainer's
``metrics.jsonl`` step/val/rewind entries, the resilience loader's
``loader_retry``/``loader_skip_batch`` events, the serving engine's
``serving_admit``/``serving_finish`` events — behind a single callable
``sink(entry: dict)``. Event NAMES are unchanged (compatibility layer:
anything already parsing metrics.jsonl keeps working); what unifies is the writer: one
process-gating rule, one echo format, one logger bridge.

A sink writes to a jsonl ``path``, a ``stream``, or both; ``echo`` mirrors the Trainer's human-readable console line;
``logger`` bridges numeric fields to a Lightning-style
``log_metrics``. Multihost gating: only ``process_index == 0`` writes
(``only_process_zero=False`` opts out — bench children are already
single-process).

``max_bytes`` caps the jsonl file for long-running serve processes:
when the next line would push past the cap, the file rotates
``path -> path.1 -> ... -> path.<backups>`` (oldest dropped). Rotation
only renames files — the event names and the line format stay
byte-identical, so anything tailing the jsonl keeps parsing. A sink is
shared by concurrent writers (the serving engine's scheduler thread,
HTTP handler threads, the fleet router's poll sweep), so the
rotate-then-append step runs under a lock: without it two threads
racing a rotation boundary can interleave half-written lines or lose a
freshly rotated file's first entries.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Optional, TextIO


def _process_index() -> int:
    try:
        import jax
        return int(jax.process_index())
    except Exception:  # noqa: BLE001 — no jax = single process
        return 0


class JsonlSink:
    """Callable structured-event sink: ``sink({"event": ..., ...})``."""

    def __init__(self, path: Optional[str] = None,
                 stream: Optional[TextIO] = None,
                 echo: bool = False,
                 echo_prefix: str = "[fengshen-tpu] ",
                 logger: Optional[Any] = None,
                 only_process_zero: bool = True,
                 max_bytes: Optional[int] = None,
                 backups: int = 1):
        self.path = path
        self.stream = stream
        self.echo = echo
        self.echo_prefix = echo_prefix
        self.logger = logger
        self.only_process_zero = only_process_zero
        self.max_bytes = max_bytes
        self.backups = max(int(backups), 1)
        # one writer at a time: rotation is a multi-step rename chain
        # and concurrent callers must not interleave inside it
        self._lock = threading.Lock()

    def _maybe_rotate(self, incoming: int) -> None:
        """Size-based rotation (opt-in via ``max_bytes``): shift the
        backup chain so the active file always has room for the next
        line; renames only, content untouched."""
        try:
            size = os.path.getsize(self.path)
        except OSError:
            return      # no file yet — nothing to rotate
        if size + incoming <= self.max_bytes:
            return
        for i in range(self.backups, 0, -1):
            src = self.path if i == 1 else f"{self.path}.{i - 1}"
            if os.path.exists(src):
                os.replace(src, f"{self.path}.{i}")

    @staticmethod
    def format_echo(entry: dict) -> str:
        """The Trainer's console line format (floats at .4g)."""
        return " ".join(
            f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in entry.items())

    def __call__(self, entry: dict) -> None:
        if self.only_process_zero and _process_index() != 0:
            return
        line = json.dumps(entry)
        if self.path is not None:
            with self._lock:
                parent = os.path.dirname(self.path)
                if parent:
                    os.makedirs(parent, exist_ok=True)
                if self.max_bytes is not None:
                    self._maybe_rotate(len(line) + 1)
                with open(self.path, "a") as f:
                    f.write(line + "\n")
        if self.stream is not None:
            # same lock as the file path: shared streams get the same
            # no-interleaved-lines guarantee the rotation test pins
            with self._lock:
                self.stream.write(line + "\n")
                self.stream.flush()
        if self.echo:
            print(f"{self.echo_prefix}{self.format_echo(entry)}",
                  flush=True)
        if self.logger is not None and hasattr(self.logger,
                                               "log_metrics"):
            self.logger.log_metrics(
                {k: v for k, v in entry.items()
                 if isinstance(v, (int, float))},
                step=entry.get("step"))
