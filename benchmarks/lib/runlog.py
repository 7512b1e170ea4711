"""Earlier lines of a run: free text, flushed, never the last line."""

from __future__ import annotations

import time

T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T0:7.2f}s] {msg}", flush=True)
