"""The device a run stands on: the gate, the peaks and the memory peak."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

#: tests lift the gate here (never an option of the harness)
REQUIRED_PLATFORM = "tpu"


def gate(chips: int) -> dict:
    """Exit non-zero, printing no result, unless jax's default backend
    is the accelerator with at least `chips` devices."""
    import jax
    devices = jax.devices()
    first = devices[0]
    if first.platform != REQUIRED_PLATFORM:
        sys.exit(f"benchmarks: the default backend is {first.platform!r}, "
                 f"not {REQUIRED_PLATFORM!r}; a cell runs on the chip or "
                 "not at all")
    if len(devices) < chips:
        sys.exit(f"benchmarks: the cell asks for {chips} chips, jax sees "
                 f"{len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": chips}


def peaks(device_kind: str) -> dict:
    """Published per-chip peaks; a device not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks on record for {device_kind!r}")
    return table[device_kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip, as the runtime counts it
    (it misses program scratch: PERF.md, open questions)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])
