"""From the profiler's trace to device busy and idle time, host spans,
time by operation and idle gaps by what the host was doing.

`load` turns an `.xplane.pb` into plain lists (seconds on the trace's
own clock); everything else works on those lists, so the reduction can
be checked on a small recorded trace kept as JSON.

    {"devices": {plane name: [[op name, start_s, dur_s], ...]},
     "host": [[span name, start_s, dur_s], ...]}
"""

from __future__ import annotations

import glob
import os
import re

#: the device line that holds one event per executed XLA operation
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the marker the harness puts around the traced window
WINDOW_SPAN = "bench/traced"
#: gaps under this are launch overhead between operations, not a wait
#: on the host; they are summed under one name
SHORT_GAP = 20e-6
SHORT_NAME = "between operations (<20us each)"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute",
    re.I)


_RESULT = re.compile(r" = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO text; keep
    its own name and the shape of its (first) result."""
    head = name.split(" = ", 1)[0].lstrip("%")
    m = _RESULT.search(name)
    return (head + " " + m.group(1)) if m else head[:120]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, is_span=lambda name: "/" in name and
         not name.startswith("$")) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        out["lines"][plane.name] = [line.name for line in plane.lines]
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["devices"][plane.name] = [
                        [short_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                    for e in line.events if is_span(e.name))
    return out


def window(trace: dict) -> tuple:
    """(lo, hi) of the marker span the harness wrapped the window in."""
    marks = [(s, s + d) for name, s, d in trace["host"]
             if name == WINDOW_SPAN]
    if not marks:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    return marks[-1]


def merged(events, lo: float, hi: float) -> list:
    """Union of the events' intervals, clipped to [lo, hi]."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s < hi and s + d > lo)
    out = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_seconds(trace: dict, lo: float, hi: float) -> float:
    """Seconds an operation ran, averaged over the device planes."""
    per = [sum(b - a for a, b in merged(ev, lo, hi))
           for ev in trace["devices"].values()]
    return sum(per) / len(per) if per else 0.0


def first_device(trace: dict) -> list:
    return trace["devices"][sorted(trace["devices"])[0]] \
        if trace["devices"] else []


def spans(trace: dict, name: str, lo: float, hi: float) -> list:
    return [(s, s + d) for n, s, d in trace["host"]
            if n == name and s >= lo and s + d <= hi]


def op_seconds(trace: dict, pattern, lo: float, hi: float) -> float:
    """Device 0 seconds in operations whose name matches `pattern`."""
    return sum(min(s + d, hi) - max(s, lo)
               for n, s, d in first_device(trace)
               if pattern.search(n) and s < hi and s + d > lo)


def self_seconds(events, lo: float, hi: float) -> dict:
    """Seconds by operation name with nested operations taken out of
    their parents (a `while` holds its body's operations on the same
    line), clipped to [lo, hi]."""
    total: dict = {}
    stack: list = []        # [name, end, seconds still its own]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        close(a)
        if stack:
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    close(float("inf"))
    return total


def top_ops(trace: dict, lo: float, hi: float, n: int = 10) -> list:
    total = self_seconds(first_device(trace), lo, hi)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: dict, lo: float, hi: float, n: int = 10) -> list:
    """Idle seconds of device 0 by the host span that covered most of
    each gap ("no span" where none did)."""
    busy = merged(first_device(trace), lo, hi)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    host = [(nm, s, s + d) for nm, s, d in trace["host"]
            if nm != WINDOW_SPAN]
    total: dict = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        if b - a < SHORT_GAP:
            total[SHORT_NAME] = total.get(SHORT_NAME, 0.0) + (b - a)
            continue
        # the shortest span that covers most of the gap: the most
        # specific thing the host was doing (a client thread's long
        # wait for the engine's lock covers everything and says least)
        best, length = "no span", float("inf")
        for nm, s, e in host:
            if min(e, b) - max(s, a) > 0.5 * (b - a) and e - s < length:
                best, length = nm, e - s
        total[best] = total.get(best, 0.0) + (b - a)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]
