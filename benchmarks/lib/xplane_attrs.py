"""What `lib.xplane.load` drops from a run's `.xplane.pb`, for the
readers that need it: the attributes the program's `span(...)` calls
carry (request id, bucket, lanes), and the device's module line (one
event per run of a jitted program, named by jit from the function); and
two helpers the new readers share: the median duration of a span, and an
index over the device's busy time, so a reader can ask for the busy
seconds of many short intervals without walking every operation again.

    {"spans": [[span name, start_s, dur_s, {attribute: value}], ...],
     "modules": [[module name, start_s, dur_s], ...]}

Seconds are on the trace's own clock, as in `lib.xplane`. The harness
loads the trace itself and keeps no path, so the file is found where
the harness wrote it: `.bench_run/<cell>/trace`.
"""

from __future__ import annotations

import bisect
import os
import re
import statistics

from benchmarks.lib import manifest, xplane

#: the device line that holds one event per executed program
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"spans": [], "modules": []}
    device = None
    for plane in data.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            # device 0, as `xplane.first_device` takes it
            if device is None or plane.name < device.name:
                device = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if "/" not in e.name or e.name.startswith("$"):
                        continue
                    attrs = {k: v for k, v in e.stats
                             if isinstance(v, (int, float, str))}
                    if attrs:
                        out["spans"].append(
                            [e.name, e.start_ns * 1e-9,
                             e.duration_ns * 1e-9, attrs])
    if device is not None:
        for line in device.lines:
            if line.name == MODULES_LINE:
                out["modules"] = [
                    [_MODULE_ID.sub("", e.name), e.start_ns * 1e-9,
                     e.duration_ns * 1e-9] for e in line.events]
    return out


def of(obs: dict):
    """The attributes and module line of this run's trace, or None
    where the run has no trace, no device plane or no file. Read once
    a run and kept on `obs` for the next reader."""
    if "trace_attrs" not in obs:
        obs["trace_attrs"] = None
        if obs.get("trace") is not None and obs["trace"]["devices"]:
            trace_dir = os.path.join(manifest.ROOT, ".bench_run",
                                     obs["cell"]["name"], "trace")
            try:
                obs["trace_attrs"] = load(xplane.find_xplane(trace_dir))
            except FileNotFoundError:
                pass
    return obs["trace_attrs"]


def median_span_ms(obs: dict, name: str):
    """Median duration, in ms, of the host spans `name` inside the
    traced window; None without a trace, a device plane or the span."""
    if obs.get("trace") is None or not obs["trace"]["devices"]:
        return None
    spans = xplane.spans(obs["trace"], name, *obs["trace_window"])
    return 1e3 * statistics.median(b - a for a, b in spans) if spans \
        else None


def spans_with(attrs: dict, name: str, lo: float, hi: float) -> list:
    """[(start, end, attributes)] of the spans `name` inside [lo, hi]."""
    return [(s, s + d, a) for n, s, d, a in attrs["spans"]
            if n == name and s >= lo and s + d <= hi]


def module_seconds(attrs: dict, pattern, lo: float, hi: float) -> list:
    """Seconds of each run, inside [lo, hi], of the programs whose
    module name matches `pattern`."""
    return [d for n, s, d in attrs["modules"]
            if pattern.search(n) and s >= lo and s + d <= hi]


class Busy:
    """Device 0's busy time inside [lo, hi] as merged intervals with
    running sums: `seconds(a, b)` and `last_end(a, b)` cost a bisection
    each."""

    def __init__(self, trace: dict, lo: float, hi: float):
        merged = xplane.merged(xplane.first_device(trace), lo, hi)
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.before = [0.0]
        for a, b in merged:
            self.before.append(self.before[-1] + (b - a))

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(t, self.ends[i - 1]) - \
            self.starts[i - 1]

    def seconds(self, a: float, b: float) -> float:
        """Busy seconds inside [a, b]."""
        return self._upto(b) - self._upto(a)

    def last_end(self, a: float, b: float):
        """The end (clipped to b) of the last busy interval that
        touches [a, b], or None where the device was idle throughout."""
        i = bisect.bisect_left(self.starts, b) - 1
        if i < 0 or self.ends[i] <= a:
            return None
        return min(self.ends[i], b)


def children(trace: dict, parent: str, child: str, lo: float,
             hi: float) -> list:
    """[((parent start, end), (child start, end))] for each span
    `parent` inside [lo, hi] that holds a span `child`."""
    kids = sorted(xplane.spans(trace, child, lo, hi))
    starts = [a for a, _ in kids]
    out = []
    for a, b in sorted(xplane.spans(trace, parent, lo, hi)):
        i = bisect.bisect_left(starts, a)
        # a nanosecond's grace: seconds are sums of two rounded floats
        if i < len(kids) and kids[i][1] <= b + 1e-9:
            out.append(((a, b), kids[i]))
    return out
