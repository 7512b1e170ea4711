"""Device time by the program's `jax.named_scope` names.

`lib.xplane.load` keeps an operation's own name and result shape; a
named scope is not in those, nor in anything `ProfileData` hands out
of an event (my chip run, PR 26: 64,731 operations, none carried one).
The profiler keeps the HLO `op_name`, which holds the scope path, once
per kind of event, so this module reads the run's `.xplane.pb` once
more, takes those attributes off the wire (`lib.xplane_meta`) and
keeps, for device 0's operations, every string it finds:

    [[text, start_s, dur_s], ...]

on the trace's own clock, as in `lib.xplane`. `lib.trace_lines` sums the
seconds of the operations whose text holds a scope's name, over the
traced window or inside one program's runs. The harness keeps no path,
so the file is found where it wrote it: `.bench_run/<cell>/trace` (as
`lib.xplane_attrs` does).
"""

from __future__ import annotations

import os

from benchmarks.lib import manifest, obsutil, xplane, xplane_meta


def load(path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device = None
    for plane in data.planes:
        if xplane.DEVICE_PLANE.match(plane.name) and (
                device is None or plane.name < device.name):
            device = plane
    out = []
    if device is None:
        return out
    kinds = xplane_meta.load(path, lambda name: name == device.name).get(
        device.name, {})
    for line in device.lines:
        if line.name != xplane.OPS_LINE:
            continue
        for e in line.events:
            text = " ".join([e.name, kinds.get(e.name, "")] +
                            [v for _, v in e.stats if isinstance(v, str)])
            out.append([text, e.start_ns * 1e-9, e.duration_ns * 1e-9])
    return out


def of(obs: dict):
    """The run's operations with their texts, or None without a trace,
    a device plane or the file. Read once a run, kept on `obs`."""
    if "scope_ops" not in obs:
        obs["scope_ops"] = None
        if obsutil.traced(obs) is not None:
            trace_dir = os.path.join(manifest.ROOT, ".bench_run",
                                     obs["cell"]["name"], "trace")
            try:
                obs["scope_ops"] = load(xplane.find_xplane(trace_dir))
            except FileNotFoundError:
                pass
    return obs["scope_ops"]

