"""Device time of one run of the engine's assign program (`assign_fn`,
which installs a prefilled request in its lane of the KV pool), from
the trace's module line, median."""
import re
import statistics

from benchmarks.lib import obsutil, xplane_attrs

ASSIGN = re.compile(r"\bjit_assign_fn\b")


def read(obs):
    t = obsutil.traced(obs)
    attrs = xplane_attrs.of(obs)
    if t is None or attrs is None:
        return None
    runs = xplane_attrs.module_seconds(attrs, ASSIGN, t[1], t[2])
    return 1e3 * statistics.median(runs) if runs else None
