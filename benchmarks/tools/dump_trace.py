"""Write a run's reduced trace (what `lib.xplane.load` returns) as JSON,
for a look by hand or for a test fixture.

    python3 benchmarks/tools/dump_trace.py <trace dir> <out.json> [seconds]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.lib import xplane  # noqa: E402


def main(trace_dir: str, out: str, seconds: str = "") -> None:
    trace = xplane.load(xplane.find_xplane(trace_dir))
    lo, hi = xplane.window(trace)
    if seconds:
        hi = lo + float(seconds)
        keep = lambda ev: [e for e in ev if lo <= e[1] and e[1] + e[2] <= hi]
        trace["devices"] = {k: keep(v) for k, v in trace["devices"].items()}
        trace["host"] = [e for e in trace["host"]
                         if e[0] == xplane.WINDOW_SPAN or
                         (lo <= e[1] and e[1] + e[2] <= hi)]
    names = xplane.self_seconds(xplane.first_device(trace), lo, hi)
    trace["self_seconds"] = sorted(names.items(), key=lambda kv: -kv[1])
    with open(out, "w") as f:
        json.dump(trace, f)


if __name__ == "__main__":
    main(*sys.argv[1:])
