"""Share of the traced window's token gaps that contain a
`serving/prefill` span."""
from benchmarks.lib import obsutil, xplane


def read(obs):
    t = obsutil.traced(obs)
    if t is None:
        return None
    trace, lo, hi = t
    off = obs["pc_minus_trace"]
    starts = sorted(a for a, _ in xplane.spans(trace, "serving/prefill",
                                               lo, hi))
    gaps = hit = 0
    for r in obs["records"]:
        times = [x - off for x in r["token_times"]]
        for a, b in zip(times, times[1:]):
            if lo <= a and b <= hi:
                gaps += 1
                hit += any(a <= s < b for s in starts)
    return 100.0 * hit / gaps if gaps else None
