"""One point of the rate sweep that finds an open-loop mix's knee: the
cell as committed, at another arrival rate, with what shows whether the
engine keeps up.

    python3 benchmarks/tools/sweep.py --workload <name> --seed <n> \
        --seconds <s> --rate <requests/s>

A rate is sustained when the backlog (requests sent and not yet
finished) does not grow through the window and lanes are left over.
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmarks import run
    from benchmarks.lib import check, manifest, traffic
    from benchmarks.lib import reduce as R
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rate", type=float, required=True)
    args = parser.parse_args()
    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    mix = traffic.load_mix(cell["traffic"])
    mix["arrivals"]["rate_per_s"] = args.rate
    obs: dict = {}
    result = run.execute(man, cell, manifest.config_of(man, cell), mix,
                         check.limits_of(cell["name"]), args.seed,
                         args.seconds, False, obs_out=obs)
    lo, hi = obs["window"]
    records = obs["records"]
    due = R.due_in_window(records, lo, hi)

    def backlog(t):
        return sum(1 for r in records if r["sent"] is not None
                   and r["sent"] <= t and (r["done"] is None or r["done"] > t))
    thirds = [backlog(lo + f * (hi - lo)) for f in (0.0, 1 / 3, 2 / 3, 1.0)]
    polls = [p for p in obs["polls"] if lo <= p[0] <= hi]
    gaps = R.token_gaps(records, lo, hi)
    ttft = [t for t in R.ttfts(due)]
    print(json.dumps({
        "rate_offered": args.rate, "correct": result["correct"],
        "due": len(due), "seconds": hi - lo,
        "finished_per_s": sum(1 for r in records if r["done"] is not None
                              and lo <= r["done"] < hi) / (hi - lo),
        "backlog_at_0_1/3_2/3_1": thirds,
        "lanes_mean": statistics.mean(p[3] for p in polls),
        "lanes_max": max(p[3] for p in polls),
        "queue_max": max(p[4] for p in polls),
        "ttft_p50_ms": 1e3 * R.percentile(ttft, 0.5),
        "ttft_p90_ms": 1e3 * R.percentile(ttft, 0.9),
        "gap_p50_ms": 1e3 * R.percentile(gaps, 0.5),
        "gap_p95_ms": 1e3 * R.percentile(gaps, 0.95)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
