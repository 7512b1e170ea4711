"""What tracing costs and what it covers, for one cell.

    python3 benchmarks/tools/trace_cost.py --workload <name> --seed <n> \
        --seconds <s>
    python3 benchmarks/tools/trace_cost.py --span_cost

The first form is a `--trace 1` run of the cell as committed that also
prints the cell's end-to-end metrics from the SAME run (the harness
prints per-layer metrics only when it traces): against a `--trace 0`
run of the same seed, that is what the profiler session costs. It adds
the share of the traced window that the spans of the hot thread cover
(the scheduler's `serving/*` without the submitters' `serving/admit*`,
or the Trainer's `train/*`), the longest stretches they leave
uncovered with the span on either side, every idle gap by span, every span of the program by name
(count, median ms, total s), and each program of the trace's module
line with its runs and median device time.

The second form times one `span()` entry and exit on this host with no
profiler session and inside one.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

HOT = ("serving/", "train/")
NOT_HOT = ("serving/admit",)


def coverage(trace: dict, lo: float, hi: float) -> dict:
    from benchmarks.lib import xplane
    hot = [e for e in trace["host"] if e[0].startswith(HOT)
           and not e[0].startswith(NOT_HOT)]
    covered = xplane.merged(hot, lo, hi)
    edges = [lo] + [t for ab in covered for t in ab] + [hi]
    holes = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])),
                   reverse=True)

    def beside(t: float, ends: bool) -> str:
        """The span that ends (or starts) at the hole's edge `t`."""
        return next((n for n, s, d in hot
                     if abs((s + d if ends else s) - t) < 1e-9), "-")
    return {"share": sum(b - a for a, b in covered) / (hi - lo),
            "uncovered_s": sum(h for h, _, _ in holes),
            # [ms, the span before the hole, the span after it]
            "largest_uncovered": [[1e3 * h, beside(a, True), beside(b, False)]
                                  for h, a, b in holes[:12]],
            "spans": sorted({e[0] for e in hot})}


def span_cost(n: int = 20000) -> dict:
    import jax

    from fengshen_tpu.observability import MetricsRegistry, span
    registry = MetricsRegistry()

    def per_span_us() -> float:
        t = time.perf_counter()
        for _ in range(n):
            with span("cost/probe", registry=registry, lanes=32):
                pass
        return 1e6 * (time.perf_counter() - t) / n

    per_span_us()
    off = [per_span_us() for _ in range(5)]
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            on = [per_span_us() for _ in range(5)]
        finally:
            jax.profiler.stop_trace()
    return {"span_us_no_session": statistics.median(off),
            "span_us_in_session": statistics.median(on),
            "platform": jax.devices()[0].platform}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--span_cost", action="store_true")
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int)
    parser.add_argument("--seconds", type=float)
    args = parser.parse_args()
    if args.span_cost:
        print(json.dumps(span_cost()), flush=True)
        return 0
    from benchmarks import run
    from benchmarks.lib import check, manifest, traffic, xplane, xplane_attrs
    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    obs: dict = {}
    result = run.execute(man, cell, manifest.config_of(man, cell),
                         traffic.load_mix(cell["traffic"]),
                         check.limits_of(cell["name"]), args.seed,
                         args.seconds, True, obs_out=obs)
    out = {"workload": args.workload, "seed": args.seed, "result": result,
           "end_to_end_while_traced": {
               m["name"]: manifest.reader(m["name"])(obs)
               for m in manifest.metrics_of(man, cell["name"])[0]}}
    if obs.get("trace") is not None:
        lo, hi = obs["trace_window"]
        out["coverage"] = coverage(obs["trace"], lo, hi)
        out["idle_gaps"] = xplane.idle_gaps(obs["trace"], lo, hi, n=40)
        by_span: dict = {}
        for name, s, d in obs["trace"]["host"]:
            if name.startswith(HOT) and lo <= s and s + d <= hi:
                by_span.setdefault(name, []).append(d)
        out["spans"] = {k: [len(v), 1e3 * statistics.median(v), sum(v)]
                        for k, v in sorted(by_span.items())}
        attrs = xplane_attrs.of(obs)
        by_module: dict = {}
        for name, s, d in (attrs or {"modules": []})["modules"]:
            if lo <= s and s + d <= hi:
                by_module.setdefault(name, []).append(d)
        out["modules"] = {k: [len(v), 1e3 * statistics.median(v)]
                          for k, v in sorted(by_module.items())}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
