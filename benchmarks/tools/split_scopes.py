"""A prefill window and a decode tick by scope, from the trace a
`--trace 1` run of a cell left in `.bench_run/<cell>/trace`.

    python3 benchmarks/tools/split_scopes.py <cell> [--program REGEX ...]
        [--scope NAME ...] [--top N]

For each program of the trace's module line (by default the engine's
four: `jit_window_fn`, `jit_prefill_fn`, `jit_decode_fn`,
`jit_assign_fn`) its runs inside the traced window, their median and
mean; then, inside those runs, each scope's device ms a run and share of
the program (`lib/trace_lines.scope_seconds_in`: the union of the
intervals of the operations whose text holds the scope's name), and
BESIDE each total the scope's longest operations — a scope's seconds
are not a kernel's: "the experts, 35.7 ms a window" was 22 ms of grouped
matmuls and 13.7 ms of sort, gather and sum (PERF.md, PR 38). Last, the
longest operations under no scope. Without `--scope` the scopes are the
`fstpu_*` names the trace itself holds, and the grouped matmuls by
their own name (`%ragged-dot`: XLA:TPU drops their `op_name`).

It needs no chip (it reads a file) and never the program: run it after
a traced run, in the same checkout.
"""

import argparse
import bisect
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PROGRAMS = ("jit_window_fn", "jit_prefill_fn", "jit_decode_fn",
            "jit_assign_fn")
SCOPE_NAME = re.compile(r"fstpu_[a-z0-9_]*[a-z0-9]")
RAGGED = "%ragged-dot"
#: operations that only hold others on the same line
HOLDERS = ("while", "conditional", "call")


def in_runs(events: list, runs: list) -> list:
    """The events ([text, start, dur]) that lie inside one of `runs`
    ([(start, end)], sorted)."""
    starts = [a for a, _ in runs]
    out = []
    for e in events:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] + e[2] <= runs[i][1]:
            out.append(e)
    return out


def longest(events: list, top: int) -> list:
    """[(short name, seconds, calls)] of `events`, the longest first;
    the operations that only hold others on their line left out."""
    from benchmarks.lib import xplane
    by: dict = {}
    for text, _, d in events:
        name = xplane.short_name(text)
        if not name.startswith(HOLDERS):
            entry = by.setdefault(name, [0.0, 0])
            entry[0] += d
            entry[1] += 1
    return sorted(((n, t, c) for n, (t, c) in by.items()),
                  key=lambda e: -e[1])[:top]


def split(obs: dict, program: str, asked, top: int, out) -> None:
    from benchmarks.lib import scopes, trace_lines
    pattern = re.compile(r"\b" + program + r"\b")
    runs = sorted(trace_lines.module_intervals(obs, pattern))
    if not runs:
        print(f"  {program}: no run in the traced window", file=out)
        return
    seconds = [b - a for a, b in runs]
    print(f"  {program}: {len(runs)} runs, median "
          f"{1e3 * statistics.median(seconds):.3f} ms, mean "
          f"{1e3 * statistics.mean(seconds):.3f} ms, total "
          f"{sum(seconds):.3f} s", file=out)

    def show(events, n):
        for op, t, calls in longest(events, n):
            print(f"        {op:58s} {1e3 * t / len(runs):8.3f} ms a run, "
                  f"{calls / len(runs):5.1f} calls, {1e3 * t / calls:.3f} "
                  "ms each", file=out)

    inside = in_runs(scopes.of(obs) or [], runs)
    names = asked or sorted(
        {m for e in inside for m in SCOPE_NAME.findall(e[0])} | {RAGGED})
    rows = []
    for name in names:
        taken = trace_lines.scope_seconds_in(obs, name, pattern)
        if taken and taken[0]:
            rows.append((taken[0], name))
    for total, name in sorted(rows, reverse=True):
        print(f"    {name}: {1e3 * total / len(runs):.3f} ms a run "
              f"({100 * total / sum(seconds):.1f} % of the program)",
              file=out)
        show([e for e in inside if name in e[0]], top)
    print("    under none of these:", file=out)
    show([e for e in inside if not any(n in e[0] for _, n in rows)],
         2 * top)


def main(argv=None, out=sys.stdout) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("cell")
    parser.add_argument("--program", action="append")
    parser.add_argument("--scope", action="append")
    parser.add_argument("--top", type=int, default=4)
    args = parser.parse_args(argv)
    from benchmarks.lib import manifest, xplane
    trace_dir = os.path.join(manifest.ROOT, ".bench_run", args.cell, "trace")
    trace = xplane.load(xplane.find_xplane(trace_dir))
    lo, hi = xplane.window(trace)
    obs = {"cell": {"name": args.cell}, "trace": trace,
           "trace_window": (lo, hi)}
    print(f"{args.cell}: traced window {hi - lo:.3f} s, busy "
          f"{xplane.busy_seconds(trace, lo, hi):.3f} s", file=out)
    for program in args.program or PROGRAMS:
        split(obs, program, args.scope, args.top, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
