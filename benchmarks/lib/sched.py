"""The scheduler thread's cycle, from the trace and from the engine's
counters (the `sched_*`, `decode_dispatch_cpu_share.*` and
`commit_cpu_share.*` readers).

From the trace: a cycle runs from the start of one `serving/lock_wait`
span to the start of the next (the serve loop takes the lock once an
iteration). A PURE DECODE cycle holds a `serving/decode/dispatch` and no
`serving/prefill` or `serving/assign`: the host's work for one tick and
nothing else. The scheduler thread's spans are taken by name: every
`serving/*` but the submitters' `serving/admit*`.

From the counters (`fstpu_serving_scheduler_*_seconds_total` and the
dispatch and commit pairs, deltas over the whole window): the thread's
wall seconds are its CPU seconds, the seconds it waited by design (the
lock, the device's tokens, the idle condition: off-CPU time inside the
spans that declare a wait) and what was TAKEN from it: the GIL,
preemption, a call that blocks where none was declared.

A reader that finds no trace, no such span or no such counter (the
parent of the PR that added them) returns None.
"""

from __future__ import annotations

import statistics

from benchmarks.lib import obsutil, xplane

CYCLE = "serving/lock_wait"
DISPATCH = "serving/decode/dispatch"
NOT_PURE = ("serving/prefill", "serving/assign")
HOT = "serving/"
NOT_HOT = "serving/admit"

WALL = "fstpu_serving_scheduler_wall_seconds_total"
CPU = "fstpu_serving_scheduler_cpu_seconds_total"
WAIT = "fstpu_serving_scheduler_wait_seconds_total"
LOCK_WAIT = "fstpu_serving_lock_wait_seconds_total"
TICKS = "fstpu_serving_decode_ticks_total"


def pure_cycles(obs):
    """[(start, end)] of the pure decode cycles inside the traced
    window, or None without a trace or a device plane."""
    t = obsutil.traced(obs)
    if t is None:
        return None
    trace, lo, hi = t
    starts = sorted(a for a, _ in xplane.spans(trace, CYCLE, lo, hi))
    dispatches = [a for a, _ in xplane.spans(trace, DISPATCH, lo, hi)]
    others = [a for name in NOT_PURE
              for a, _ in xplane.spans(trace, name, lo, hi)]
    return [(a, b) for a, b in zip(starts, starts[1:])
            if any(a <= d < b for d in dispatches)
            and not any(a <= o < b for o in others)]


def cycle_ms(obs):
    """Median length of a pure decode cycle, ms."""
    cycles = pure_cycles(obs)
    if not cycles:
        return None
    return 1e3 * statistics.median(b - a for a, b in cycles)


def uncovered_share(obs):
    """Of the pure decode cycles' seconds, the share no span of the
    scheduler thread covers, %."""
    cycles = pure_cycles(obs)
    if not cycles:
        return None
    trace, lo, hi = obsutil.traced(obs)
    hot = [e for e in trace["host"] if e[0].startswith(HOT)
           and not e[0].startswith(NOT_HOT)]
    covered = xplane.merged(hot, lo, hi)
    total = inside = 0.0
    i = 0
    for a, b in cycles:
        total += b - a
        while i < len(covered) and covered[i][1] <= a:
            i += 1
        j = i
        while j < len(covered) and covered[j][0] < b:
            inside += min(covered[j][1], b) - max(covered[j][0], a)
            j += 1
    return 100.0 * (1.0 - inside / total)


def _deltas(obs, *names):
    values = [obsutil.counter_delta(obs, n) for n in names]
    return None if any(v is None for v in values) else values


def _ms_per_tick(obs, seconds: str):
    d = _deltas(obs, seconds, TICKS)
    if d is None or not d[1]:
        return None
    return 1e3 * d[0] / d[1]


def cpu_ms_per_tick(obs):
    """The scheduler thread's CPU a decode tick, ms (admissions' in)."""
    return _ms_per_tick(obs, CPU)


def taken_share(obs):
    """Of the scheduler thread's wall, what was neither its CPU nor a
    declared wait, %."""
    d = _deltas(obs, WALL, CPU, WAIT)
    if d is None or not d[0]:
        return None
    wall, cpu, wait = d
    return 100.0 * (wall - cpu - wait) / wall


def lock_wait_ms_per_tick(obs):
    """MEAN wall of `serving/lock_wait` a decode tick, ms."""
    return _ms_per_tick(obs, LOCK_WAIT)


def cpu_share(obs, what: str):
    """CPU over wall of the `dispatch` or `commit` spans, %."""
    d = _deltas(obs, f"fstpu_serving_{what}_cpu_seconds_total",
                f"fstpu_serving_{what}_seconds_total")
    if d is None or not d[1]:
        return None
    return 100.0 * d[0] / d[1]
