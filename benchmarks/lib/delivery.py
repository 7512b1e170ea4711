"""The threads that deliver: the server's handler threads, one a
stream, from the engine's counters (the `handler_*`, `stream_*`,
`taken_unexplained_ms_per_tick.*` and `process_cpu_ms_per_tick.*`
readers). Deltas over the whole window of unlabelled counters on the
engine's registry, credited by each handler thread every 64 delivered
tokens (its CPU seconds every 512) and at its stream's end, so a window
over streams that live for a minute reads steady state to within that
many tokens a stream at each edge.

Beside `lib/sched.py`'s account of the scheduler thread: what is TAKEN
from that thread (its wall less its CPU less its declared waits) is the
GIL held by the handlers, by whatever else shares the process (the
benchmark's client threads; the runtime's own) and preemption. The
handlers' CPU is the part of it the server itself can explain.

A reader that finds no such counter (the parent of the PR that added
them) returns None.
"""

from __future__ import annotations

from benchmarks.lib import sched

ADMIT_CPU = "fstpu_serving_handler_admit_cpu_seconds_total"
STREAM_CPU = "fstpu_serving_handler_stream_cpu_seconds_total"
WAKEUPS = "fstpu_stream_wakeups_total"
DELIVERED = "fstpu_stream_tokens_delivered_total"
LAG = "fstpu_stream_delivery_lag_seconds_total"
PROCESS_CPU = "fstpu_serving_process_cpu_seconds_total"
ADMITTED = "fstpu_serving_admitted_total"


def _over(obs, per: float, denominator: str, *numerators):
    """`per` x the summed deltas of `numerators` over `denominator`'s,
    or None without one of the counters or with nothing to divide by."""
    d = sched._deltas(obs, denominator, *numerators)
    if d is None or not d[0]:
        return None
    return per * sum(d[1:]) / d[0]


def handler_cpu_ms_per_tick(obs):
    """The handler threads' CPU a decode tick, admission and delivery
    together, ms: what the server's own threads beside the scheduler
    spend under the GIL a tick."""
    return _over(obs, 1e3, sched.TICKS, ADMIT_CPU, STREAM_CPU)


def taken_unexplained_ms_per_tick(obs):
    """What is taken from the scheduler thread a tick LESS the handler
    threads' CPU a tick, ms: an upper bound on what the clients in the
    server's process and preemption take. A difference, not a share: a
    run whose scheduler lost nothing still reads a number, and it is
    NEGATIVE where the handlers' work fits inside the waits the
    scheduler declares (a cell the device paces)."""
    d = sched._deltas(obs, sched.TICKS, sched.WALL, sched.CPU, sched.WAIT,
                ADMIT_CPU, STREAM_CPU)
    if d is None or not d[0]:
        return None
    ticks, wall, cpu, wait, admit, stream = d
    return 1e3 * ((wall - cpu - wait) - (admit + stream)) / ticks


def admit_cpu_ms_per_request(obs):
    """A handler thread's CPU from a POST's entry to the return of
    `submit()`, a request admitted, ms (the prompt arrives as text)."""
    return _over(obs, 1e3, ADMITTED, ADMIT_CPU)


def delivery_lag_ms(obs):
    """MEAN of a delivered token's `flush()` return less the commit
    that brought it, ms."""
    return _over(obs, 1e3, DELIVERED, LAG)


def tokens_per_wakeup(obs):
    """Tokens delivered a wake-up of a stream's reader: 1 where every
    tick wakes every stream for one token, a block's tokens where a
    commit delivers a block, more where readers fall behind."""
    return _over(obs, 1.0, WAKEUPS, DELIVERED)


def process_cpu_ms_per_tick(obs):
    """The whole process's CPU a decode tick, ms: the scheduler's, the
    handlers' and the rest (the runtime's threads; in the benchmark the
    clients too)."""
    return _over(obs, 1e3, sched.TICKS, PROCESS_CPU)
