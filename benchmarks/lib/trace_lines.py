"""Programs and scopes on the trace: device time read from the device's
own lines, never from inside a host span.

The module line holds one event a run of a jitted program
(`jit_decode_fn`, `jit_window_fn`, `jit_prefill_fn`, `jit_assign_fn`);
the operation line one event an executed operation, found by its own
name (a Mosaic kernel keeps the `name=` of its `pallas_call`) or by the
`jax.named_scope` in its `op_name` (`lib.scopes`). Since one decode tick
is kept in flight (PR 27) a `serving/decode` span holds the NEXT tick's
dispatch and THIS tick's fetch, and a prompt's windows are enqueued back
to back: a host span no longer bounds its program's device time, the
module line's run intervals do. A scope that occurs in two programs (a
convolution, the experts, `ragged-dot*` calls whose `op_name` XLA:TPU
drops) is told apart by those intervals too.

Every function returns None (or an empty list) where the run has no
trace, no device plane or the thing is not in it. A model's scope lists
live with its cost functions (`lib/costs_<model>.py`).
"""

from __future__ import annotations

import bisect
import re
import statistics

from benchmarks.lib import obsutil, scopes, xplane, xplane_attrs

DECODE = re.compile(r"\bjit_decode_fn\b")
WINDOW = re.compile(r"\bjit_window_fn\b")
PREFILL = re.compile(r"\bjit_prefill_fn\b")
ASSIGN = re.compile(r"\bjit_assign_fn\b")
PREFILL_SPAN = "serving/prefill"
WINDOW_SPAN = "serving/prefill/window"


def module_intervals(obs: dict, pattern) -> list:
    """[(start, end)] of each run of the programs matching `pattern`
    that lies inside the traced window."""
    t, attrs = obsutil.traced(obs), xplane_attrs.of(obs)
    if t is None or attrs is None:
        return []
    _, lo, hi = t
    return [(s, s + d) for n, s, d in attrs["modules"]
            if pattern.search(n) and s >= lo and s + d <= hi]


def module_runs(obs: dict, pattern) -> list:
    """Seconds of each run of a program inside the traced window."""
    return [b - a for a, b in module_intervals(obs, pattern)]


def median_run_ms(obs: dict, pattern):
    """Median device time of one run of a program, ms."""
    runs = module_runs(obs, pattern)
    return 1e3 * statistics.median(runs) if runs else None


def decode_step_ms(obs: dict):
    """One decode tick on the device: the median `jit_decode_fn` run."""
    return median_run_ms(obs, DECODE)


def assign_ms(obs: dict):
    """One run of the engine's assign program (`assign_fn` installs a
    prefilled request in its lane of the pool), median."""
    return median_run_ms(obs, ASSIGN)


def _marks(names) -> tuple:
    return (names,) if isinstance(names, str) else tuple(names)


def _inside(events: list, runs: list) -> float:
    """Seconds of the union of `events` ([text, start, dur], sorted by
    start) inside each of `runs`, summed."""
    starts = [e[1] for e in events]
    total = 0.0
    for a, b in runs:
        # an operation of a run starts inside it; a `while` may have
        # started before its body's operations: look a little back
        i = max(bisect.bisect_left(starts, a) - 1, 0)
        j = bisect.bisect_right(starts, b)
        total += sum(y - x for x, y in xplane.merged(events[i:j], a, b))
    return total


def scope_seconds(obs: dict, names):
    """Device seconds, inside the traced window, of the operations
    under any of the scopes `names` (the union of their intervals)."""
    t, ops = obsutil.traced(obs), scopes.of(obs)
    if t is None or not ops:
        return None
    marks = _marks(names)
    under = [e for e in ops if any(m in e[0] for m in marks)]
    if not under:
        return None
    _, lo, hi = t
    return sum(b - a for a, b in xplane.merged(
        sorted(under, key=lambda e: e[1]), lo, hi))


def scope_seconds_in(obs: dict, names, program):
    """(device seconds of the operations under any of `names` inside
    the runs of the programs matching `program` that lie in the traced
    window, those runs' count), or None."""
    ops, runs = scopes.of(obs), module_intervals(obs, program)
    if not ops or not runs:
        return None
    marks = _marks(names)
    under = sorted((e for e in ops if any(m in e[0] for m in marks)),
                   key=lambda e: e[1])
    if not under:
        return None
    return _inside(under, runs), len(runs)


def kernel_seconds_in(obs: dict, prefix: str, program):
    """As `scope_seconds_in`, for the operations whose OWN name starts
    with `prefix` (a Mosaic kernel's `name=`; the compiler appends its
    counter): the kernel alone, without what its scope holds beside
    it."""
    t, runs = obsutil.traced(obs), module_intervals(obs, program)
    if t is None or not runs:
        return None
    own = sorted((e for e in xplane.first_device(t[0])
                  if e[0].startswith(prefix)), key=lambda e: e[1])
    if not own:
        return None
    return _inside(own, runs), len(runs)


def seconds_a_run(taken):
    """A `(seconds, runs)` pair as seconds a run, None where either is
    nothing."""
    if not taken or not taken[0] or not taken[1]:
        return None
    return taken[0] / taken[1]


def busy_seconds(obs: dict):
    t = obsutil.traced(obs)
    return None if t is None else xplane.busy_seconds(*t)


def share_of_busy(obs: dict, names):
    under, busy = scope_seconds(obs, names), busy_seconds(obs)
    if under is None or not busy:
        return None
    return 100.0 * under / busy


def window_spans(obs: dict) -> list:
    """[(window index, real tokens)] of the prefill windows whose host
    span lies inside the traced window."""
    t, attrs = obsutil.traced(obs), xplane_attrs.of(obs)
    if t is None or attrs is None:
        return []
    return [(int(a["window"]), int(a["tokens"]))
            for _, _, a in xplane_attrs.spans_with(attrs, WINDOW_SPAN,
                                                   t[1], t[2])
            if "window" in a and "tokens" in a]


def prefill_us_per_padded_token(obs: dict):
    """Device seconds of the prefill programs' runs inside the traced
    window over the PADDED tokens those runs were wide, us a token. A
    run of the window program is as wide as the window (the mix's
    widest bucket); a run of the whole-prompt program as wide as the
    `bucket` attribute of the `serving/prefill` span that dispatched it:
    that span waits for the prompt's first token, so the run's middle
    lies inside it. A whole-prompt run that finds no such span is left
    out of both sums."""
    attrs = xplane_attrs.of(obs)
    if attrs is None:
        return None
    windows = module_runs(obs, WINDOW)
    seconds = sum(windows)
    tokens = len(windows) * max(obs["mix"]["engine_args"]["buckets"]) \
        if windows else 0
    spans = sorted((s, s + d, a["bucket"]) for n, s, d, a in attrs["spans"]
                   if n == PREFILL_SPAN and "bucket" in a)
    starts = [s for s, _, _ in spans]
    for a, b in module_intervals(obs, PREFILL):
        i = bisect.bisect_right(starts, (a + b) / 2) - 1
        if i >= 0 and (a + b) / 2 <= spans[i][1]:
            seconds += b - a
            tokens += spans[i][2]
    return 1e6 * seconds / tokens if tokens else None
