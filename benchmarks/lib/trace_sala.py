"""What the `.longdoc` readers share: device time by program (the
trace's module line) and by named scope over the TRACED window itself,
not inside host spans — with a tick in flight and a prompt's windows
enqueued back to back, a host span no longer bounds its program's
device time (PERF.md section 3's note on PR 27). Every function
returns None where the run has no trace or the thing is not in it."""

from __future__ import annotations

import re

from benchmarks.lib import obsutil, scopes, xplane, xplane_attrs

DECODE = re.compile(r"\bjit_decode_fn\b")
WINDOW = re.compile(r"\bjit_window_fn\b")
WINDOW_SPAN = "serving/prefill/window"

#: the device scopes of the two mixers (fengshen_tpu/ops)
SCOPES = ("fstpu_lightning_prefill", "fstpu_lightning_decode",
          "fstpu_sparse_pool", "fstpu_sparse_select",
          "fstpu_sparse_decode_attention", "fstpu_sparse_prefill_attention")


def module_runs(obs: dict, pattern) -> list:
    """Seconds of each run of a program inside the traced window."""
    t, attrs = obsutil.traced(obs), xplane_attrs.of(obs)
    if t is None or attrs is None:
        return []
    return xplane_attrs.module_seconds(attrs, pattern, t[1], t[2])


def scope_seconds(obs: dict, names) -> float:
    """Device seconds, inside the traced window, of the operations
    under any of the scopes `names` (the union of their intervals)."""
    t, ops = obsutil.traced(obs), scopes.of(obs)
    if t is None or not ops:
        return None
    marks = (names,) if isinstance(names, str) else tuple(names)
    under = [e for e in ops if any(m in e[0] for m in marks)]
    if not under:
        return None
    _, lo, hi = t
    return sum(b - a for a, b in xplane.merged(
        sorted(under, key=lambda e: e[1]), lo, hi))


def busy_seconds(obs: dict):
    t = obsutil.traced(obs)
    return None if t is None else xplane.busy_seconds(*t)


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


def share_of_busy(obs: dict, names):
    under, busy = scope_seconds(obs, names), busy_seconds(obs)
    if under is None or not busy:
        return None
    return 100.0 * under / busy
