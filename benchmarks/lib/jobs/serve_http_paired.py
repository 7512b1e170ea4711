"""Job kind `serve_http_paired`: `serve_http` to the letter (the same
server, engine, warm-up, ramp, window and checks inside it), with the
comparison against the plain reference read as the mean gap over every
served token LESS the mean gap of the reference's own first tokens in
the configuration's precision (`lib/check_paired.py` says why), as
`serve_http_mean` swaps in the plain mean: `serve_http.run` calls
`check.served_gap` by that name and a PR that adds a cell may not edit
it. A `benchmark` PR that gives `serve_http` the statistic as a key of
the mix's `check` retires both files (PERF.md section 7)."""

from __future__ import annotations

from benchmarks.lib import check, check_paired
from benchmarks.lib.jobs import serve_http


class _Check:
    """`lib.check` with `served_gap` answered by `lib.check_paired`."""

    served_gap = staticmethod(check_paired.served_gap)

    def __getattr__(self, name):
        return getattr(check, name)


def run(ctx: dict) -> dict:
    serve_http.check = _Check()
    try:
        return serve_http.run(ctx)
    finally:
        serve_http.check = check
