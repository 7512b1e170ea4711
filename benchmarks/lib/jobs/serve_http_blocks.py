"""Job kind `serve_http_blocks`: `serve_http` to the letter (the same
server, engine, warm-up, ramp, window and checks inside it) for an
engine whose ticks forward a BLOCK of positions a lane and deliver a
finished block's tokens together (docs/serving.md "Block generation").
Two things are answered for blocks, neither by editing `serve_http.py`
(a PR that adds a cell may not):

- the comparison against the plain reference is `lib/check_blocks.py`'s
  (`serve_http.run` calls `check.served_gap` by that name, as
  `serve_http_paired` found): every served token on the reference's
  logits of the denoising step that revealed it, paired as Keye's;
- "client decode tokens minus the engine's counter" assumed that the
  first token of a request is a prefill's and that a tick delivers one
  token a lane. Here every output token, the first included, is a
  tick's (`fstpu_serving_decode_tokens_total`) and up to `lanes x L`
  arrive at once, so the number is recomputed from what the run
  observed once `serve_http.run` has returned: `|client output tokens -
  engine counter|` against `2 x lanes x L` (one tick's tokens may fall
  on either side of each edge), its tuple replaced in the returned
  numbers (which `benchmarks/run.py` reads only after the job returns)
  and `obs["token_count_gap"]` with it.

A `benchmark` PR that gives `serve_http` the statistic and the tokens a
tick may deliver as keys of the mix retires this file with
`serve_http_mean` and `serve_http_paired` (PERF.md section 7)."""

from __future__ import annotations

import functools

from benchmarks.lib import check, check_blocks
from benchmarks.lib import reduce as R
from benchmarks.lib.jobs import serve_http
from benchmarks.lib.runlog import say

REPLACED = "client decode tokens minus the engine's counter"


class _Check:
    """`lib.check` with `served_gap` answered by `lib.check_blocks`."""

    def __init__(self, steps: int):
        self.served_gap = functools.partial(check_blocks.served_gap,
                                            steps=steps)

    def __getattr__(self, name):
        return getattr(check, name)


def token_count(obs: dict, block_length: int) -> tuple:
    """(what, value, limit, ok): the clients' count of output tokens in
    the window against the engine's counter of delivered tokens."""
    t_open, t_close = obs["window"]
    clients = R.credited_tokens(obs["records"], t_open, t_close)["output"]
    engine = int(obs["stats_close"][serve_http.DECODE_TOKENS]
                 - obs["stats_open"][serve_http.DECODE_TOKENS])
    gap, limit = abs(clients - engine), 2 * obs["lanes"] * block_length
    say(f"output tokens in the window: clients {clients}, engine counter "
        f"{engine}")
    return ("client output tokens minus the engine's counter", gap, limit,
            gap <= limit)


def run(ctx: dict) -> dict:
    serve_http.check = _Check(ctx["mix"]["engine_args"]["denoise_steps"])
    try:
        out = serve_http.run(ctx)
    finally:
        serve_http.check = check
    counted = token_count(ctx["obs"],
                          ctx["config"]["assumed"]["block_length"])
    ctx["obs"]["token_count_gap"] = counted[1]
    out["numbers"] = [counted if n[0] == REPLACED else n
                      for n in out["numbers"]]
    return out
