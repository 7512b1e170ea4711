"""The handler threads' account: what it costs a delivered token on this
host, and whether it closes in a cell.

    python3 benchmarks/tools/delivery_cost.py [--tokens N]
    python3 benchmarks/tools/delivery_cost.py --workload <name> \
        --seed <n> --seconds <s>

The second form is a `--trace 1` run of the cell as committed that
prints, after the result, the window's deltas of the counters the
`delivery` readers and `lib/sched.py` take (`account`) and what has to
hold of them (`closes`): the tokens the handlers flushed against the
tokens the scheduler pushed, within the tokens in flight (two a lane)
and what a handler holds in its locals between two credits (under 64 a
stream, at either edge of the window); the scheduler's CPU plus the
handlers' against the whole process's.

The first form times the frames loop of `api.main._engine_stream` as it stands (a
batch a wake-up, a clock read and three additions a token, a credit
every 64 tokens, a reading of the CPU clock every 512) against the loop as it was before the counters
(`_frames_before` below: no stamps, no batches, no account). The consumer of the frames publishes the stream's next token
after each frame, so every wake-up finds ONE token, as a tick's commit
leaves it; the publish is in both loops and the difference is the
bookkeeping alone: no socket, no scheduler, no other thread. Also that
`TokenStream.publish` of one token with its stamp, the scheduler's
side. No device is touched.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


class _Pipeline:
    def encode(self, text):
        return [1]

    def decode(self, ids):
        return ""


class _Engine:
    """Just what `_engine_stream` asks of an engine: `submit()` hands
    back a request whose stream holds its first token."""

    request_id = "r"

    def __init__(self):
        from fengshen_tpu.serving.metrics import EngineMetrics
        from fengshen_tpu.streaming import TokenStream
        self.metrics = EngineMetrics()
        self.streams = self
        self.stream, self.tokens = TokenStream(), [0]

    def attach_stream(self, rid):
        return None

    def submit(self, ids, **_):
        self.stream.publish(self.tokens, stamp=time.perf_counter())
        return self

    def get(self, rid):
        return self.stream

    def commit(self, n: int) -> None:
        """The next token, or the end after `n`."""
        self.tokens.append(len(self.tokens))
        self.stream.publish(
            self.tokens, stamp=time.perf_counter(),
            finish_reason="length" if len(self.tokens) >= n else None)


def _events_before(stream, start: int, timeout: float):
    """`TokenStream.events` as it was before the account: a token an
    item straight off the stream's list, no stamps, no batches."""
    pos = start
    while True:
        with stream._cond:
            while len(stream._tokens) <= pos and not stream.closed:
                if not stream._cond.wait(timeout=timeout):
                    return
            batch = stream._tokens[pos:]
            closed = stream.closed
            reason = stream.finish_reason
        for tok in batch:
            yield ("token", pos, tok)
            pos += 1
        if closed:
            yield ("done", pos, reason)
            return


def _frames_before(stream, timeout: float):
    """The frames loop over it, as it was: a frame a token."""
    from fengshen_tpu.streaming import format_event
    first = True
    for kind, idx, payload in _events_before(stream, 0, timeout):
        if first:
            first = False
        if kind == "token":
            yield format_event("token", {"token": payload}, event_id=idx)
        else:
            yield format_event("done", {"request_id": "r",
                                        "finish_reason": payload,
                                        "result": ""}, event_id=idx)


def measure(n: int) -> dict:
    from fengshen_tpu.api.main import _engine_stream
    from fengshen_tpu.streaming import TokenStream

    def with_account() -> float:
        engine = _Engine()
        _, _, frames = _engine_stream(engine, _Pipeline(),
                                      {"input_text": "1"}, 5.0)
        t = time.perf_counter()
        for _ in frames:
            if not engine.stream.closed:
                engine.commit(n)
        dt = time.perf_counter() - t
        c = engine.metrics._stream_delivered.value(), \
            engine.metrics._stream_wakeups.value()
        if c != (n, n):
            raise RuntimeError(f"{c} of {n} tokens and wake-ups credited")
        return 1e6 * dt / n

    def without() -> float:
        engine = _Engine()
        engine.submit(None)
        t = time.perf_counter()
        for _ in _frames_before(engine.stream, 5.0):
            if not engine.stream.closed:
                engine.commit(n)
        return 1e6 * (time.perf_counter() - t) / n

    def publish() -> float:
        stream, tokens = TokenStream(), []
        t = time.perf_counter()
        for i in range(n):
            tokens.append(i)
            stream.publish(tokens, stamp=t)
        return 1e6 * (time.perf_counter() - t) / n

    with_account(), without()
    on, off = [], []
    for _ in range(7):      # interleaved: a drift of the host hits both
        on.append(with_account())
        off.append(without())
    return {"tokens": n,
            "frame_us_with_account": statistics.median(on),
            "frame_us_without": statistics.median(off),
            "account_us_per_token":
                statistics.median(on) - statistics.median(off),
            "publish_us": statistics.median(publish() for _ in range(5))}


def account(workload: str, seed: int, seconds: float) -> dict:
    from fengshen_tpu.api.main import _CREDIT_EVERY

    from benchmarks import run
    from benchmarks.lib import (check, delivery, manifest, obsutil, sched,
                                traffic)
    man = manifest.load()
    cell = manifest.cell(man, workload)
    obs: dict = {}
    result = run.execute(man, cell, manifest.config_of(man, cell),
                         traffic.load_mix(cell["traffic"]),
                         check.limits_of(cell["name"]), seed, seconds, True,
                         obs_out=obs)
    names = {"ticks": sched.TICKS, "sched_wall": sched.WALL,
             "sched_cpu": sched.CPU, "sched_wait": sched.WAIT,
             "admit_cpu": delivery.ADMIT_CPU,
             "stream_cpu": delivery.STREAM_CPU,
             "process_cpu": delivery.PROCESS_CPU,
             "wakeups": delivery.WAKEUPS, "delivered": delivery.DELIVERED,
             "lag": delivery.LAG, "admitted": delivery.ADMITTED,
             "pushed": "fstpu_stream_tokens_total"}
    d = {k: obsutil.counter_delta(obs, n) for k, n in names.items()}
    out = {"workload": workload, "seed": seed, "result": result,
           "account": d}
    if None not in d.values():
        lanes = obs.get("lanes") or 0
        out["closes"] = {
            "pushed_minus_delivered": d["pushed"] - d["delivered"],
            "limit_in_flight_and_uncredited": (2 + _CREDIT_EVERY) * lanes,
            "process_cpu_minus_sched_and_handlers":
                d["process_cpu"] - d["sched_cpu"] - d["admit_cpu"]
                - d["stream_cpu"],
            "handlers_cpu_over_taken":
                (d["admit_cpu"] + d["stream_cpu"]) / max(
                    d["sched_wall"] - d["sched_cpu"] - d["sched_wait"],
                    1e-9)}
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--tokens", type=int, default=100000)
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int)
    parser.add_argument("--seconds", type=float)
    args = parser.parse_args()
    if args.workload:
        print(json.dumps(account(args.workload, args.seed, args.seconds)),
              flush=True)
    else:
        print(json.dumps(measure(args.tokens)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
