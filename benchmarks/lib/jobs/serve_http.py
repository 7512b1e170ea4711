"""Job kind `serve_http`: the real `api/main.py` server and the
continuous-batching engine in this process, clients on threads.

Set-up: weights on the device from the seed, engine, warm-up thread and
stdlib server exactly as `api/main.py` wires them, one short request
per bucket through the route (the first admission of a bucket compiles
its assign program), then the mix's ramp. The window opens in steady
state. After it closes the program is stopped and freed, and the plain
reference scores a seeded sample of the requests the window finished.
"""

from __future__ import annotations

import gc
import importlib
import threading
import time

import numpy as np

from benchmarks.lib import check, loadgen, weights
from benchmarks.lib import reduce as R
from benchmarks.lib.runlog import say
from benchmarks.lib.tracing import Traced


DECODE_TOKENS = "fstpu_serving_decode_tokens_total"
LANES_PEAK = "fstpu_serving_slots_active_peak"


class IntTokenizer:
    """A text of space-separated ids <-> those ids: the seeded token
    ids reach `engine.submit` unchanged and no tokenizer is timed."""

    eos_token_id = None
    pad_token_id = 0

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(int(t)) for t in ids)


def _make_params(model, seed: int):
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.jit(lambda key: weights.fill_like(key, shapes))(
        weights.base_key(seed))
    return jax.block_until_ready(params)


def _counters(engine) -> dict:
    """The engine's own counters as `/metrics` renders them, read
    without the scheduler's lock (a `stats()` at a window edge waited
    seconds behind 64 submitting clients)."""
    from fengshen_tpu.observability import render_prometheus
    out = {}
    for line in render_prometheus(engine.metrics.registry).splitlines():
        if line.startswith("fstpu_") and "{" not in line:
            name, _, value = line.partition(" ")
            out[name] = float(value)
    return out


def _poller(engine, obs: dict, stop: threading.Event, period: float,
            timelines: bool) -> None:
    n = 0
    while not stop.wait(period):
        s = engine.stats()
        obs["polls"].append((time.perf_counter(), s["kv_blocks_used"],
                             s["kv_blocks_total"], s["slots_active"],
                             s["queue_depth"]))
        n += 1
        if timelines and n % 4 == 0:
            for d in engine.debug_requests()["recent"]:
                obs["timelines"][d["request_id"]] = d


def run(ctx: dict) -> dict:
    import jax

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       _start_warmup_thread,
                                       build_stdlib_server,
                                       create_continuous_engine)
    from fengshen_tpu.pipelines.text_generation import Pipeline

    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    seconds, meter = ctx["seconds"], ctx["meter"]
    phases = ctx["phases"]
    family = ctx["family"]

    t = time.perf_counter()
    model, model_cfg = family.build(config)
    params = _make_params(model, seed)
    pipe = Pipeline(module=model, params=params, tokenizer=IntTokenizer(),
                    max_new_tokens=mix["engine_args"]["max_new_tokens"])
    phases["weights_s"] = time.perf_counter() - t
    say(f"weights on the device: "
        f"{sum(p.nbytes for p in jax.tree_util.tree_leaves(params)) / 1e9:.2f}"
        f" GB in {phases['weights_s']:.1f}s")

    t = time.perf_counter()
    engine_args = {**config["engine_args"], **mix["engine_args"]}
    events: list = []
    engine = create_continuous_engine(pipe, engine_args, log=events.append)
    server_cfg = ServerConfig(host="127.0.0.1", port=0, engine="continuous")
    pipeline_cfg = PipelineConfig(task="text_generation")
    ready = _start_warmup_thread(server_cfg, pipeline_cfg, pipe, engine)
    server = build_stdlib_server(server_cfg, pipeline_cfg, pipeline=pipe,
                                 engine=engine, ready=ready)
    server_thread = threading.Thread(target=server.serve_forever,
                                     daemon=True)
    server_thread.start()
    ready.settled.wait()
    if ready.error is not None:
        raise RuntimeError(f"engine warm-up failed: {ready.error}")
    phases["server_warmup_s"] = time.perf_counter() - t
    say(f"server up and engine warm in {phases['server_warmup_s']:.1f}s "
        f"(kv pool {engine._kv_bytes / 1e9:.2f} GB, "
        f"{engine.num_blocks} blocks)")

    port = server.server_address[1]
    gen = loadgen.LoadGen("127.0.0.1", port, "/api/text_generation", mix,
                          seed, config["vocab_size"])
    obs = ctx["obs"]
    obs.update(polls=[], timelines={}, lanes=engine.config.num_slots)
    try:
        # one short request per bucket: its first admission compiles
        t = time.perf_counter()
        buckets = sorted(mix["engine_args"]["buckets"])
        warm = loadgen.LoadGen("127.0.0.1", port, "/api/text_generation",
                               mix, seed, config["vocab_size"])
        for i, b in enumerate(buckets):
            row = {"index": 2 ** 40 + i, "prompt_len": b, "output_len": 2}
            rec = warm.send(row, warm.body(row), time.perf_counter())
            if rec["failed"] or len(rec["tokens"]) != 2:
                raise RuntimeError(f"bucket {b} warm request failed: {rec}")
        phases["bucket_warm_s"] = time.perf_counter() - t

        # the ramp: part of set-up
        t = time.perf_counter()
        gen.start()
        ramp = mix["ramp"]
        deadline = t + 180
        while True:
            ok = True
            if "open_after_completed" in ramp:
                ok &= gen.completed >= ramp["open_after_completed"]
            if ramp.get("every_lane_occupied"):
                ok &= _counters(engine)[LANES_PEAK] >= obs["lanes"]
            ok &= time.perf_counter() - t >= ramp.get("min_s", 0)
            if "open_after_due" in ramp:
                ok &= len(gen.records) >= ramp["open_after_due"]
            if ok:
                break
            if time.perf_counter() > deadline:
                raise RuntimeError("the ramp never reached steady state: "
                                   f"{engine.stats()}")
            time.sleep(0.05)
        phases["ramp_s"] = time.perf_counter() - t
        say(f"ramp done in {phases['ramp_s']:.1f}s: {gen.completed} "
            "completed")

        # ---- the window ------------------------------------------------
        compile_mark = meter.mark()
        stats_open = _counters(engine)
        t_open = time.perf_counter()
        stop_poll = threading.Event()
        poller = threading.Thread(
            target=_poller, args=(engine, obs, stop_poll, 0.25,
                                  ctx["trace"]), daemon=True)
        poller.start()
        traced = None
        if ctx["trace"]:
            # a few seconds from the middle of the window
            trace_s = min(ctx["trace_seconds"], seconds / 2)
            time.sleep(max(0.0, (seconds - trace_s) / 2))
            traced = Traced(ctx["trace_dir"])
            with traced:
                time.sleep(trace_s)
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        stats_close = _counters(engine)
        t_close = time.perf_counter()
        stop_poll.set()
        window_compiles = meter.mark()[1] - compile_mark[1]
        obs.update(window=(t_open, t_close), stats_open=stats_open,
                   stats_close=stats_close,
                   compiles_in_window=window_compiles)
        say(f"window closed after {t_close - t_open:.3f}s")

        # requests due in the window get their first token before the
        # streams are dropped (open loop); nothing new is sent
        gen.stop_issuing()
        grace = time.perf_counter() + 15
        while time.perf_counter() < grace:
            late = [r for r in gen.snapshot()
                    if t_open <= r["due"] < t_close and not r["failed"]
                    and not r["token_times"]]
            if not late:
                break
            time.sleep(0.05)
        obs["last_error"] = engine.stats()["last_error"]
        obs["tick_errors"] = [e for e in events
                              if e.get("event") == "serving_tick_error"]
    finally:
        gen.abort()
        server.shutdown()
        server.server_close()
        engine.stop()
        server_thread.join(timeout=10)
    records = gen.snapshot()
    obs["records"] = records
    obs["memory_peak_bytes"] = ctx["memory_peak"]()
    if traced is not None:
        obs["trace"], obs["trace_window"] = traced.load()
        obs["pc_minus_trace"] = traced.pc_minus_trace

    # ---- checks inside the window --------------------------------------
    numbers = []          # (what, value, limit, ok)
    in_window = [r for r in records
                 if r["done"] is not None and t_open <= r["done"] < t_close]
    wrong_len = [r for r in in_window
                 if len(r["tokens"]) != r["output_len"]
                 or r["finish_reason"] != "length"]
    failed = [r for r in records if r["failed"]]
    attempted = sum(1 for r in records if r["sent"] is not None
                    and r["sent"] < t_close)
    numbers.append(("requests answered with another token count than "
                    "asked", len(wrong_len), 0, not wrong_len))
    numbers.append(("requests failed or refused", len(failed), 0,
                    not failed))
    numbers.append(("programs compiled inside the window",
                    window_compiles, 0, window_compiles == 0))
    numbers.append(("engine tick errors", len(obs["tick_errors"]), 0,
                    not obs["tick_errors"] and obs["last_error"] is None))
    # the client's count of decode tokens against the engine's counter:
    # at each edge one tick's tokens may fall on either side
    client_out = R.credited_tokens(records, t_open, t_close)["output"]
    firsts = sum(1 for r in records if r["token_times"]
                 and t_open <= r["token_times"][0] < t_close)
    engine_out = int(stats_close[DECODE_TOKENS] - stats_open[DECODE_TOKENS])
    gap = abs((client_out - firsts) - engine_out)
    limit = 2 * obs["lanes"]
    obs["token_count_gap"] = gap
    say(f"decode tokens in the window: clients {client_out - firsts}, "
        f"engine counter {engine_out}")
    numbers.append(("client decode tokens minus the engine's counter",
                    gap, limit, gap <= limit))
    # the other statistics of the same window, for whoever weighs a
    # steadier or a further tail against the ones the metrics report
    due = R.due_in_window(records, t_open, t_close)
    gaps = R.token_gaps(records, t_open, t_close)
    if mix["loop"] == "open" and due and gaps:
        t = R.ttfts(due)
        say("ttft from the due instant (ms): " + ", ".join(
            f"p{int(q * 100)} {1e3 * R.percentile(t, q):.1f}"
            for q in (0.5, 0.75, 0.9)) + f" over {len(t)} requests; "
            "token gaps (ms): " + ", ".join(
            f"p{round(q * 100, 1)} {1e3 * R.percentile(gaps, q):.2f}"
            for q in (0.5, 0.9, 0.95, 0.99, 0.995)) +
            f", mean {1e3 * sum(gaps) / len(gaps):.2f} over {len(gaps)}")
    # what shorter windows opened at the same instant would have read:
    # the table PERF.md's choice of run_seconds rests on
    say("tokens/s credited over the window's first N s: " + ", ".join(
        f"{n}: {R.credited_tokens(records, t_open, t_open + n)['total'] / n:.1f}"
        for n in range(10, int(t_close - t_open) + 1, 10)))

    # ---- the reference, after the program is freed ----------------------
    t = time.perf_counter()
    finished = [r for r in records if r["done"] is not None
                and r["done"] < t_close and len(r["tokens"]) ==
                r["output_len"]]
    # handler threads and closures may still name the engine: delete
    # its device buffers outright, then drop the names
    for leaf in jax.tree_util.tree_leaves(
            (params, engine._cache, engine._history, engine._mask,
             engine._keys)):
        if hasattr(leaf, "delete"):
            leaf.delete()
    del engine, pipe, params, server, gen
    gc.collect()
    reference = importlib.import_module(family.REFERENCE)
    result = check.served_gap(
        reference, family.reference_config(config), seed, finished,
        dict(mix["check"], limit=ctx["limits"]["served_logit_gap"],
             rows=mix["engine_args"]["max_new_tokens"]),
        config["vocab_size"], ctx.get("control"))
    phases["reference_s"] = time.perf_counter() - t
    numbers.extend(result["numbers"])
    obs["reference"] = result
    return {"numbers": numbers, "attempted": attempted,
            "failed": len(failed), "t_open": t_open}
