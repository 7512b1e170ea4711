"""Fleet microbench: aggregate tokens/s at N replicas vs one, plus the
kill-one-replica-mid-run robustness rung.

    make serve-bench-fleet
    FLEET_BENCH_REPLICAS=3 python -m fengshen_tpu.fleet.bench

Spawns N **real replica subprocesses** (`--replica`: a random-init
llama in the weight-memory-bound serve-bench shape behind the stdlib
api server + continuous engine), fronts them with a `FleetRouter`, and
drives the same request set three ways:

1. one replica only → `tokens_per_sec_1` (the baseline);
2. all N replicas → `value` (the ≥2x acceptance bar of ISSUE 10 —
   each replica is slot-capacity-bound, so the fleet's win is real
   batched-decode capacity, not timer noise);
3. all N replicas with replica #1 SIGKILLed after `KILL_AFTER`
   responses: every request must still answer 200 (the router retries
   connect/reset failures on a different replica; requests are
   idempotent-safe greedy with router-assigned ids), `failed` must be
   0, and the kill-run outputs must be token-identical to run 2's.

One BENCH-schema JSON line ({"metric", "value", "unit",
"vs_baseline", ...}) with the **replica count in the row**
(`"replicas": N`): benchdiff treats rows at different N as
incomparable, like offload placements (docs/observability.md).

`FLEET_BENCH_FAKE=1` swaps the replicas for in-process fake servers
(pure stdlib, no jax: deterministic token function + a per-token sleep
emulating decode) so the fast-lane smoke test
(`tests/test_fleet_bench_smoke.py`) exercises the whole harness —
schema, phases, the kill rung — in a couple of seconds without a
model. Env knobs (FLEET_BENCH_*): REPLICAS, REQUESTS, NEW_TOKENS,
SLOTS (per replica), KILL (0 disables rung 3), KILL_AFTER, FAKE,
FAKE_TOKEN_S, BASE_PORT, and the serve-bench model shape knobs VOCAB /
HIDDEN / INTER / LAYERS / HEADS / BUCKETS / SEED.
"""

from __future__ import annotations

import argparse
import http.server
import json
import os
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional, Tuple

from fengshen_tpu.fleet.launcher import replica_backend, replica_env
from fengshen_tpu.fleet.router import FleetConfig, FleetRouter


def _env(name: str, default: int) -> int:
    return int(os.environ.get(f"FLEET_BENCH_{name}", default))


def _buckets() -> Tuple[int, ...]:
    return tuple(int(b) for b in os.environ.get(
        "FLEET_BENCH_BUCKETS", "32,64").split(","))


def _emit(row: dict) -> None:
    from fengshen_tpu.observability import JsonlSink
    if os.environ.get("BENCH_DEGRADED", "0") == "1":
        row["degraded"] = True
    JsonlSink(stream=sys.stdout, only_process_zero=False)(row)


class _IntTokenizer:
    """Whitespace-int tokenizer ('5 7 9' <-> [5, 7, 9]) — the bench's
    prompts are synthetic, a real vocab would only add weight."""

    eos_token_id = None
    pad_token_id = 0

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(int(t)) for t in ids)


# ---- fake replicas (FLEET_BENCH_FAKE=1: the harness-smoke path) -----

def _fake_result(ids: List[int], n: int, vocab: int = 97) -> str:
    """Deterministic stand-in for greedy decode: the same prompt gives
    the same tokens on EVERY replica, so retry/kill runs can assert
    token identity without a model."""
    s = sum(ids)
    return " ".join(str((s + i) % vocab) for i in range(n))


def start_fake_replica(num_slots: int, token_s: float,
                       default_new_tokens: int,
                       host: str = "127.0.0.1", port: int = 0):
    """In-process fake api replica: /healthz, /stats, and a generate
    route whose latency is num-tokens x token_s gated by a
    num_slots-wide semaphore (decode capacity). Returns (server,
    thread); kill it with `server.shutdown(); server.server_close()`
    (new connects then refuse — the fake analog of a dead process)."""
    sem = threading.BoundedSemaphore(num_slots)
    lock = threading.Lock()
    active = [0]

    class Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _send(self, code, payload):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/healthz":
                self._send(200, {"status": "ok", "ready": True})
            elif self.path == "/stats":
                with lock:
                    a = active[0]
                self._send(200, {"slots_active": min(a, num_slots),
                                 "queue_depth": max(a - num_slots, 0),
                                 "num_slots": num_slots,
                                 "draining": False})
            else:
                self._send(404, {"error": "not found"})

        def do_POST(self):
            if not self.path.startswith("/api/"):
                self._send(404, {"error": "not found"})
                return
            length = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(length) or b"{}")
            ids = [int(t) for t in req["input_text"].split()]
            n = int(req.get("max_new_tokens") or default_new_tokens)
            with lock:
                active[0] += 1
            try:
                with sem:
                    time.sleep(n * token_s)
            finally:
                with lock:
                    active[0] -= 1
            self._send(200, {"result": _fake_result(ids, n),
                             "request_id": req.get("request_id"),
                             "ttft_s": 0.0,
                             "finish_reason": "length"})

    server = http.server.ThreadingHTTPServer((host, port), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, thread


# ---- real replica subprocess (`--replica`) --------------------------

def replica_main(port: int) -> None:
    """Subprocess entry: random-init llama (serve-bench's default
    weight-memory-bound shape) + continuous engine + stdlib api server
    with warmup gating and SIGTERM drain — a faithful single replica."""
    import jax
    import jax.numpy as jnp

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       _start_warmup_thread,
                                       build_stdlib_server,
                                       create_continuous_engine,
                                       install_drain_handler)
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.pipelines.text_generation import Pipeline

    buckets = _buckets()
    new_tokens = _env("NEW_TOKENS", 48)
    config = LlamaConfig(
        vocab_size=_env("VOCAB", 4096),
        hidden_size=_env("HIDDEN", 1024),
        intermediate_size=_env("INTER", 2816),
        num_hidden_layers=_env("LAYERS", 4),
        num_attention_heads=_env("HEADS", 8),
        max_position_embeddings=buckets[-1] + new_tokens,
        dtype="float32")
    model = LlamaForCausalLM(config)
    params = jax.jit(lambda r: model.init(
        r, jnp.zeros((1, 8), jnp.int32))["params"])(
        jax.random.PRNGKey(_env("SEED", 0)))
    pipe = Pipeline(module=model, params=params,
                    tokenizer=_IntTokenizer(),
                    max_new_tokens=new_tokens, eos_token_id=None,
                    pad_token_id=0)
    engine = create_continuous_engine(
        pipe, {"num_slots": _env("SLOTS", 2), "buckets": buckets,
               "max_new_tokens": new_tokens, "max_queue": 512})
    server_cfg = ServerConfig(host="127.0.0.1", port=port,
                              engine="continuous")
    pipeline_cfg = PipelineConfig(task="text_generation")
    ready = _start_warmup_thread(server_cfg, pipeline_cfg, pipe, engine)
    draining = threading.Event()
    server = build_stdlib_server(server_cfg, pipeline_cfg,
                                 pipeline=pipe, engine=engine,
                                 ready=ready, draining=draining)
    install_drain_handler(server, draining, engine=engine)
    print(f"[fleet-bench] replica on 127.0.0.1:{port}", flush=True)
    server.serve_forever()


def _spawn_real_replicas(n: int, base_port: int
                         ) -> Tuple[List[str], list]:
    procs, targets = [], []
    for i in range(n):
        port = base_port + i
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "fengshen_tpu.fleet.bench",
             "--replica", "--port", str(port)], env=replica_env(i)))
        targets.append(f"127.0.0.1:{port}")
    return targets, procs


# ---- the driver -----------------------------------------------------

def _make_router(targets, timeout_s: float = 180.0,
                 poll_interval_s: float = 0.2) -> FleetRouter:
    """Router over `targets`, polled until every replica is healthy
    (replica warmup bounds the wait)."""
    router = FleetRouter(FleetConfig(
        replicas=targets, max_retries=3, breaker_threshold=2,
        breaker_cooldown_s=2.0, recovery_probes=1,
        poll_interval_s=poll_interval_s, request_timeout_s=300.0))
    deadline = time.monotonic() + timeout_s
    while router.healthy_count() < len(targets):
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"replicas not healthy after {timeout_s}s: "
                f"{router.fleet_state()}")
        router.poll_once()
        time.sleep(0.2)
    router.start_polling()
    return router


def _drive(router: FleetRouter, prompts: List[str], new_tokens: int,
           width: int,
           kill: Optional[Tuple[int, Callable[[], None]]] = None
           ) -> dict:
    """Push every prompt through the router from a `width`-wide pool;
    with `kill=(after, fn)`, fn fires once `after` responses landed."""
    results: List[Optional[str]] = [None] * len(prompts)
    failed: List[Tuple[int, int, dict]] = []
    lock = threading.Lock()
    done = [0]
    killed = [False]

    def one(i: int) -> None:
        status, body = router.route_generate(
            {"input_text": prompts[i], "max_new_tokens": new_tokens})
        with lock:
            done[0] += 1
            fire = (kill is not None and not killed[0]
                    and done[0] >= kill[0])
            if fire:
                killed[0] = True
        if fire:
            kill[1]()
        if status == 200:
            results[i] = body["result"]
        else:
            with lock:
                failed.append((i, status, body))

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=width) as pool:
        list(pool.map(one, range(len(prompts))))
    dt = time.perf_counter() - t0
    tokens = sum(len(r.split()) for r in results if r)
    return {"seconds": dt, "tokens": tokens,
            "tokens_per_sec": tokens / dt if dt > 0 else 0.0,
            "results": results, "failed": failed}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        prog="python -m fengshen_tpu.fleet.bench")
    parser.add_argument("--replica", action="store_true",
                        help="run as a bench replica subprocess")
    parser.add_argument("--port", type=int, default=0)
    args = parser.parse_args(argv)
    if args.replica:
        replica_main(args.port)
        return

    n = _env("REPLICAS", 3)
    slots = _env("SLOTS", 2)
    new_tokens = _env("NEW_TOKENS", 48)
    n_req = max(_env("REQUESTS", 6 * n * slots), 2)
    fake = _env("FAKE", 0) == 1
    kill_enabled = _env("KILL", 1) == 1 and n > 1
    kill_after = _env("KILL_AFTER", max(n_req // 4, 1))
    buckets = _buckets()
    width = max(2 * n * slots, 4)

    import random as _random
    rng = _random.Random(_env("SEED", 0))
    prompt_len = max(buckets[0] // 2, 1)
    prompts = [" ".join(str(rng.randint(3, 95))
                        for _ in range(prompt_len))
               for _ in range(n_req)]

    procs: list = []
    fake_servers: list = []
    if fake:
        token_s = float(os.environ.get("FLEET_BENCH_FAKE_TOKEN_S",
                                       "0.002"))
        targets = []
        for _ in range(n):
            server, _t = start_fake_replica(slots, token_s, new_tokens)
            fake_servers.append(server)
            targets.append("127.0.0.1:%d" % server.server_address[1])
    else:
        targets, procs = _spawn_real_replicas(
            n, _env("BASE_PORT", 8190))

    try:
        # 1. baseline: the fleet reduced to ONE replica
        r1 = _make_router(targets[:1])
        single = _drive(r1, prompts, new_tokens, width=max(2 * slots,
                                                           2))
        r1.stop()
        # 2. the fleet: same requests, N replicas
        rn = _make_router(targets)
        full = _drive(rn, prompts, new_tokens, width=width)
        rn.stop()
        # 3. kill rung: replica #1 dies mid-run; zero failures allowed
        kill_section = {"enabled": False}
        if kill_enabled:
            # poll slower than the rung lasts: the router must discover
            # the death through a FAILED REQUEST (breaker + retry), not
            # through a lucky health poll — otherwise `retries >= 1` is
            # a race against the poll thread
            rk = _make_router(targets, poll_interval_s=60.0)

            def kill_victim():
                if fake:
                    fake_servers[1].shutdown()
                    fake_servers[1].server_close()
                else:
                    procs[1].kill()     # SIGKILL: the harsh path — no
                    #   drain, in-flight requests die with it
                print(f"[fleet-bench] killed replica {targets[1]}",
                      flush=True)

            killrun = _drive(rk, prompts, new_tokens, width=width,
                             kill=(kill_after, kill_victim))
            retries = sum(rk.retries_total().values())
            rk.stop()
            kill_section = {
                "enabled": True,
                "killed": targets[1],
                "after_responses": kill_after,
                "failed": len(killrun["failed"]),
                "completed": sum(1 for r in killrun["results"]
                                 if r is not None),
                "retries": retries,
                "token_identical":
                    killrun["results"] == full["results"],
            }

        tps1 = single["tokens_per_sec"]
        tpsn = full["tokens_per_sec"]
        backend = "fake" if fake else replica_backend(targets[0])
        _emit({
            "metric": "fleet_router_tokens_per_sec",
            "value": round(tpsn, 1),
            "unit": "tokens/s",
            "vs_baseline": round(tpsn / tps1, 3) if tps1 > 0 else 0.0,
            "mode": "fleet",
            # the comparison identity: benchdiff never compares fleet
            # rows across different replica counts
            "replicas": n,
            "num_slots": slots,
            "requests": n_req,
            "new_tokens": new_tokens,
            "tokens_per_sec_1": round(tps1, 1),
            "failed": len(single["failed"]) + len(full["failed"]),
            "token_identical_n_vs_1":
                full["results"] == single["results"],
            "kill": kill_section,
            "fake": fake,
            "backend": backend,
        })
    finally:
        for server in fake_servers:
            try:
                server.shutdown()
                server.server_close()
            except OSError:
                pass
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


if __name__ == "__main__":
    main()
