"""Preemption-tolerance microbench: SIGTERM-mid-decode (live lane
evacuation) and SIGKILL-mid-decode (resume-from-token-k) drills.

    make serve-bench-evac
    FLEET_BENCH_FAKE=1 python -m fengshen_tpu.fleet.evac_bench

Three rungs over ONE request set against a 3-replica fleet — the
router fronts replicas A and C while B stands by as A's configured
evacuation peer (docs/fault_tolerance.md "Preemption runbook"):

1. **baseline**: undisturbed run → reference outputs + tokens/s;
2. **sigterm drill**: replica A receives its preemption notice after
   `PREEMPT_AFTER` responses — it drains, EVACUATES its in-flight
   lanes to B (KV push + commit-journal cursors), and the blocked
   POSTs answer disagg-style redirects the router re-collects from B.
   Every request must answer 200, token-identical to rung 1, with at
   least one lane adopted and zero locally-regenerated retries;
3. **sigkill drill**: the same preemption, then B (the adopter) is
   hard-killed after `GRACE_S`. The router's collect fails, it mines
   the fleet's commit journals (`GET /partial/<id>` — A, still
   draining, serves the evacuated prefix) and re-places each request
   on C with `resume_tokens`, which prefills prompt+prefix and
   decodes only the remainder. Every request must answer 200,
   token-identical, with `resumed >= 1` and ZERO journal misses (no
   request regenerated from token 0); the row carries the recovered
   request overhead vs regenerate-from-zero
   (`1 - resumed_tokens / (resumed * new_tokens)` saved).

One BENCH-schema JSON line with ``"drill": "preempt"`` in the row:
benchdiff folds the drill into the comparison identity, so evacuation
rounds never diff against undisturbed fleet rounds.

`FLEET_BENCH_FAKE=1` (or `EVAC_BENCH_FAKE=1`) swaps the replicas for
in-process fakes (pure stdlib, no jax) that speak the full surface —
api + /stats draining + `PUT/GET /kv/<id>` + `GET /partial/<id>` —
with a deterministic token function, so the REAL router's redirect /
collect / journal-consult / resume path is exercised end to end in
seconds (`tests/test_evac_bench_smoke.py`). The adopter B decodes
slower than A/C (`FAKE_ADOPTER_FACTOR`) so the sigkill drill reliably
catches evacuated lanes mid-decode.

Env knobs (EVAC_BENCH_*, falling back to FLEET_BENCH_*): REQUESTS,
NEW_TOKENS, SLOTS, PROMPT_LEN, PREEMPT_AFTER, GRACE_S, FAKE,
FAKE_TOKEN_S, FAKE_ADOPTER_FACTOR, BASE_PORT, SEED, plus fleet.bench's
model-shape knobs for the real-replica path.
"""

from __future__ import annotations

import argparse
import http.server
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from typing import List, Optional

from fengshen_tpu.fleet.bench import (_buckets, _drive, _emit,
                                      _IntTokenizer, _make_router)
from fengshen_tpu.fleet.launcher import replica_backend, replica_env


def _env(name: str, default: int) -> int:
    v = os.environ.get(f"EVAC_BENCH_{name}",
                       os.environ.get(f"FLEET_BENCH_{name}"))
    return default if v is None else int(v)


def _fenv(name: str, default: float) -> float:
    v = os.environ.get(f"EVAC_BENCH_{name}",
                       os.environ.get(f"FLEET_BENCH_{name}"))
    return default if v is None else float(v)


def _resume_totals(router) -> dict:
    """{outcome: count} over the router's fstpu_resume_total."""
    return {values[0]: int(child.value)
            for values, child in router._c_resume.children()}


# ---- fake evac replicas (the harness-smoke fast lane) ---------------

def _fake_tok(s: int, i: int, vocab: int = 97) -> int:
    """Position-deterministic token: matches fleet.bench._fake_result,
    so a resumed tail is token-identical to the undisturbed run by
    construction — exactly the greedy-decode property the real resume
    path guarantees."""
    return (s + i) % vocab


def start_fake_evac_replica(num_slots: int, token_s: float,
                            default_new_tokens: int,
                            host: str = "127.0.0.1", port: int = 0
                            ) -> dict:
    """In-process fake replica speaking the full evacuation surface:
    generate + /stats (with the draining flag) + adopt (`PUT /kv`) +
    collect (`GET /kv`) + commit journal (`GET /partial`). Returns a
    control dict: url/target/server/counters plus `drain(peer_urls)` —
    the preemption notice: flips draining, pushes every in-flight lane
    with >= 1 committed token to the first adopting peer (the rest
    finish locally, never an error)."""
    sem = threading.BoundedSemaphore(num_slots)
    lock = threading.Lock()
    active = [0]
    draining = [False]
    journal: dict = {}   # rid -> {"ids","n","tokens","state","result"}
    lanes: dict = {}     # rid -> {"cut": adopter url or None}
    adopted: dict = {}   # rid -> {"event", "result"}
    killed = [False]     # SIGKILL: sever in-flight responses too
    counters = {"adopted": 0, "evacuated": 0, "local_finish": 0,
                "resumed": 0}

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

        def _read(self):
            length = int(self.headers.get("Content-Length", 0))
            return json.loads(self.rfile.read(length) or b"{}")

        def do_GET(self):
            if self.path == "/healthz":
                if draining[0]:
                    self._send(503, {"ready": False,
                                     "reason": "draining"})
                else:
                    self._send(200, {"status": "ok", "ready": True})
            elif self.path == "/stats":
                with lock:
                    a = active[0]
                self._send(200, {"slots_active": min(a, num_slots),
                                 "queue_depth": max(a - num_slots, 0),
                                 "num_slots": num_slots,
                                 "draining": draining[0],
                                 "phase": "both"})
            elif self.path.startswith("/partial/"):
                rid = self.path[len("/partial/"):]
                with lock:
                    entry = journal.get(rid)
                    entry = None if entry is None else dict(
                        entry, tokens=list(entry["tokens"]))
                if entry is None:
                    self._send(404, {"error": "unknown"})
                    return
                out = {"request_id": rid, "state": entry["state"],
                       "generated_tokens": len(entry["tokens"]),
                       "tokens": entry["tokens"],
                       "max_new_tokens": entry["n"]}
                if entry["state"] == "finished":
                    out["result"] = entry["result"]
                    out["finish_reason"] = "length"
                    out["ttft_s"] = 0.0
                self._send(200, out)
            elif self.path.startswith("/kv/"):
                rid = self.path[len("/kv/"):]
                with lock:
                    entry = adopted.get(rid)
                if entry is None:
                    self._send(404, {"error": "unknown"})
                    return
                deadline = time.monotonic() + 30.0
                while not entry["event"].wait(timeout=0.02):
                    if killed[0]:
                        # a real SIGKILL severs the long-poll
                        # mid-flight; the router must see a reset,
                        # not a clean response
                        self.close_connection = True
                        try:
                            self.connection.shutdown(socket.SHUT_RDWR)
                        except OSError:
                            pass
                        return
                    if time.monotonic() >= deadline:
                        self._send(504, {"error": "still decoding"})
                        return
                with lock:
                    adopted.pop(rid, None)
                self._send(200, {"result": entry["result"],
                                 "request_id": rid, "ttft_s": 0.0,
                                 "finish_reason": "length"})
            else:
                self._send(404, {"error": "not found"})

        def do_POST(self):
            if not self.path.startswith("/api/"):
                self._send(404, {"error": "not found"})
                return
            req = self._read()
            if draining[0]:
                self._send(503, {"error": "replica draining",
                                 "reason": "draining"})
                return
            ids = [int(t) for t in req["input_text"].split()]
            n = int(req.get("max_new_tokens") or default_new_tokens)
            rid = str(req.get("request_id"))
            resume = [int(t) for t in (req.get("resume_tokens") or [])]
            committed = list(resume)
            lane = {"cut": None}
            s = sum(ids)
            with lock:
                active[0] += 1
                lanes[rid] = lane
                journal[rid] = {"ids": ids, "n": n,
                                "tokens": list(committed),
                                "state": "running", "result": None}
                if resume:
                    counters["resumed"] += 1
            try:
                target = None
                with sem:
                    for i in range(len(committed), n):
                        time.sleep(token_s)
                        with lock:
                            target = lane["cut"]
                            if target is not None:
                                break
                            committed.append(_fake_tok(s, i))
                            journal[rid]["tokens"] = list(committed)
                if target is not None:
                    self._send(200, {"disagg_redirect": True,
                                     "request_id": rid,
                                     "target": target,
                                     "evacuated": True})
                    return
                result = " ".join(str(t) for t in committed)
                with lock:
                    journal[rid].update(state="finished",
                                        result=result)
                    if draining[0]:
                        counters["local_finish"] += 1
                self._send(200, {"result": result, "request_id": rid,
                                 "ttft_s": 0.0,
                                 "finish_reason": "length"})
            finally:
                with lock:
                    active[0] -= 1
                    lanes.pop(rid, None)

        def do_PUT(self):
            if not self.path.startswith("/kv/"):
                self._send(404, {"error": "not found"})
                return
            rid = self.path[len("/kv/"):]
            payload = self._read()
            if draining[0]:
                self._send(409, {"adopted": False,
                                 "reason": "draining"})
                return
            ids = [int(t) for t in payload["ids"]]
            n = int(payload["n"])
            committed = [int(t) for t in payload["committed"]]
            entry = {"event": threading.Event(), "result": None}
            with lock:
                adopted[rid] = entry
                counters["adopted"] += 1
                # the adopter journals the lane too: a hard-killed
                # source leaves the prefix readable here
                journal[rid] = {"ids": ids, "n": n,
                                "tokens": list(committed),
                                "state": "running", "result": None}
            s = sum(ids)

            def run():
                with sem:
                    for i in range(len(committed), n):
                        time.sleep(token_s)
                        if killed[0]:
                            # SIGKILL: the adopted lane dies
                            # uncommitted — only the source's journal
                            # prefix survives
                            return
                        committed.append(_fake_tok(s, i))
                        with lock:
                            journal[rid]["tokens"] = list(committed)
                entry["result"] = " ".join(str(t) for t in committed)
                with lock:
                    journal[rid].update(state="finished",
                                        result=entry["result"])
                entry["event"].set()

            threading.Thread(target=run, daemon=True).start()
            self._send(200, {"adopted": True, "request_id": rid})

    server = http.server.ThreadingHTTPServer((host, port), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    url = "http://127.0.0.1:%d" % server.server_address[1]

    def drain(peer_urls: List[str]) -> None:
        draining[0] = True
        snapshot: list = []
        # a just-admitted lane has no committed token yet and cannot
        # be resumed, and a nearly-finished one wins the race against
        # its own cut; evacuate lanes with real work remaining and
        # give the decode loop a few ticks to surface one
        for _ in range(5):
            with lock:
                snapshot = [
                    (rid, dict(journal[rid],
                               tokens=list(journal[rid]["tokens"])))
                    for rid in list(lanes)
                    if 0 < len(journal.get(rid, {}).get("tokens", ()))
                    <= journal[rid]["n"] - 4]
            if snapshot:
                break
            time.sleep(2 * token_s)
        for rid, entry in snapshot:
            for peer in peer_urls:
                body = json.dumps(
                    {"request_id": rid, "ids": entry["ids"],
                     "n": entry["n"],
                     "committed": entry["tokens"]}).encode()
                req = urllib.request.Request(
                    peer.rstrip("/") + f"/kv/{rid}", data=body,
                    method="PUT",
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=10.0) as r:
                        ok = bool(json.loads(r.read()).get("adopted"))
                except Exception:  # noqa: BLE001 — push failure =
                    ok = False     # try the next peer / local finish
                if ok:
                    with lock:
                        lane = lanes.get(rid)
                        if lane is not None:
                            lane["cut"] = peer
                        journal[rid].update(
                            state="evacuated",
                            tokens=list(entry["tokens"]))
                        counters["evacuated"] += 1
                    break

    def kill() -> None:
        """Fake SIGKILL: refuse new connects AND sever in-flight
        long-polls, so the router sees resets, never clean answers."""
        killed[0] = True
        server.shutdown()
        server.server_close()

    return {"url": url,
            "target": "127.0.0.1:%d" % server.server_address[1],
            "server": server, "counters": counters, "drain": drain,
            "kill": kill}


def _stop_fake(*ctls) -> None:
    for ctl in ctls:
        try:
            ctl["server"].shutdown()
            ctl["server"].server_close()
        except OSError:
            pass


# ---- real replica subprocess (`--replica --peers ...`) --------------

def replica_main(port: int, peers: List[str]) -> None:
    """Subprocess entry: the fleet bench's random-init llama replica
    with a `DisaggCoordinator` and the drain handler wired for live
    evacuation — SIGTERM makes it push its in-flight lanes to
    `peers` before the idle wait."""
    import jax
    import jax.numpy as jnp

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       _start_warmup_thread,
                                       build_stdlib_server,
                                       create_continuous_engine,
                                       install_drain_handler)
    from fengshen_tpu.disagg.coordinator import DisaggCoordinator
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.pipelines.text_generation import Pipeline

    buckets = _buckets()
    new_tokens = _env("NEW_TOKENS", 16)
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
    disagg = DisaggCoordinator(engine, pipe)
    server_cfg = ServerConfig(host="127.0.0.1", port=port,
                              engine="continuous",
                              peers=tuple(peers))
    pipeline_cfg = PipelineConfig(task="text_generation")
    ready = _start_warmup_thread(server_cfg, pipeline_cfg, pipe, engine)
    draining = threading.Event()
    server = build_stdlib_server(server_cfg, pipeline_cfg,
                                 pipeline=pipe, engine=engine,
                                 ready=ready, draining=draining,
                                 disagg=disagg)
    install_drain_handler(server, draining, engine=engine,
                          disagg=disagg, peers=server_cfg.peers)
    print(f"[evac-bench] replica on 127.0.0.1:{port} "
          f"(peers={list(peers)})", flush=True)
    server.serve_forever()


def _spawn_fleet(base_port: int) -> tuple:
    """A, B, C subprocess replicas; A evacuates to B on drain."""
    ports = [base_port, base_port + 1, base_port + 2]
    peers = [f"http://127.0.0.1:{ports[1]}", "", ""]
    procs = []
    for i, (port, peer) in enumerate(zip(ports, peers)):
        cmd = [sys.executable, "-m", "fengshen_tpu.fleet.evac_bench",
               "--replica", "--port", str(port)]
        if peer:
            cmd += ["--peers", peer]
        procs.append(subprocess.Popen(cmd, env=replica_env(i)))
    targets = [f"127.0.0.1:{p}" for p in ports]
    return targets, procs


def _wait_healthy(target: str, timeout_s: float = 180.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(
                    f"http://{target}/healthz", timeout=2.0) as r:
                if r.status == 200:
                    return
        except Exception:  # noqa: BLE001 — still warming
            pass
        time.sleep(0.2)
    raise RuntimeError(f"replica {target} not healthy in {timeout_s}s")


def _reap(procs) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=15)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


# ---- the driver -----------------------------------------------------

def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        prog="python -m fengshen_tpu.fleet.evac_bench")
    parser.add_argument("--replica", action="store_true",
                        help="run as a bench replica subprocess")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--peers", type=str, default="")
    args = parser.parse_args(argv)
    if args.replica:
        replica_main(args.port,
                     [p for p in args.peers.split(",") if p])
        return

    slots = _env("SLOTS", 2)
    new_tokens = _env("NEW_TOKENS", 16)
    prompt_len = _env("PROMPT_LEN", 8)
    n_req = max(_env("REQUESTS", 24), 2)
    preempt_after = _env("PREEMPT_AFTER", max(n_req // 4, 1))
    grace_s = _fenv("GRACE_S", 0.05)
    fake = _env("FAKE", 0) == 1
    # slow enough that in-flight lanes are reliably mid-decode when
    # the preemption notice lands (the whole point of the drill)
    token_s = _fenv("FAKE_TOKEN_S", 0.02)
    adopter_factor = _fenv("FAKE_ADOPTER_FACTOR", 5.0)
    width = max(4 * slots, 8)

    import random as _random
    rng = _random.Random(_env("SEED", 0))
    prompts = [" ".join(str(rng.randint(3, 95))
                        for _ in range(prompt_len))
               for _ in range(n_req)]

    backends: set = set()

    def fresh_fleet(rung):
        """(router_targets, drain_a, kill_b, counters, cleanup)."""
        if fake:
            # sigterm rung: B decodes adopted lanes slowly but
            # finishes them (collect succeeds). sigkill rung: B is
            # effectively frozen, so every evacuated lane is still
            # mid-decode at the kill and MUST come back through
            # resume-from-token-k — the drill is deterministic
            adopter_s = (30.0 if rung == "sigkill"
                         else token_s * adopter_factor)
            a = start_fake_evac_replica(slots, token_s, new_tokens)
            b = start_fake_evac_replica(slots, adopter_s, new_tokens)
            c = start_fake_evac_replica(slots, token_s, new_tokens)

            return ([a["target"], c["target"]],
                    lambda: a["drain"]([b["url"]]), b["kill"],
                    {"adopted": b["counters"],
                     "source": a["counters"]},
                    lambda: _stop_fake(a, b, c))
        targets, procs = _spawn_fleet(_env("BASE_PORT", 8470))
        for t in targets:
            _wait_healthy(t)
        backends.add(replica_backend(targets[0]))
        return ([targets[0], targets[2]],
                lambda: procs[0].send_signal(signal.SIGTERM),
                lambda: procs[1].kill(), None, lambda: _reap(procs))

    sections = {}
    results = {}
    for rung in ("baseline", "sigterm", "sigkill"):
        targets, drain_a, kill_b, counters, cleanup = fresh_fleet(rung)
        try:
            # slow poll on the drill rungs: the router must learn of
            # the drain through 503-draining answers, deterministically
            router = _make_router(
                targets,
                poll_interval_s=0.2 if rung == "baseline" else 60.0)
            if rung == "baseline":
                trigger = None
            elif rung == "sigterm":
                trigger = drain_a
            else:
                def trigger():
                    drain_a()

                    def later():
                        time.sleep(grace_s)
                        kill_b()
                    threading.Thread(target=later,
                                     daemon=True).start()
            run = _drive(router, prompts, new_tokens, width=width,
                         kill=None if trigger is None
                         else (preempt_after, trigger))
            resume = _resume_totals(router)
            resume_tokens = int(router._c_resume_tokens.value())
            router.stop()
            results[rung] = run
            sections[rung] = {
                "failed": len(run["failed"]),
                "completed": sum(1 for r in run["results"]
                                 if r is not None),
                "tokens_per_sec": round(run["tokens_per_sec"], 1),
                "resume": resume,
                "resume_tokens": resume_tokens,
            }
            if counters is not None:
                sections[rung]["adopted"] = \
                    counters["adopted"]["adopted"]
                sections[rung]["evacuated"] = \
                    counters["source"]["evacuated"]
                sections[rung]["local_finish"] = \
                    counters["source"]["local_finish"]
        finally:
            cleanup()

    base, term, hard = (results["baseline"], results["sigterm"],
                        results["sigkill"])
    hard_resume = sections["sigkill"]["resume"]
    resumed = int(hard_resume.get("resumed", 0))
    resumed_tokens = int(sections["sigkill"]["resume_tokens"])
    backend = "fake" if fake else ",".join(sorted(backends))
    # recovered-request overhead vs regenerate-from-zero: the share of
    # a recovered request's tokens that had to be decoded AGAIN — 1.0
    # would mean the journal saved nothing, < 1.0 is the win
    overhead = (round(1.0 - resumed_tokens / (resumed * new_tokens), 3)
                if resumed else None)
    tps_b = base["tokens_per_sec"]
    tps_t = term["tokens_per_sec"]
    _emit({
        "metric": "evac_tokens_per_sec",
        "value": round(tps_t, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps_t / tps_b, 3) if tps_b > 0 else 0.0,
        "mode": "evac",
        # the comparison identity: a preemption drill is never diffed
        # against an undisturbed fleet round
        "drill": "preempt",
        "replicas": 3,
        "num_slots": slots,
        "requests": n_req,
        "new_tokens": new_tokens,
        "preempt_after": preempt_after,
        "tokens_per_sec_baseline": round(tps_b, 1),
        "failed": (len(base["failed"]) + len(term["failed"])
                   + len(hard["failed"])),
        "token_identical_sigterm": term["results"] == base["results"],
        "token_identical_sigkill": hard["results"] == base["results"],
        "resumed": resumed,
        "zero_regenerated": int(hard_resume.get("miss", 0)) == 0,
        "recovered_overhead_vs_regenerate": overhead,
        "sigterm": sections["sigterm"],
        "sigkill": sections["sigkill"],
        "fake": fake,
        "backend": backend,
    })


if __name__ == "__main__":
    main()
