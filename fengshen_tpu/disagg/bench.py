"""Disaggregation microbench: prefill/decode-split fleet tokens/s vs a
homogeneous fleet, plus the adopt-decline fallback rung.

    make serve-bench-disagg
    DISAGG_BENCH_PREFILL=2 DISAGG_BENCH_DECODE=2 \
        python -m fengshen_tpu.disagg.bench

Three rungs over ONE mixed long-prompt/short-decode request set
(docs/disaggregation.md):

1. **homogeneous**: `HOMOGENEOUS` both-phase replicas behind a
   `FleetRouter` → `tokens_per_sec_homogeneous` (the baseline);
2. **disagg**: `PREFILL` prefill-tier + `DECODE` decode-tier replicas
   behind the same router — phase-aware placement primes each lane on
   the prefill tier, pushes its KV to the decode tier, and the router
   collects the decode tail (`value`; the acceptance bar is
   disagg >= homogeneous on this workload shape). Outputs must be
   token-identical to rung 1's;
3. **fallback** (fake lane only): the same disagg topology with every
   decode replica DECLINING adoption — every request must still answer
   200 with token-identical output (local prefill-and-decode on the
   originating replica), and the fallback count must equal the request
   count.

One BENCH-schema JSON line with the **topology in the row**
(`"topology": "prefill=P,decode=D"`): benchdiff folds topology into
the comparison identity, so disaggregated rounds never diff against
homogeneous or differently-split ones.

`FLEET_BENCH_FAKE=1` (or `DISAGG_BENCH_FAKE=1`) swaps the replicas for
in-process fake servers (pure stdlib, no jax) whose cost model keeps
the one thing the bench measures: a both-phase replica pays a
**phase-switch interference cost** on every prefill (the running
decode batch stalls while the prefill monopolizes the chip — the
exact cost disaggregation removes), while a prefill-tier replica pays
raw prefill only and a decode-tier replica's batch is never
interrupted. The fakes speak the full transfer-plane shape (`PUT` /
`GET` / `DELETE /kv/<id>`, adopt acks, declines), so the REAL router +
placement policy + redirect/collect path is exercised end to end in
the fast-lane smoke test (`tests/test_disagg_bench_smoke.py`).

Env knobs (DISAGG_BENCH_*, falling back to FLEET_BENCH_* where both
exist): PREFILL, DECODE, HOMOGENEOUS, REQUESTS, NEW_TOKENS, SLOTS,
PROMPT_LEN, FAKE, FAKE_TOKEN_S, FAKE_PREFILL_S (per prompt token),
FAKE_SWITCH_S, BASE_PORT, SEED, plus fleet.bench's model-shape knobs
for the real-replica path (VOCAB / HIDDEN / INTER / LAYERS / HEADS /
BUCKETS).
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
import urllib.request
from typing import List, Tuple

from fengshen_tpu.fleet.bench import (_IntTokenizer, _buckets, _drive,
                                      _emit, _fake_result,
                                      _make_router)
from fengshen_tpu.fleet.launcher import replica_backend, replica_env


def _env(name: str, default: int) -> int:
    v = os.environ.get(f"DISAGG_BENCH_{name}",
                       os.environ.get(f"FLEET_BENCH_{name}"))
    return default if v is None else int(v)


def _fenv(name: str, default: float) -> float:
    v = os.environ.get(f"DISAGG_BENCH_{name}",
                       os.environ.get(f"FLEET_BENCH_{name}"))
    return default if v is None else float(v)


# ---- fake phase replicas (the harness-smoke fast lane) --------------

def _fake_push(push_to: str, rid: str, ids: List[int],
               n: int) -> bool:
    """The fake prefill side's KV push: same verb + path + ack contract
    as the real transfer plane, fake payload (there is no engine)."""
    body = json.dumps({"request_id": rid, "ids": ids, "n": n}).encode()
    req = urllib.request.Request(
        push_to.rstrip("/") + f"/kv/{rid}", data=body, method="PUT",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=10.0) as r:
            return bool(json.loads(r.read()).get("adopted"))
    except Exception:  # noqa: BLE001 — any push failure = fall back
        return False


def start_fake_phase_replica(phase: str, num_slots: int,
                             token_s: float, prefill_per_tok_s: float,
                             switch_s: float, default_new_tokens: int,
                             decline: bool = False,
                             host: str = "127.0.0.1", port: int = 0):
    """In-process fake replica speaking the api + transfer surface for
    one serving phase. Cost model: prefill monopolizes the chip
    (exclusive lock, `len(prompt) * prefill_per_tok_s`), PLUS
    `switch_s` interference on a both-phase replica (the stalled
    decode batch); decode sleeps `n * token_s` gated by a
    num_slots-wide semaphore and is never interrupted. `decline=True`
    turns a decode replica into an adopt-decliner (the fallback rung).
    Returns (server, thread, counters)."""
    chip = threading.Lock()
    sem = threading.BoundedSemaphore(num_slots)
    lock = threading.Lock()
    active = [0]
    counters = {"fallbacks": 0, "redirects": 0, "adopted": 0,
                "declined": 0}
    adopted: dict = {}

    def decode_sleep(n: int) -> None:
        with sem:
            time.sleep(n * token_s)

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
                self._send(200, {"status": "ok", "ready": True})
            elif self.path == "/stats":
                with lock:
                    a = active[0]
                self._send(200, {"slots_active": min(a, num_slots),
                                 "queue_depth": max(a - num_slots, 0),
                                 "num_slots": num_slots,
                                 "draining": False,
                                 "phase": phase})
            elif self.path.startswith("/kv/"):
                rid = self.path[len("/kv/"):]
                with lock:
                    entry = adopted.get(rid)
                if entry is None:
                    self._send(404, {"error": "unknown"})
                    return
                if not entry["event"].wait(timeout=30.0):
                    self._send(504, {"error": "still decoding"})
                    return
                with lock:
                    adopted.pop(rid, None)
                self._send(200, {"result": entry["result"],
                                 "request_id": rid, "ttft_s": 0.0,
                                 "finish_reason": "length",
                                 "adopted": True})
            else:
                self._send(404, {"error": "not found"})

        def do_POST(self):
            if not self.path.startswith("/api/"):
                self._send(404, {"error": "not found"})
                return
            req = self._read()
            ids = [int(t) for t in req["input_text"].split()]
            n = int(req.get("max_new_tokens") or default_new_tokens)
            rid = req.get("request_id")
            push_to = req.get("disagg_push_to")
            with lock:
                active[0] += 1
            try:
                cost = len(ids) * prefill_per_tok_s
                if phase == "both":
                    # interference: this prefill preempted a running
                    # decode batch — the cost disaggregation removes
                    cost += switch_s
                with chip:
                    time.sleep(cost)
                if push_to:
                    if _fake_push(push_to, rid, ids, n):
                        with lock:
                            counters["redirects"] += 1
                        self._send(200, {"disagg_redirect": True,
                                         "request_id": rid,
                                         "target": push_to})
                        return
                    with lock:
                        counters["fallbacks"] += 1
                decode_sleep(n)
                self._send(200, {"result": _fake_result(ids, n),
                                 "request_id": rid, "ttft_s": 0.0,
                                 "finish_reason": "length"})
            finally:
                with lock:
                    active[0] -= 1

        def do_PUT(self):
            if not self.path.startswith("/kv/"):
                self._send(404, {"error": "not found"})
                return
            rid = self.path[len("/kv/"):]
            payload = self._read()
            if decline or phase == "prefill":
                with lock:
                    counters["declined"] += 1
                self._send(409, {"adopted": False,
                                 "reason": "injected" if decline
                                 else "wrong_phase"})
                return
            entry = {"event": threading.Event(), "result": None}
            with lock:
                adopted[rid] = entry
                counters["adopted"] += 1

            def run():
                decode_sleep(int(payload["n"]))
                entry["result"] = _fake_result(
                    [int(t) for t in payload["ids"]],
                    int(payload["n"]))
                entry["event"].set()

            threading.Thread(target=run, daemon=True).start()
            self._send(200, {"adopted": True, "request_id": rid})

        def do_DELETE(self):
            if not self.path.startswith("/kv/"):
                self._send(404, {"error": "not found"})
                return
            rid = self.path[len("/kv/"):]
            with lock:
                cancelled = adopted.pop(rid, None) is not None
            self._send(200, {"cancelled": cancelled})

    server = http.server.ThreadingHTTPServer((host, port), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, thread, counters


def _start_fake_fleet(phases: List[str], slots: int, token_s: float,
                      prefill_per_tok_s: float, switch_s: float,
                      new_tokens: int, decline_decode: bool = False
                      ) -> Tuple[List[str], list, List[dict]]:
    targets, servers, counters = [], [], []
    for phase in phases:
        server, _t, c = start_fake_phase_replica(
            phase, slots, token_s, prefill_per_tok_s, switch_s,
            new_tokens,
            decline=(decline_decode and phase == "decode"))
        servers.append(server)
        counters.append(c)
        targets.append("127.0.0.1:%d" % server.server_address[1])
    return targets, servers, counters


def _stop_fakes(servers) -> None:
    for server in servers:
        try:
            server.shutdown()
            server.server_close()
        except OSError:
            pass


# ---- real replica subprocess (`--replica --phase X`) ----------------

def replica_main(port: int, phase: str) -> None:
    """Subprocess entry: the fleet bench's random-init llama replica
    plus a `DisaggCoordinator` and a configured serving phase — a
    faithful prefill- or decode-tier member."""
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
                              engine="continuous", phase=phase)
    pipeline_cfg = PipelineConfig(task="text_generation")
    ready = _start_warmup_thread(server_cfg, pipeline_cfg, pipe, engine)
    draining = threading.Event()
    server = build_stdlib_server(server_cfg, pipeline_cfg,
                                 pipeline=pipe, engine=engine,
                                 ready=ready, draining=draining,
                                 disagg=disagg)
    install_drain_handler(server, draining, engine=engine)
    print(f"[disagg-bench] {phase} replica on 127.0.0.1:{port}",
          flush=True)
    server.serve_forever()


def _spawn_real_replicas(phases: List[str], base_port: int
                         ) -> Tuple[List[str], list]:
    procs, targets = [], []
    for i, phase in enumerate(phases):
        port = base_port + i
        # chips are numbered like the ports: the homogeneous and the
        # disaggregated fleet are alive together and share no chip
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "fengshen_tpu.disagg.bench",
             "--replica", "--port", str(port), "--phase", phase],
            env=replica_env(port - _env("BASE_PORT", 8260))))
        targets.append(f"127.0.0.1:{port}")
    return targets, procs


# ---- the driver -----------------------------------------------------

def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        prog="python -m fengshen_tpu.disagg.bench")
    parser.add_argument("--replica", action="store_true",
                        help="run as a bench replica subprocess")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--phase", type=str, default="both")
    args = parser.parse_args(argv)
    if args.replica:
        replica_main(args.port, args.phase)
        return

    n_prefill = _env("PREFILL", 2)
    n_decode = _env("DECODE", 2)
    n_homog = _env("HOMOGENEOUS", 3)
    slots = _env("SLOTS", 4)
    new_tokens = _env("NEW_TOKENS", 8)       # short decode tails …
    prompt_len = _env("PROMPT_LEN", 32)      # … behind long prompts
    n_req = max(_env("REQUESTS", 24), 2)
    fake = _env("FAKE", 0) == 1
    token_s = _fenv("FAKE_TOKEN_S", 0.005)
    prefill_per_tok_s = _fenv("FAKE_PREFILL_S", 0.001)
    switch_s = _fenv("FAKE_SWITCH_S", 0.05)
    width = max(2 * (n_prefill + n_decode) * slots, 8)

    import random as _random
    rng = _random.Random(_env("SEED", 0))
    prompts = [" ".join(str(rng.randint(3, 95))
                        for _ in range(prompt_len))
               for _ in range(n_req)]

    disagg_phases = (["prefill"] * n_prefill
                     + ["decode"] * n_decode)
    topology = f"prefill={n_prefill},decode={n_decode}"

    all_servers: list = []
    procs: list = []
    try:
        # 1. homogeneous baseline: N both-phase replicas
        if fake:
            h_targets, h_servers, _ = _start_fake_fleet(
                ["both"] * n_homog, slots, token_s,
                prefill_per_tok_s, switch_s, new_tokens)
            all_servers += h_servers
        else:
            h_targets, h_procs = _spawn_real_replicas(
                ["both"] * n_homog, _env("BASE_PORT", 8260))
            procs += h_procs
        rh = _make_router(h_targets)
        homog = _drive(rh, prompts, new_tokens, width=width)
        rh.stop()
        if fake:
            _stop_fakes(h_servers)

        # 2. disaggregated: prefill tier + decode tier, REAL router
        #    placement + KV push + redirect/collect end to end
        if fake:
            d_targets, d_servers, d_counters = _start_fake_fleet(
                disagg_phases, slots, token_s, prefill_per_tok_s,
                switch_s, new_tokens)
            all_servers += d_servers
        else:
            d_targets, d_procs = _spawn_real_replicas(
                disagg_phases, _env("BASE_PORT", 8260) + n_homog)
            procs += d_procs
        rd = _make_router(d_targets)
        disagg = _drive(rd, prompts, new_tokens, width=width)
        state = rd.fleet_state()
        rd.stop()
        if fake:
            _stop_fakes(d_servers)
            redirects = sum(c["redirects"] for c in d_counters)
        else:
            redirects = None

        # 3. fallback rung (fake lane): decode tier declines every
        #    adoption — zero client-visible errors allowed
        fallback_section = {"enabled": False}
        if fake:
            f_targets, f_servers, f_counters = _start_fake_fleet(
                disagg_phases, slots, token_s, prefill_per_tok_s,
                switch_s, new_tokens, decline_decode=True)
            all_servers += f_servers
            rf = _make_router(f_targets)
            fb = _drive(rf, prompts, new_tokens, width=width)
            rf.stop()
            _stop_fakes(f_servers)
            fallback_section = {
                "enabled": True,
                "failed": len(fb["failed"]),
                "completed": sum(1 for r in fb["results"]
                                 if r is not None),
                "fallbacks": sum(c["fallbacks"] for c in f_counters),
                "declined": sum(c["declined"] for c in f_counters),
                "token_identical": fb["results"] == homog["results"],
            }

        tps_h = homog["tokens_per_sec"]
        tps_d = disagg["tokens_per_sec"]
        backend = "fake" if fake else replica_backend(h_targets[0])
        _emit({
            "metric": "disagg_tokens_per_sec",
            "value": round(tps_d, 1),
            "unit": "tokens/s",
            "vs_baseline": round(tps_d / tps_h, 3) if tps_h > 0
            else 0.0,
            "mode": "disagg",
            # the comparison identity: benchdiff never compares rows
            # across replica counts OR phase topologies
            "replicas": n_prefill + n_decode,
            "topology": topology,
            "router_topology": state.get("topology"),
            "homogeneous_replicas": n_homog,
            "tokens_per_sec_homogeneous": round(tps_h, 1),
            "num_slots": slots,
            "requests": n_req,
            "new_tokens": new_tokens,
            "prompt_len": prompt_len,
            "failed": len(homog["failed"]) + len(disagg["failed"]),
            "redirects": redirects,
            "token_identical_disagg_vs_homogeneous":
                disagg["results"] == homog["results"],
            "fallback": fallback_section,
            "fake": fake,
            "backend": backend,
        })
    finally:
        _stop_fakes(all_servers)
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
