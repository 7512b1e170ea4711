"""Fleet router (ISSUE 10, docs/fleet.md): health-gated multi-replica
serving with retries, backoff, circuit breaking, and graceful drain.

Two tiers:

- deterministic UNIT tests over an in-memory fake transport + manual
  clock + recorded sleeps (no jax, no sockets): placement, health
  gating with eased recovery, retry/backoff semantics, the breaker's
  open/half-open/close lifecycle, structured zero-healthy degradation,
  router drain, and the PYTHONHASHSEED-pinned `/fleet` debug JSON;
- INTEGRATION tests over three REAL stdlib api replicas (tiny llama,
  continuous engines) behind a `FleetFaultPlan`-wrapped transport: the
  acceptance pin — kill one replica mid-run, every greedy request
  still completes token-identical to a single sequential engine, zero
  dropped or duplicated responses, and `fstpu_fleet_retries_total`
  matches the injected fault count EXACTLY — plus the replica-side
  SIGTERM drain (healthz flips to draining-503 while an in-flight
  request completes; extends the PR-8 SIGTERM-chain coverage) and the
  request-id dedupe/reject hook the idempotent-safe retries rest on.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.fleet import (BROKEN, DRAINING, HEALTHY,
                                FleetConfig, FleetFaultPlan,
                                FleetRouter, TransportError,
                                UrllibTransport, healthz_payload)
from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.serving import (ContinuousBatchingEngine, Draining,
                                  DuplicateRequest, EngineConfig)
from fengshen_tpu.utils.generate import generate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- unit tier: fake transport, manual clock ----------------------------

class ManualClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeReplica:
    """One simulated replica the fake transport answers for."""

    def __init__(self, num_slots: int = 4):
        self.healthz = (200, {"status": "ok", "ready": True})
        self.stats = {"slots_active": 0, "queue_depth": 0,
                      "num_slots": num_slots, "draining": False}
        self.fail = None            # None | "connect" | "timeout"
        self.generate_code = 200
        self.requests = []          # bodies seen by /api/ POSTs

    def response(self, body):
        return self.generate_code, {
            "result": "ok", "request_id": body.get("request_id"),
            "finish_reason": "length"}


class FakeTransport:
    def __init__(self, replicas):
        self.replicas = replicas    # name -> FakeReplica

    def request(self, base_url, method, path, body, timeout_s):
        rep = self.replicas[base_url.split("://", 1)[1]]
        if rep.fail is not None:
            raise TransportError(f"injected {rep.fail}",
                                 sent=rep.fail == "timeout")
        if path == "/healthz":
            return rep.healthz
        if path == "/stats":
            return 200, rep.stats
        if method == "POST" and path.startswith("/api/"):
            rep.requests.append(body)
            return rep.response(body)
        return 404, {}


def _mk_router(names, replicas, clock=None, sleeps=None, **cfg):
    cfg.setdefault("recovery_probes", 1)
    cfg.setdefault("backoff_base_s", 0.05)
    cfg.setdefault("seed", 0)
    return FleetRouter(
        FleetConfig(replicas=names, **cfg),
        transport=FakeTransport(replicas),
        clock=clock or ManualClock(),
        sleep=(sleeps.append if sleeps is not None else lambda s: None))


def test_health_gating_and_eased_recovery():
    """Unprobed replicas are OUT; healthz 503 takes one out in a single
    poll; re-entry needs `recovery_probes` CONSECUTIVE healthy polls."""
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    router = _mk_router(("a:1", "b:2"), reps, recovery_probes=2)
    # unprobed: nothing routed, loud structured 503
    code, body = router.route_generate({"input_text": "1"})
    assert code == 503 and body["reason"] == "no_healthy_replicas"
    assert set(body["replicas"]) == {"a:1", "b:2"}
    router.poll_once()
    assert router.healthy_count() == 0      # streak 1 of 2
    router.poll_once()
    assert router.healthy_count() == 2
    # b drains (orderly 503): out after ONE poll, breaker untouched
    reps["b:2"].healthz = (503, {"ready": False, "reason": "draining"})
    router.poll_once()
    state = router.fleet_state()
    b = [r for r in state["replicas"] if r["name"] == "b:2"][0]
    assert b["state"] == DRAINING and b["reason"] == "draining"
    assert b["breaker"]["consecutive_failures"] == 0
    assert router.healthy_count() == 1
    # recovery is eased: one healthy poll is not enough
    reps["b:2"].healthz = (200, {"ready": True})
    router.poll_once()
    assert router.healthy_count() == 1
    router.poll_once()
    assert router.healthy_count() == 2


def test_stats_draining_routes_around_before_healthz():
    """engine.begin_drain() without the API-layer event: /stats flips
    `draining` while /healthz is still 200 — the poll must take the
    replica out orderly (no breaker charge) on that signal alone, and
    ease it back in once it stops reporting draining."""
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    router = _mk_router(("a:1", "b:2"), reps, recovery_probes=2)
    router.poll_once()
    router.poll_once()
    assert router.healthy_count() == 2
    reps["b:2"].stats = dict(reps["b:2"].stats, draining=True)
    router.poll_once()
    state = {r["name"]: r for r in router.fleet_state()["replicas"]}
    assert state["b:2"]["state"] == DRAINING
    assert state["b:2"]["reason"] == "draining"
    assert state["b:2"]["breaker"]["consecutive_failures"] == 0
    assert state["b:2"]["occupancy"]["draining_reported"] is True
    code, _ = router.route_generate({"input_text": "1"})
    assert code == 200
    assert [len(r.requests) for r in reps.values()] == [1, 0]
    # stops draining → eased re-entry, like any other recovery
    reps["b:2"].stats = dict(reps["b:2"].stats, draining=False)
    router.poll_once()
    assert router.healthy_count() == 1
    router.poll_once()
    assert router.healthy_count() == 2


def test_least_occupancy_pick_is_deterministic():
    """Least (slots_active+queue_depth+in_flight)/num_slots wins; ties
    break by replica index."""
    reps = {n: FakeReplica() for n in ("a:1", "b:2", "c:3")}
    reps["a:1"].stats.update(slots_active=3)
    reps["b:2"].stats.update(slots_active=1)
    reps["c:3"].stats.update(slots_active=1, queue_depth=2)
    router = _mk_router(("a:1", "b:2", "c:3"), reps)
    router.poll_once()
    code, _ = router.route_generate({"input_text": "1"})
    assert code == 200
    assert [len(r.requests) for r in reps.values()] == [0, 1, 0]
    # tie (fresh stats make b and c equal) → lowest index among ties
    reps["c:3"].stats.update(queue_depth=0)
    router.poll_once()
    router.route_generate({"input_text": "2"})
    assert [len(r.requests) for r in reps.values()] == [0, 2, 0]


def test_retry_on_connect_failure_lands_on_different_replica():
    """A connect failure retries on ANOTHER replica after a jittered
    backoff; the failed replica's breaker charges; the retry counter
    carries the reason."""
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    router = _mk_router(("a:1", "b:2"), reps, sleeps=(sleeps := []),
                        breaker_threshold=1, max_retries=2,
                        backoff_base_s=0.1)
    router.poll_once()
    reps["a:1"].fail = "connect"
    code, body = router.route_generate({"input_text": "1"})
    assert code == 200
    assert len(reps["b:2"].requests) == 1
    assert router.retries_total() == {"connect": 1}
    # jitter is seeded-uniform in [0.5, 1.0) x nominal
    assert len(sleeps) == 1 and 0.05 <= sleeps[0] < 0.1
    a = router.fleet_state()["replicas"][0]
    assert a["state"] == BROKEN and a["breaker"]["open"]
    # both attempts carried the SAME router-assigned request id — the
    # replica-side dedupe hook makes this retry idempotent-safe
    assert body["request_id"].startswith("fleet-")


def test_5xx_retries_and_503_is_orderly():
    """HTTP 500 charges the breaker and retries; HTTP 503 (the replica
    saying warming/draining) retries and leaves rotation WITHOUT
    charging the breaker."""
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    router = _mk_router(("a:1", "b:2"), reps, breaker_threshold=2,
                        max_retries=1)
    router.poll_once()
    reps["a:1"].generate_code = 500
    code, _ = router.route_generate({"input_text": "1"})
    assert code == 200 and len(reps["b:2"].requests) == 1
    assert router.retries_total() == {"http_500": 1}
    state = {r["name"]: r for r in router.fleet_state()["replicas"]}
    assert state["a:1"]["breaker"]["consecutive_failures"] == 1
    # now a 503: replica leaves rotation, breaker count RESETS (orderly)
    reps["a:1"].generate_code = 503
    router.poll_once()           # back to healthy first
    router.poll_once()
    code, _ = router.route_generate({"input_text": "2"})
    assert code == 200
    state = {r["name"]: r for r in router.fleet_state()["replicas"]}
    assert state["a:1"]["state"] == DRAINING
    assert state["a:1"]["breaker"]["consecutive_failures"] == 0


def test_maybe_executed_failure_not_retried_when_disabled():
    """With retry_maybe_executed=False a timeout (the replica may
    still be executing) is NOT retried: 502 back to the caller."""
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    router = _mk_router(("a:1", "b:2"), reps,
                        retry_maybe_executed=False, max_retries=2)
    router.poll_once()
    reps["a:1"].fail = "timeout"
    code, body = router.route_generate({"input_text": "1"})
    assert code == 502 and body["reason"] == "timeout"
    assert router.retries_total() == {}
    assert len(reps["b:2"].requests) == 0


def test_circuit_breaker_half_open_probe_cycle():
    """threshold failures open the breaker; during cooldown the replica
    takes no traffic (structured 503 when it was the only one); after
    cooldown exactly one half-open probe may close it."""
    clock = ManualClock()
    reps = {"a:1": FakeReplica()}
    router = _mk_router(("a:1",), reps, clock=clock,
                        breaker_threshold=2, breaker_cooldown_s=5.0,
                        max_retries=0)
    router.poll_once()
    reps["a:1"].fail = "connect"
    for _ in range(2):
        code, _ = router.route_generate({"input_text": "x"})
        assert code == 502
    assert router.fleet_state()["replicas"][0]["state"] == BROKEN
    # cooldown holds: no attempt reaches the replica at all
    n_before = len(reps["a:1"].requests)
    code, body = router.route_generate({"input_text": "x"})
    assert code == 503 and body["reason"] == "no_healthy_replicas"
    assert body["replicas"]["a:1"]["state"] == BROKEN
    assert len(reps["a:1"].requests) == n_before
    # past cooldown + replica recovered: the half-open probe closes it
    clock.advance(5.1)
    reps["a:1"].fail = None
    code, _ = router.route_generate({"input_text": "y"})
    assert code == 200
    assert router.fleet_state()["replicas"][0]["state"] == HEALTHY
    # healthy polls past cooldown close it too (poll-as-probe): break
    # it again, recover via polls only
    reps["a:1"].fail = "connect"
    router.route_generate({"input_text": "z"})
    router.route_generate({"input_text": "z"})
    assert router.fleet_state()["replicas"][0]["state"] == BROKEN
    clock.advance(5.1)
    reps["a:1"].fail = None
    router.poll_once()
    assert router.fleet_state()["replicas"][0]["state"] == HEALTHY


def test_router_drain_stops_admission():
    reps = {"a:1": FakeReplica()}
    router = _mk_router(("a:1",), reps)
    router.poll_once()
    assert healthz_payload(router)[0] == 200
    router.drain()
    code, body = router.route_generate({"input_text": "1"})
    assert code == 503 and body["reason"] == "draining"
    code, body = healthz_payload(router)
    assert code == 503 and body["ready"] is False
    assert body["reason"] == "draining"
    assert router.wait_drained(timeout_s=1.0)
    assert len(reps["a:1"].requests) == 0


def test_fleet_state_json_deterministic_across_hashseed(tmp_path):
    """`/fleet` (sorted JSON) is byte-identical across PYTHONHASHSEED —
    the debug payload the acceptance pin reads must be deterministic.
    Pure-stdlib subprocess: the fleet package must not pull jax."""
    script = """
import json, sys
assert "jax" not in sys.modules
from fengshen_tpu.fleet import FleetConfig, FleetRouter, TransportError
assert "jax" not in sys.modules, "fleet package must stay jax-free"

class Clock:
    # constant: poll_once sweeps replicas on parallel threads, so an
    # advancing clock would make timestamps scheduling-dependent
    def __call__(self): return 100.0

class T:
    def request(self, base_url, method, path, body, timeout_s):
        if base_url.endswith(":1"):
            if path == "/healthz": return 200, {"ready": True}
            if path == "/stats": return 200, {"slots_active": 1,
                                              "num_slots": 4,
                                              "queue_depth": 0,
                                              "phase": "prefill"}
            return 200, {"result": "ok",
                         "request_id": body["request_id"]}
        raise TransportError("dead", sent=False)

r = FleetRouter(FleetConfig(replicas=("a:1", "b:2"),
                            recovery_probes=1, breaker_threshold=1,
                            backoff_base_s=0.0),
                transport=T(), clock=Clock(), sleep=lambda s: None)
r.poll_once()
r.route_generate({"input_text": "1"})
print(json.dumps(r.fleet_state(), sort_keys=True))
"""
    outs = []
    for seed in ("0", "1"):
        out = subprocess.run(
            [sys.executable, "-c", script],
            env={**os.environ, "PYTHONHASHSEED": seed},
            capture_output=True, text=True, cwd=REPO)
        assert out.returncode == 0, out.stderr
        outs.append(out.stdout)
    assert outs[0] == outs[1]
    state = json.loads(outs[0])
    assert state["healthy"] == 1 and state["broken"] == 1
    # phase flows from polled /stats into /fleet; the unpolled dead
    # replica stays "both", and the topology label reflects the mix
    phases = {r["name"]: r["phase"] for r in state["replicas"]}
    assert phases == {"a:1": "prefill", "b:2": "both"}
    assert state["topology"] == "prefill=1,decode=0,both=1"


def test_fault_plan_coordinates():
    """FleetFaultPlan: kills are sticky from their index on, 503/slow
    are one-shot at (index, replica), polls never advance the index."""
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    inner = FakeTransport(reps)
    plan = FleetFaultPlan(kill_at={2: "a:1"},
                          error_503_at={0: "b:2"}, slow_at={1: "b:2"},
                          slow_s=0.01)
    slept = []
    t = plan.wrap(inner, sleep=slept.append)
    # polls: no index movement, a:1 still alive
    assert t.request("http://a:1", "GET", "/healthz", None, 1)[0] == 200
    # idx 0 → b: one-shot 503
    code, body = t.request("http://b:2", "POST", "/api/t",
                           {"input_text": "x"}, 1)
    assert code == 503 and body["reason"] == "injected"
    # idx 1 → b: slow, then fine
    code, _ = t.request("http://b:2", "POST", "/api/t",
                        {"input_text": "x"}, 1)
    assert code == 200 and slept == [0.01]
    # idx 2 arms the kill; this attempt targets a → dead
    with pytest.raises(TransportError) as e:
        t.request("http://a:1", "POST", "/api/t",
                  {"input_text": "x"}, 1)
    assert e.value.sent is False
    # and a stays dead for polls too
    with pytest.raises(TransportError):
        t.request("http://a:1", "GET", "/healthz", None, 1)
    assert plan.fired == [("error_503", 0, "b:2"), ("slow", 1, "b:2"),
                          ("kill", 2, "a:1")]
    assert plan.fault_count == 3


# ---- integration tier: real replicas, tiny model ------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig(vocab_size=97, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64, dtype="float32")
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


class _IntTok:
    eos_token_id = None
    pad_token_id = 0

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(int(t)) for t in ids)


def _ref(model, params, prompt, max_new):
    out = np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                              max_new_tokens=max_new))
    return out[0, len(prompt):].tolist()


def _start_replica(tiny, max_new=5, num_slots=2, start=True):
    """One real stdlib api replica over a continuous engine. Returns
    (server, engine, serve_thread, draining_event, pipeline)."""
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)
    from fengshen_tpu.pipelines.text_generation import Pipeline
    model, params = tiny
    pipe = Pipeline(module=model, params=params, tokenizer=_IntTok(),
                    max_new_tokens=max_new, eos_token_id=None,
                    pad_token_id=0)
    engine = ContinuousBatchingEngine(
        model, params,
        EngineConfig(num_slots=num_slots, buckets=(8,),
                     max_new_tokens=max_new, max_queue=32,
                     pad_token_id=0))
    engine.warmup()
    if start:
        engine.start()
    ready = threading.Event()
    ready.set()
    draining = threading.Event()
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine, ready=ready, draining=draining)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, engine, thread, draining, pipe


def test_fleet_kill_one_replica_token_identical_exact_retries(tiny):
    """THE acceptance pin (ISSUE 10): 3 replicas, one killed mid-run at
    a deterministic request index — every submitted greedy request
    completes token-identical to a single sequential engine, zero
    dropped or duplicated responses, and the router's retry counter
    matches the injected fault count EXACTLY."""
    model, params = tiny
    fleet = [_start_replica(tiny) for _ in range(3)]
    targets = [f"127.0.0.1:{s.server_address[1]}"
               for s, *_ in fleet]
    plan = FleetFaultPlan(kill_at={4: targets[0]})
    transport = plan.wrap(UrllibTransport())
    router = FleetRouter(
        FleetConfig(replicas=targets, max_retries=2,
                    breaker_threshold=1, recovery_probes=1,
                    backoff_base_s=0.0, request_timeout_s=60.0),
        transport=transport, sleep=lambda s: None)
    transport.bind(router)
    try:
        router.poll_once()
        assert router.healthy_count() == 3
        rng = np.random.RandomState(0)
        prompts = [rng.randint(3, 96, n).astype(np.int32)
                   for n in (3, 5, 7, 4, 6, 8, 2, 5, 3)]
        responses = []
        for p in prompts:
            code, body = router.route_generate(
                {"input_text": " ".join(str(t) for t in p)})
            responses.append((code, body))
        # zero dropped: every request answered 200
        assert [c for c, _ in responses] == [200] * len(prompts)
        # token-identical to a single sequential engine
        refs = [" ".join(str(t) for t in _ref(model, params, p, 5))
                for p in prompts]
        assert [b["result"] for _, b in responses] == refs
        # zero duplicated: one distinct router-assigned id per request
        rids = [b["request_id"] for _, b in responses]
        assert len(set(rids)) == len(prompts)
        assert all(r.startswith("fleet-") for r in rids)
        # retries == injected faults, EXACTLY (the kill fired once:
        # breaker_threshold=1 takes the dead replica out after its
        # single failed attempt)
        assert plan.fired == [("kill", 4, targets[0])]
        assert router.retries_total() == {"connect": 1}
        # the dead replica reads broken in /fleet; the JSON is sorted-
        # dumpable (the hashseed pin covers byte determinism)
        state = {r["name"]: r
                 for r in router.fleet_state()["replicas"]}
        assert state[targets[0]]["state"] == BROKEN
        json.dumps(router.fleet_state(), sort_keys=True)
    finally:
        for server, engine, thread, *_ in fleet:
            server.shutdown()
            server.server_close()
            engine.stop()


def test_wedged_replica_retry_is_idempotent_safe(tiny):
    """A WEDGE (timeout: the replica may still be executing) retries on
    a different replica because the surface is idempotent-safe — the
    response comes from the healthy replica, once."""
    model, params = tiny
    fleet = [_start_replica(tiny) for _ in range(2)]
    targets = [f"127.0.0.1:{s.server_address[1]}"
               for s, *_ in fleet]
    plan = FleetFaultPlan(wedge_at={1: targets[0]})
    transport = plan.wrap(UrllibTransport())
    router = FleetRouter(
        FleetConfig(replicas=targets, max_retries=2,
                    breaker_threshold=1, recovery_probes=1,
                    backoff_base_s=0.0, request_timeout_s=60.0),
        transport=transport, sleep=lambda s: None)
    transport.bind(router)
    try:
        router.poll_once()
        prompt = np.asarray([5, 7, 9], np.int32)
        text = "5 7 9"
        codes = []
        for _ in range(3):
            code, body = router.route_generate({"input_text": text})
            codes.append(code)
            assert body["result"] == " ".join(
                str(t) for t in _ref(model, params, prompt, 5))
        assert codes == [200, 200, 200]
        assert router.retries_total() == {"timeout": 1}
        assert plan.fired == [("wedge", 1, targets[0])]
    finally:
        for server, engine, thread, *_ in fleet:
            server.shutdown()
            server.server_close()
            engine.stop()


def test_replica_sigterm_drains_while_inflight_completes(tiny):
    """Satellite (extends the PR-8 SIGTERM chain): SIGTERM to a stdlib
    api replica flips /healthz to the draining-503 body; new requests
    get 503; a request that is queued-but-not-slotted when the drain
    lands is flushed back as the SAME orderly 503 immediately (the
    router re-places it on a healthy replica — docs/fleet.md "Drain
    runbook" step 2; RUNNING lanes completing or evacuating is pinned
    in tests/test_evac.py); the server then shuts itself down once
    idle."""
    from fengshen_tpu.api.main import install_drain_handler
    # serve loop NOT started yet: the posted request stays queued on
    # the replica — deterministically in flight when SIGTERM lands —
    # and starts decoding only after the drain assertions below
    server, engine, thread, draining, _pipe = _start_replica(
        tiny, max_new=50, start=False)
    port = server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    prev = install_drain_handler(server, draining, engine=engine,
                                 drain_timeout_s=30.0)
    # the drain's waiter shuts the server down as soon as it is idle,
    # and the flush below makes it idle at once: hold it off until the
    # drain assertions have been made over HTTP (they raced its 50 ms
    # poll and timed out on a closed server, one run in four here)
    asserted = threading.Event()
    in_flight = server.in_flight
    server.in_flight = lambda: in_flight() if asserted.is_set() else 1
    result = {}

    def worker():
        req = urllib.request.Request(
            base + "/api/text_generation",
            data=json.dumps({"input_text": "5 7 9"}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                result["code"] = r.status
                result["body"] = json.loads(r.read())
        except urllib.error.HTTPError as e:
            result["code"] = e.code
            result["body"] = json.loads(e.read())

    def _get(path):
        try:
            with urllib.request.urlopen(base + path, timeout=10) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        w = threading.Thread(target=worker)
        w.start()
        # wait for the request to be IN FLIGHT (queued on the engine;
        # the serve loop is idle so /stats is contention-free)
        import time as _time
        for _ in range(2000):
            if engine.stats()["queue_depth"] >= 1:
                break
            _time.sleep(0.005)
        else:
            pytest.fail("request never admitted")
        signal.raise_signal(signal.SIGTERM)
        # the replica answers draining-503 on /healthz (the body the
        # fleet router keys on) while the in-flight request runs on
        code, body = _get("/healthz")
        assert code == 503
        assert body == {"status": "draining", "task": "text_generation",
                        "ready": False, "reason": "draining"}
        # new work is refused at the admission edge
        req = urllib.request.Request(
            base + "/api/text_generation",
            data=json.dumps({"input_text": "3 4"}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=30)
        assert exc.value.code == 503
        assert json.loads(exc.value.read())["reason"] == "draining"
        # /stats exposes the drain for the router's poll, and the
        # queued-but-not-slotted request was FLUSHED, not kept waiting
        code, stats = _get("/stats")
        assert code == 200 and stats["draining"] is True
        assert stats["queue_depth"] == 0
        # ... flushed as the same orderly 503 the admission edge
        # answers — the router treats it as draining (no breaker
        # charge) and re-places it on a healthy replica
        w.join(timeout=60)
        assert not w.is_alive()
        assert result["code"] == 503
        assert result["body"]["reason"] == "draining"
        # and the drained server shuts itself down (serve_forever
        # returns in the serving thread) once the engine runs idle
        asserted.set()
        engine.start()
        thread.join(timeout=30)
        assert not thread.is_alive()
    finally:
        signal.signal(signal.SIGTERM, prev)
        try:
            server.shutdown()
            server.server_close()
        except OSError:
            pass
        engine.stop()


def test_request_id_dedupe_and_engine_drain(tiny):
    """The replica-side idempotency hook: a live duplicate request_id
    is REJECTED (DuplicateRequest → 409 at the API layer); begin_drain
    refuses new submissions (Draining → 503 reason draining) and shows
    in /stats."""
    from fengshen_tpu.api.main import _engine_generate
    model, params = tiny
    engine = ContinuousBatchingEngine(
        model, params,
        EngineConfig(num_slots=1, buckets=(8,), max_new_tokens=4,
                     max_queue=8, pad_token_id=0))
    # no serve thread: submissions stay QUEUED, deterministically live
    engine.submit(np.asarray([5, 7], np.int32), request_id="fleet-9")
    with pytest.raises(DuplicateRequest):
        engine.submit(np.asarray([5, 7], np.int32),
                      request_id="fleet-9")
    engine.submit(np.asarray([5, 7], np.int32), request_id="fleet-10")
    assert engine.stats()["rejected_duplicate"] == 1

    class _Pipe:
        def encode(self, text):
            return [int(t) for t in text.split()]

        def decode(self, ids):
            return " ".join(str(int(t)) for t in ids)

    code, body = _engine_generate(
        engine, _Pipe(), {"input_text": "5 7", "request_id": "fleet-9"},
        timeout_s=1.0)
    assert code == 409 and "fleet-9" in body["error"]
    # drain: stats flip + 503 with reason at the API mapping
    assert engine.stats()["draining"] is False
    engine.begin_drain()
    assert engine.stats()["draining"] is True
    with pytest.raises(Draining):
        engine.submit(np.asarray([3, 4], np.int32))
    code, body = _engine_generate(engine, _Pipe(),
                                  {"input_text": "3 4"}, timeout_s=1.0)
    assert code == 503 and body["reason"] == "draining"
    assert engine.stats()["rejected_draining"] == 2
