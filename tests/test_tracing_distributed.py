"""Fleet-wide distributed tracing (ISSUE 11, docs/observability.md
"Distributed tracing"): trace-context propagation, cross-process
waterfall assembly, and Perfetto-exportable trace bundles.

Three tiers:

- UNIT: traceparent parse/format round-trips, seeded id determinism,
  the span ledger's bounded record, and the router's span ledger over
  a fake transport (admit/placement/attempt spans, retries as sibling
  children of one trace, the `fstpu_fleet_attempt_seconds{outcome}`
  histogram, traceparent propagated to replicas as body field + lifted
  from the header);
- ASSEMBLY: `/debug/traces/<id>` stitches the router ledger with the
  involved replicas' waterfalls — clock anchoring with skew REPORTED,
  fetch failures degrading to error entries, byte-identical JSON
  across PYTHONHASHSEED in a jax-free subprocess (like `/fleet`), and
  `traceview` emitting valid Chrome trace-event JSON;
- INTEGRATION (tiny llama, real stdlib replicas): the acceptance pin —
  a FleetFaultPlan fault at a chosen request index yields ONE assembled
  trace whose ledger shows attempt 1 (failed, faulted replica) +
  attempt 2 (ok, surviving replica) as children of the same trace_id,
  per-process waterfalls attached with phases summing exactly, and
  greedy outputs token-identical with tracing on (one decode compile —
  trace bookkeeping adds no traced-code inputs).
"""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.fleet import (FleetConfig, FleetFaultPlan,
                                FleetRouter, TransportError,
                                UrllibTransport)
from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.observability import (FlightRecorder, SpanLedger,
                                        TraceContext, TraceIds,
                                        parse_traceparent)
from fengshen_tpu.observability.traceview import chrome_trace
from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
from fengshen_tpu.utils.generate import generate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- trace context units ------------------------------------------------

def test_traceparent_round_trip_and_rejects():
    ctx = TraceContext(trace_id="ab" * 16, span_id="cd" * 8)
    assert ctx.to_traceparent() == f"00-{'ab' * 16}-{'cd' * 8}-01"
    back = parse_traceparent(ctx.to_traceparent())
    assert back == ctx
    # malformed inputs degrade to None (fresh trace), never raise
    for bad in (None, 17, "", "00-zz-cd-01",
                f"ff-{'ab' * 16}-{'cd' * 8}-01",          # version ff
                f"zz-{'ab' * 16}-{'cd' * 8}-01",          # non-hex ver
                f"00-{'0' * 32}-{'cd' * 8}-01",           # zero trace
                f"00-{'ab' * 16}-{'0' * 16}-01",          # zero span
                f"00-{'ab' * 15}-{'cd' * 8}-01",          # short trace
                "no-dashes-here"):
        assert parse_traceparent(bad) is None, bad


def test_trace_ids_seeded_deterministic():
    a, b = TraceIds(seed=7), TraceIds(seed=7)
    assert [a.trace_id() for _ in range(3)] == \
        [b.trace_id() for _ in range(3)]
    assert a.span_id() == b.span_id()
    # UNSEEDED mints must not collide (OS entropy, the production
    # default): two routers with the same config draw distinct ids
    assert TraceIds().trace_id() != TraceIds().trace_id()
    tid = TraceIds(seed=0).trace_id()
    assert len(tid) == 32 and set(tid) <= set("0123456789abcdef")
    assert parse_traceparent(
        TraceContext(tid, TraceIds(seed=0).span_id())
        .to_traceparent()) is not None


def test_span_ledger_records_and_bounds():
    t = [100.0]
    ledger = SpanLedger("router", clock=lambda: t[0],
                        wall=lambda: 5000.25, max_traces=2,
                        ids=TraceIds(seed=0))
    ctx = ledger.start_trace("fleet/request", request_id="r-0")
    t[0] += 0.5
    child = ledger.start_span(ctx.trace_id, "router/attempt",
                              ctx.span_id, replica="a:1")
    t[0] += 0.25
    ledger.end_span(ctx.trace_id, child, outcome="ok", status=200)
    trace = ledger.get_trace(ctx.trace_id)
    assert trace["service"] == "router"
    assert trace["epoch_unix_s"] == 5000.25
    root, att = trace["spans"]
    assert root["name"] == "fleet/request"
    assert root["parent_span_id"] is None
    assert root["attrs"]["request_id"] == "r-0"
    assert att["parent_span_id"] == root["span_id"]
    assert att["t_start_s"] == 0.5 and att["duration_s"] == 0.25
    assert att["attrs"] == {"replica": "a:1", "outcome": "ok",
                            "status": 200}
    # bounded: a third trace evicts the oldest
    ledger.start_trace("fleet/request")
    ledger.start_trace("fleet/request")
    assert ledger.get_trace(ctx.trace_id) is None
    assert len(ledger.provider()["traces"]) == 2
    # unknown trace: recording degrades to no-ops, never raises
    assert ledger.start_span("f" * 32, "x", None) is None
    ledger.end_span("f" * 32, "deadbeefdeadbeef")


def test_span_ledger_caps_spans_per_trace():
    """A client may legally reuse ONE traceparent across many requests;
    joining must not grow a single record without bound — past the cap
    spans are dropped (start_span -> None, so end_span no-ops) and
    counted in the rendered trace."""
    ledger = SpanLedger("router", max_spans_per_trace=3,
                        ids=TraceIds(seed=0))
    ctx = ledger.start_trace("fleet/request")
    assert ledger.start_span(ctx.trace_id, "a", ctx.span_id) is not None
    assert ledger.start_span(ctx.trace_id, "b", ctx.span_id) is not None
    assert ledger.start_span(ctx.trace_id, "c", ctx.span_id) is None
    # joining the same trace id past the cap still returns a usable
    # context (propagation keeps working) but records nothing more
    ctx2 = ledger.start_trace("fleet/request", trace_id=ctx.trace_id)
    assert ctx2.trace_id == ctx.trace_id
    trace = ledger.get_trace(ctx.trace_id)
    assert len(trace["spans"]) == 3
    assert trace["spans_dropped"] == 2
    # an uncapped trace never carries the key
    other = ledger.start_trace("fleet/request")
    assert "spans_dropped" not in ledger.get_trace(other.trace_id)


# ---- router ledger over a fake transport --------------------------------

class ManualClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeReplica:
    def __init__(self, num_slots: int = 4):
        self.healthz = (200, {"status": "ok", "ready": True})
        self.stats = {"slots_active": 0, "queue_depth": 0,
                      "num_slots": num_slots, "draining": False}
        self.fail = None
        self.generate_code = 200
        self.requests = []
        #: request_id -> the /debug/requests/<id> payload to answer
        self.waterfalls = {}

    def response(self, body):
        return self.generate_code, {
            "result": "ok", "request_id": body.get("request_id"),
            "finish_reason": "length"}


class FakeTransport:
    def __init__(self, replicas):
        self.replicas = replicas

    def request(self, base_url, method, path, body, timeout_s):
        rep = self.replicas[base_url.split("://", 1)[1]]
        if rep.fail is not None:
            raise TransportError(f"injected {rep.fail}",
                                 sent=rep.fail == "timeout")
        if path == "/healthz":
            return rep.healthz
        if path == "/stats":
            return 200, rep.stats
        if path.startswith("/debug/requests/"):
            rid = path[len("/debug/requests/"):]
            if rid in rep.waterfalls:
                return 200, rep.waterfalls[rid]
            return 404, {"error": "unknown"}
        if method == "POST" and path.startswith("/api/"):
            rep.requests.append(body)
            return rep.response(body)
        return 404, {}


def _mk_router(names, replicas, clock=None, **cfg):
    cfg.setdefault("recovery_probes", 1)
    cfg.setdefault("backoff_base_s", 0.05)
    cfg.setdefault("seed", 0)
    cfg.setdefault("trace_seed", 0)
    return FleetRouter(
        FleetConfig(replicas=names, **cfg),
        transport=FakeTransport(replicas),
        clock=clock or ManualClock(), sleep=lambda s: None,
        wall=lambda: 7000.0)


def test_router_spans_and_propagation_on_retry():
    """One retried request = ONE trace: placement + attempt spans as
    children of the root, the failed attempt carrying outcome/backoff,
    the traceparent body field parented to EACH attempt's own span, and
    the per-attempt histogram labelled by outcome."""
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    router = _mk_router(("a:1", "b:2"), reps, breaker_threshold=1,
                        max_retries=2, backoff_base_s=0.1)
    router.poll_once()
    reps["a:1"].fail = "connect"
    code, body = router.route_generate({"input_text": "1"})
    assert code == 200
    tid = body["trace_id"]
    trace = router.tracer.get_trace(tid)
    assert trace is not None and trace["trace_id"] == tid
    by_name = {}
    for span in trace["spans"]:
        by_name.setdefault(span["name"], []).append(span)
    root = by_name["fleet/request"][0]
    assert root["attrs"]["request_id"] == body["request_id"]
    assert root["attrs"]["outcome"] == "ok"
    assert root["attrs"]["attempts"] == 2
    assert root["duration_s"] is not None
    # every non-root span is a CHILD of the root
    for name in ("router/enqueue", "router/placement",
                 "router/attempt"):
        for span in by_name[name]:
            assert span["parent_span_id"] == root["span_id"]
    att1, att2 = by_name["router/attempt"]
    assert att1["attrs"]["replica"] == "a:1"
    assert att1["attrs"]["outcome"] == "connect"
    assert 0.05 <= att1["attrs"]["backoff_s"] < 0.1   # jittered
    assert att2["attrs"]["replica"] == "b:2"
    assert att2["attrs"]["outcome"] == "ok"
    assert att2["attrs"]["status"] == 200
    assert [p["attrs"]["replica"]
            for p in by_name["router/placement"]] == ["a:1", "b:2"]
    # the replica saw a traceparent parented to ITS attempt span
    sent = reps["b:2"].requests[0]
    ctx = parse_traceparent(sent["traceparent"])
    assert ctx.trace_id == tid and ctx.span_id == att2["span_id"]
    # per-attempt seconds landed under both outcome labels
    hist = router.registry.get("fstpu_fleet_attempt_seconds")
    outcomes = {values[0]: child.count
                for values, child in hist.children()}
    assert outcomes == {"connect": 1, "ok": 1}
    assert int(router.registry.get(
        "fstpu_trace_started_total").value()) == 1


def test_router_joins_incoming_traceparent():
    """An upstream traceparent is JOINED (same trace id, root parented
    to the caller's span), not replaced — routers stack."""
    reps = {"a:1": FakeReplica()}
    router = _mk_router(("a:1",), reps)
    router.poll_once()
    upstream = TraceContext("ab" * 16, "cd" * 8)
    code, body = router.route_generate(
        {"input_text": "1", "traceparent": upstream.to_traceparent()})
    assert code == 200 and body["trace_id"] == upstream.trace_id
    trace = router.tracer.get_trace(upstream.trace_id)
    root = trace["spans"][0]
    assert root["name"] == "fleet/request"
    assert root["parent_span_id"] == upstream.span_id


def test_fleet_state_poll_staleness_fields():
    """Satellite: /fleet carries per-replica last_poll_age_s (None
    until the first completed poll, then the age on the router clock)
    and a top-level consecutive_failures."""
    clock = ManualClock()
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    router = _mk_router(("a:1", "b:2"), reps, clock=clock,
                        breaker_threshold=3)
    state = {r["name"]: r for r in router.fleet_state()["replicas"]}
    assert state["a:1"]["last_poll_age_s"] is None
    assert state["a:1"]["consecutive_failures"] == 0
    router.poll_once()
    clock.advance(2.5)
    state = {r["name"]: r for r in router.fleet_state()["replicas"]}
    assert state["a:1"]["last_poll_age_s"] == 2.5
    assert state["b:2"]["last_poll_age_s"] == 2.5
    # an unreachable replica still counts as POLLED (the sweep ran);
    # its failure streak is the visible signal
    reps["b:2"].fail = "connect"
    router.poll_once()
    state = {r["name"]: r for r in router.fleet_state()["replicas"]}
    assert state["b:2"]["last_poll_age_s"] == 0.0
    assert state["b:2"]["consecutive_failures"] == 1


# ---- assembly -----------------------------------------------------------

def _waterfall(rid, epoch, total=0.6):
    return {"request_id": rid, "state": "finished",
            "finish_reason": "length", "prompt_tokens": 3,
            "generated_tokens": 4, "slot": 0, "ttft_s": 0.3,
            "phases": {"queue_wait_s": 0.1, "prefill_s": 0.2,
                       "decode_s": round(total - 0.3, 6),
                       "decode_stall_s": 0.0, "total_s": total},
            "events": [{"t_s": 0.0, "event": "enqueued"},
                       {"t_s": total, "event": "finished",
                        "reason": "length"}],
            "dropped_events": 0, "trace_id": None,
            "parent_span_id": None, "epoch_unix_s": epoch}


def test_assemble_attaches_waterfalls_with_skew():
    """Assembly stitches the ledger with each involved replica's
    waterfall; the clock anchoring reports offset + skew instead of
    hiding them; a failed attempt's replica still appears (as an error
    entry when unreachable)."""
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    router = _mk_router(("a:1", "b:2"), reps, breaker_threshold=1,
                        max_retries=1)
    router.poll_once()
    reps["a:1"].fail = "connect"
    code, body = router.route_generate({"input_text": "1"})
    assert code == 200
    rid, tid = body["request_id"], body["trace_id"]
    # router wall anchor is 7000.0; the surviving replica anchors 0.4s
    # later — that offset must surface, not vanish
    reps["b:2"].waterfalls[rid] = _waterfall(rid, 7000.4)
    assembled = router.assemble(tid)
    assert assembled["trace_id"] == tid
    assert assembled["request_id"] == rid
    assert sorted(assembled["replicas"]) == ["a:1", "b:2"]
    a, b = assembled["replicas"]["a:1"], assembled["replicas"]["b:2"]
    assert a["error"].startswith("unreachable")
    assert "waterfall" not in a
    assert b["waterfall"]["request_id"] == rid
    assert b["offset_in_trace_s"] == 0.4
    # manual clock: the attempt dispatched at t_start 0.0, so skew ==
    # offset here
    assert b["clock_skew_s"] == 0.4
    ph = b["waterfall"]["phases"]
    assert abs(ph["queue_wait_s"] + ph["prefill_s"] + ph["decode_s"]
               - ph["total_s"]) < 1e-9
    # unknown trace ids answer None (404 at the server layer)
    assert router.assemble("9" * 32) is None
    reg = router.registry
    assert int(reg.get("fstpu_trace_assembled_total").value()) == 1
    assert int(reg.get("fstpu_trace_fetch_errors_total").value()) == 1


def test_assemble_joined_trace_fetches_per_request():
    """One caller traceparent reused across TWO requests (W3C-legal):
    each attempt span records its OWN request_id, so assembly fetches
    every replica's actual request — never the first id the trace ever
    saw (which would 404 on replicas that served later requests)."""
    clock = ManualClock()
    reps = {"a:1": FakeReplica(), "b:2": FakeReplica()}
    router = _mk_router(("a:1", "b:2"), reps, clock=clock)
    router.poll_once()
    tp = TraceContext("ab" * 16, "cd" * 8).to_traceparent()
    code, b1 = router.route_generate(
        {"input_text": "1", "traceparent": tp, "request_id": "r-1"})
    assert code == 200
    # second request lands on the OTHER replica (a:1 now looks busy)
    reps["a:1"].stats["slots_active"] = 4
    router.poll_once()
    code, b2 = router.route_generate(
        {"input_text": "2", "traceparent": tp, "request_id": "r-2"})
    assert code == 200
    assert b1["trace_id"] == b2["trace_id"] == "ab" * 16
    reps["a:1"].waterfalls["r-1"] = _waterfall("r-1", 7000.1)
    reps["b:2"].waterfalls["r-2"] = _waterfall("r-2", 7000.2)
    assembled = router.assemble("ab" * 16)
    assert sorted(assembled["replicas"]) == ["a:1", "b:2"]
    assert assembled["replicas"]["a:1"]["waterfall"][
        "request_id"] == "r-1"
    assert assembled["replicas"]["b:2"]["waterfall"][
        "request_id"] == "r-2"
    assert int(router.registry.get(
        "fstpu_trace_fetch_errors_total").value()) == 0
    # a THIRD request on the same trace landing on b:2 again: one
    # attachment per replica (its first request), the later one NAMED
    # rather than silently invisible
    code, b3 = router.route_generate(
        {"input_text": "3", "traceparent": tp, "request_id": "r-3"})
    assert code == 200
    assembled = router.assemble("ab" * 16)
    b = assembled["replicas"]["b:2"]
    assert b["waterfall"]["request_id"] == "r-2"
    assert b["other_request_ids"] == ["r-3"]
    assert "other_request_ids" not in assembled["replicas"]["a:1"]


def test_assembled_trace_deterministic_across_hashseed(tmp_path):
    """The `/debug/traces/<id>` payload (sorted JSON) is byte-identical
    across PYTHONHASHSEED — seeded ids, injected clocks, explicit
    request id. Pure-stdlib subprocess: the fleet package AND the new
    tracing modules must not pull jax."""
    script = """
import json, sys
assert "jax" not in sys.modules
from fengshen_tpu.fleet import FleetConfig, FleetRouter, TransportError
from fengshen_tpu.observability.tracectx import SpanLedger, TraceIds
from fengshen_tpu.observability.traceview import chrome_trace
assert "jax" not in sys.modules, "tracing tier must stay jax-free"

class Clock:
    def __call__(self): return 100.0

WATERFALL = {"request_id": "req-pin", "state": "finished",
             "phases": {"queue_wait_s": 0.1, "prefill_s": 0.2,
                        "decode_s": 0.3, "decode_stall_s": 0.0,
                        "total_s": 0.6},
             "events": [{"t_s": 0.0, "event": "enqueued"},
                        {"t_s": 0.6, "event": "finished"}],
             "dropped_events": 0, "epoch_unix_s": 1000.25}

class T:
    def request(self, base_url, method, path, body, timeout_s):
        if base_url.endswith(":1"):
            if path == "/healthz": return 200, {"ready": True}
            if path == "/stats": return 200, {"slots_active": 0,
                                              "num_slots": 4,
                                              "queue_depth": 0}
            if path.startswith("/debug/requests/"):
                return 200, dict(WATERFALL)
            return 200, {"result": "ok",
                         "request_id": body["request_id"]}
        raise TransportError("dead", sent=False)

r = FleetRouter(FleetConfig(replicas=("a:1", "b:2"),
                            recovery_probes=1, breaker_threshold=1,
                            backoff_base_s=0.0, max_retries=1,
                            trace_seed=0),
                transport=T(), clock=Clock(), sleep=lambda s: None,
                wall=lambda: 1000.0)
r.poll_once()
code, body = r.route_generate({"input_text": "1",
                               "request_id": "req-pin"})
assert code == 200, code
assembled = r.assemble(body["trace_id"])
print(json.dumps(assembled, sort_keys=True))
print(json.dumps(chrome_trace(assembled), sort_keys=True))
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
    assembled = json.loads(outs[0].splitlines()[0])
    assert assembled["request_id"] == "req-pin"
    assert assembled["replicas"]["a:1"]["waterfall"]["state"] == \
        "finished"


# ---- traceview ----------------------------------------------------------

def _validate_chrome(doc):
    """The Chrome trace-event JSON-object-format contract: a
    traceEvents list whose entries carry name/ph/ts/pid (+ dur on X)."""
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    for ev in doc["traceEvents"]:
        for key in ("name", "ph", "pid", "tid"):
            assert key in ev, ev
        assert ev["ph"] in ("X", "M", "i"), ev
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], int) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], int) and ev["dur"] >= 0


def test_traceview_converts_assembled_trace(tmp_path):
    reps = {"a:1": FakeReplica()}
    router = _mk_router(("a:1",), reps)
    router.poll_once()
    code, body = router.route_generate({"input_text": "1"})
    rid = body["request_id"]
    # replica clock runs BEHIND the router's: events would go negative
    # without the shift the converter applies
    reps["a:1"].waterfalls[rid] = _waterfall(rid, 6999.5)
    assembled = router.assemble(body["trace_id"])
    doc = chrome_trace(assembled)
    _validate_chrome(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"fleet/request", "router/attempt", "queue_wait",
            "prefill", "decode", "process_name"} <= names
    procs = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert procs == {"router", "a:1"}
    assert doc["otherData"]["shifted_us"] == 500_000

    # the CLI round-trips a saved assembled trace deterministically
    path = tmp_path / "assembled.json"
    path.write_text(json.dumps(assembled, sort_keys=True))
    outs = []
    for seed in ("0", "1"):
        out = subprocess.run(
            [sys.executable, "-m",
             "fengshen_tpu.observability.traceview", str(path)],
            env={**os.environ, "PYTHONHASHSEED": seed,
                 "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, cwd=REPO)
        assert out.returncode == 0, out.stderr
        outs.append(out.stdout)
    assert outs[0] == outs[1]
    _validate_chrome(json.loads(outs[0]))
    # missing input exits 2
    assert subprocess.run(
        [sys.executable, "-m",
         "fengshen_tpu.observability.traceview",
         str(tmp_path / "nope.json")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, cwd=REPO).returncode == 2


def test_traceview_renders_fetch_error_attachment():
    """A dead replica's {"error": ...} attachment must surface the
    diagnostic in the export — an instant mark carrying the error, not
    a healthy-looking track of zero-width phase bars."""
    doc = {"schema": 1, "trace_id": "f" * 32, "request_id": "r-1",
           "router": {"trace_id": "f" * 32, "service": "router",
                      "epoch_unix_s": 7000.0, "spans": []},
           "replicas": {"a:1": {"error": "unreachable: injected"}}}
    out = chrome_trace(doc)
    evs = [e for e in out["traceEvents"] if e["ph"] != "M"]
    assert [e["name"] for e in evs] == ["fetch_error"]
    assert evs[0]["args"]["error"] == "unreachable: injected"
    assert not [e for e in out["traceEvents"] if e["ph"] == "X"]


def test_traceview_reads_flight_recorder_bundle(tmp_path):
    """Satellite: a router wired to a FlightRecorder contributes
    traces.json to every bundle, and traceview converts the bundle
    directory directly."""
    rec = FlightRecorder(dump_dir=str(tmp_path))
    reps = {"a:1": FakeReplica()}
    router = FleetRouter(
        FleetConfig(replicas=("a:1",), recovery_probes=1),
        transport=FakeTransport(reps), clock=ManualClock(),
        sleep=lambda s: None, wall=lambda: 7000.0, recorder=rec)
    router.poll_once()
    code, body = router.route_generate({"input_text": "1"})
    assert code == 200
    bundle = rec.dump(reason="test")
    traces = json.loads(
        open(os.path.join(bundle, "traces.json")).read())
    assert traces["service"] == "router"
    assert [t["trace_id"] for t in traces["traces"]] == \
        [body["trace_id"]]
    # router events rode along in the ring too
    events = [json.loads(line) for line in
              open(os.path.join(bundle, "events.jsonl"))]
    assert any(e.get("event") == "fleet_replica_in" for e in events)
    out = subprocess.run(
        [sys.executable, "-m",
         "fengshen_tpu.observability.traceview", bundle],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stderr
    _validate_chrome(json.loads(out.stdout))


# ---- fleet server surface -----------------------------------------------

def test_fleet_server_traces_endpoint_and_http_timing():
    """GET /debug/traces/<id> serves the assembled trace (404 on
    unknown ids), and the router's own endpoints land in the SAME
    fstpu_http_request_seconds{route} histogram the replica servers
    feed (satellite)."""
    from fengshen_tpu.fleet import build_fleet_server
    from fengshen_tpu.observability import get_registry

    reps = {"a:1": FakeReplica()}
    router = _mk_router(("a:1",), reps)
    router.poll_once()
    code, body = router.route_generate({"input_text": "1"})
    rid = body["request_id"]
    reps["a:1"].waterfalls[rid] = _waterfall(rid, 7000.1)
    server = build_fleet_server(router, host="127.0.0.1", port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(
                f"{base}/debug/traces/{body['trace_id']}",
                timeout=10) as r:
            assembled = json.loads(r.read())
        assert assembled["trace_id"] == body["trace_id"]
        assert assembled["replicas"]["a:1"]["waterfall"][
            "request_id"] == rid
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"{base}/debug/traces/{'9' * 32}", timeout=10)
        assert exc.value.code == 404
        with urllib.request.urlopen(f"{base}/healthz", timeout=10):
            pass
        with urllib.request.urlopen(f"{base}/fleet", timeout=10) as r:
            fleet = json.loads(r.read())
        assert fleet["replicas"][0]["last_poll_age_s"] is not None
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
        # the router's own endpoint latency + the tracing tier's
        # counters render beside the fleet gauges
        assert 'fstpu_http_request_seconds_bucket' in text
        assert 'route="/healthz"' in text
        assert 'route="/debug/traces/<id>"' in text
        assert 'fstpu_fleet_attempt_seconds_bucket' in text
        assert 'fstpu_trace_started_total' in text
        hist = get_registry().get("fstpu_http_request_seconds")
        routes = {values[0] for values, _ in hist.children()}
        assert {"/healthz", "/fleet",
                "/debug/traces/<id>"} <= routes
    finally:
        server.shutdown()
        server.server_close()


# ---- engine tier: tracing adds no traced work ---------------------------

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


def _ref(model, params, prompt, max_new):
    out = np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                              max_new_tokens=max_new))
    return out[0, len(prompt):].tolist()


def test_engine_tracing_parity_one_compile(tiny):
    """Trace ids through submit are host-side bookkeeping only: greedy
    output stays token-identical to sequential generate with exactly
    ONE decode compile, and every timeline + debug-ring entry carries
    trace_id/parent_span_id."""
    model, params = tiny
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, 96, n).astype(np.int32)
               for n in (5, 11, 16, 7)]
    refs = [_ref(model, params, p, 8) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=8, max_queue=16),
        wall=lambda: 4321.5)
    if not hasattr(eng._decode_jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    reqs = [eng.submit(p, trace_id=f"{i:032x}",
                       parent_span_id=f"{i:016x}")
            for i, p in enumerate(prompts, start=1)]
    eng.run_until_idle()
    for i, (req, ref) in enumerate(zip(reqs, refs), start=1):
        assert req.tokens == ref
        d = eng.debug_request(req.request_id)
        assert d["trace_id"] == f"{i:032x}"
        assert d["parent_span_id"] == f"{i:016x}"
        # the engine's injectable wall clock anchors the timeline —
        # the replica half of the assembler's skew math is testable
        assert d["epoch_unix_s"] == 4321.5
        ph = d["phases"]
        assert abs(ph["queue_wait_s"] + ph["prefill_s"] +
                   ph["decode_s"] - ph["total_s"]) <= 1e-3
    assert eng._decode_jit._cache_size() == 1
    # the list endpoint's summaries carry the id too
    recent = eng.debug_requests()["recent"]
    assert {r["trace_id"] for r in recent} == \
        {f"{i:032x}" for i in range(1, 5)}
    # 413-class rejections keep their trace correlation as well
    from fengshen_tpu.serving import PromptTooLong
    with pytest.raises(PromptTooLong):
        eng.submit(rng.randint(3, 96, 70).astype(np.int32),
                   request_id="rej-1", trace_id="e" * 32,
                   parent_span_id="f" * 16)
    assert eng.debug_request("rej-1")["trace_id"] == "e" * 32


# ---- integration: real replicas, fault plan, assembled trace ------------

class _IntTok:
    eos_token_id = None
    pad_token_id = 0

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(int(t)) for t in ids)


def _start_replica(tiny, max_new=5, num_slots=2):
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
    engine.start()
    ready = threading.Event()
    ready.set()
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine, ready=ready)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, engine, thread


def _shutdown(fleet):
    for server, engine, _thread in fleet:
        server.shutdown()
        server.server_close()
        engine.stop()


def test_fleet_fault_yields_one_assembled_trace(tiny):
    """THE acceptance pin (ISSUE 11): a FleetFaultPlan fault at a
    chosen request index yields ONE assembled trace — the router span
    ledger shows attempt 1 (failed, faulted replica) + attempt 2 (ok,
    surviving replica) as children of the same trace_id, both
    replicas' per-process waterfalls attach (the wedged replica really
    executed its copy), phases sum exactly per process, traceview
    emits valid Chrome trace-event JSON, and every greedy answer is
    token-identical with tracing on."""
    model, params = tiny
    fleet = [_start_replica(tiny) for _ in range(2)]
    targets = [f"127.0.0.1:{s.server_address[1]}"
               for s, *_ in fleet]
    # wedge (not kill) the faulted attempt: the request is DELIVERED
    # and executed, its response lost — so the faulted replica has a
    # real per-process waterfall for the assembler to attach
    plan = FleetFaultPlan(wedge_at={2: targets[0]})
    transport = plan.wrap(UrllibTransport())
    router = FleetRouter(
        FleetConfig(replicas=targets, max_retries=2,
                    breaker_threshold=1, recovery_probes=1,
                    backoff_base_s=0.0, request_timeout_s=60.0),
        transport=transport, sleep=lambda s: None)
    transport.bind(router)
    try:
        router.poll_once()
        assert router.healthy_count() == 2
        rng = np.random.RandomState(1)
        prompts = [rng.randint(3, 96, n).astype(np.int32)
                   for n in (3, 5, 7, 4)]
        responses = []
        for p in prompts:
            code, body = router.route_generate(
                {"input_text": " ".join(str(t) for t in p)})
            responses.append((code, body))
        assert [c for c, _ in responses] == [200] * len(prompts)
        refs = [" ".join(str(t) for t in _ref(model, params, p, 5))
                for p in prompts]
        assert [b["result"] for _, b in responses] == refs
        assert plan.fired == [("wedge", 2, targets[0])]
        assert router.retries_total() == {"timeout": 1}

        # ONE trace tells the wedged request's whole story
        wedged_code, wedged = responses[2]
        tid = wedged["trace_id"]
        assert len({b["trace_id"] for _, b in responses}) == \
            len(prompts)                    # one trace per request
        trace = router.tracer.get_trace(tid)
        root = trace["spans"][0]
        attempts = [s for s in trace["spans"]
                    if s["name"] == "router/attempt"]
        assert len(attempts) == 2
        assert all(s["parent_span_id"] == root["span_id"]
                   for s in attempts)
        assert attempts[0]["attrs"]["replica"] == targets[0]
        assert attempts[0]["attrs"]["outcome"] == "timeout"
        assert attempts[1]["attrs"]["replica"] == targets[1]
        assert attempts[1]["attrs"]["outcome"] == "ok"

        # unwedge (process "restarted") so assembly can fetch the
        # faulted replica's waterfall; the fired coordinate stays
        # consumed — no re-fire
        plan.revive(targets[0])
        assembled = router.assemble(tid)
        assert assembled["request_id"] == wedged["request_id"]
        assert sorted(assembled["replicas"]) == sorted(targets)
        for name in targets:
            entry = assembled["replicas"][name]
            wf = entry["waterfall"]
            assert wf["request_id"] == wedged["request_id"]
            assert wf["state"] == "finished"
            # the per-process PR-8 invariant survives assembly:
            # phases sum exactly per process
            ph = wf["phases"]
            assert abs(ph["queue_wait_s"] + ph["prefill_s"] +
                       ph["decode_s"] - ph["total_s"]) <= 1e-3
            assert "offset_in_trace_s" in entry
            assert "clock_skew_s" in entry
            # both executions parent into THIS trace via their
            # attempt spans
            att_ids = {s["span_id"] for s in attempts}
            assert wf["trace_id"] == tid
            assert wf["parent_span_id"] in att_ids
        # both executions returned the same greedy tokens (the
        # idempotent surface, now visible end to end)
        a_wf = assembled["replicas"][targets[0]]["waterfall"]
        b_wf = assembled["replicas"][targets[1]]["waterfall"]
        assert a_wf["generated_tokens"] == b_wf["generated_tokens"]

        doc = chrome_trace(assembled)
        _validate_chrome(doc)
        procs = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert procs == {"router"} | set(targets)
        json.dumps(assembled, sort_keys=True)    # JSON-clean
    finally:
        _shutdown(fleet)


def test_fleet_kill_trace_records_failed_attempt(tiny):
    """A KILL (connect refused — the request provably never reached
    the replica): the trace still tells the story — attempt 1 failed
    on the dead replica, attempt 2 ok on the survivor — and assembly
    degrades the dead replica to an error entry instead of failing."""
    model, params = tiny
    fleet = [_start_replica(tiny) for _ in range(2)]
    targets = [f"127.0.0.1:{s.server_address[1]}"
               for s, *_ in fleet]
    plan = FleetFaultPlan(kill_at={1: targets[0]})
    transport = plan.wrap(UrllibTransport())
    router = FleetRouter(
        FleetConfig(replicas=targets, max_retries=2,
                    breaker_threshold=1, recovery_probes=1,
                    backoff_base_s=0.0, request_timeout_s=60.0),
        transport=transport, sleep=lambda s: None)
    transport.bind(router)
    try:
        router.poll_once()
        prompts = [np.asarray([5, 7, 9], np.int32),
                   np.asarray([4, 6], np.int32)]
        bodies = []
        for p in prompts:
            code, body = router.route_generate(
                {"input_text": " ".join(str(t) for t in p)})
            assert code == 200
            bodies.append(body)
        assert plan.fired == [("kill", 1, targets[0])]
        tid = bodies[1]["trace_id"]
        trace = router.tracer.get_trace(tid)
        attempts = [s for s in trace["spans"]
                    if s["name"] == "router/attempt"]
        assert [s["attrs"]["outcome"] for s in attempts] == \
            ["connect", "ok"]
        assembled = router.assemble(tid)
        dead = assembled["replicas"][targets[0]]
        assert dead["error"].startswith("unreachable")
        alive = assembled["replicas"][targets[1]]
        assert alive["waterfall"]["request_id"] == \
            bodies[1]["request_id"]
        _validate_chrome(chrome_trace(assembled))
    finally:
        _shutdown(fleet)
