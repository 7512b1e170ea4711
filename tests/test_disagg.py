"""Prefill/decode disaggregation (ISSUE 13, docs/disaggregation.md):
KV-handoff subsystem + phase-aware fleet placement.

Four tiers:

- UNIT tests over the router-process half (`disagg/policy.py`,
  `disagg/transfer.py`): phase validation, least-occupied pair
  planning with every degenerate topology, topology labels, checksum
  seal/tamper, and the push adopt-ack contract (exact `KvPushError`
  reason + `sent` per failure mode) — no jax, no sockets;
- ENGINE tests over `serving/handoff.py` on a tiny llama: THE
  acceptance pin — greedy outputs token-identical to a single-engine
  baseline through a REAL export→adopt→detach handoff, across slot AND
  paged layouts and the int8-for-transfer → fp32-decode path, with the
  engines' compile counts pinned (handoff adds ZERO jitted programs) —
  plus the adopt-decline reason matrix and export/detach edge cases;
- HTTP tests over two REAL stdlib replicas (prefill + decode phases,
  each with its `DisaggCoordinator`) behind the REAL `FleetRouter`:
  phase-aware placement pushes the lane, the router collects the
  redirect, bodies are token-identical and the assembled trace shows
  the handoff on BOTH replicas — and the degradation pin: kill / wedge
  / adopt-decline faults at exact KV-push indices all degrade to local
  decode with zero client errors, token-identical results, and
  `fstpu_disagg_fallbacks_total{reason}` matching the faults EXACTLY;
- a pure-stdlib SUBPROCESS pin: the policy+transfer half the router
  imports must never pull jax.
"""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.disagg import (KvPushError, plan_handoff,
                                 push_payload, seal, topology,
                                 validate_phase, verify_checksum)
from fengshen_tpu.disagg.coordinator import DisaggCoordinator
from fengshen_tpu.fleet import (FleetConfig, FleetFaultPlan,
                                FleetRouter, TransportError,
                                UrllibTransport)
from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.serving import (ContinuousBatchingEngine,
                                  EngineConfig, handoff)
from fengshen_tpu.utils.generate import generate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PAGED = dict(kv_layout="paged", kv_block_size=8, kv_num_blocks=17)


# ---- unit tier: policy --------------------------------------------------

class _Rep:
    def __init__(self, name, phase, occ=0.0):
        self.name = name
        self.phase = phase
        self._occ = occ

    def occupancy(self):
        return self._occ


def test_validate_phase():
    assert validate_phase("prefill") == "prefill"
    assert validate_phase(" Decode ") == "decode"
    assert validate_phase("") == "both"
    assert validate_phase(None) == "both"
    with pytest.raises(ValueError):
        validate_phase("prefil")


def test_plan_handoff_needs_both_dedicated_tiers():
    """Every degenerate topology plans None — disaggregation never
    becomes a new way to fail a request."""
    assert plan_handoff([]) is None
    assert plan_handoff([_Rep("a", "both"), _Rep("b", "both")]) is None
    assert plan_handoff([_Rep("a", "prefill"),
                         _Rep("b", "both")]) is None
    assert plan_handoff([_Rep("a", "decode"),
                         _Rep("b", "decode")]) is None
    plan = plan_handoff([_Rep("a", "prefill"), _Rep("b", "decode"),
                         _Rep("c", "both")])
    assert (plan.prefill.name, plan.decode.name) == ("a", "b")


def test_plan_handoff_picks_least_occupied_per_tier():
    reps = [_Rep("p0", "prefill", 0.5), _Rep("p1", "prefill", 0.25),
            _Rep("d0", "decode", 0.75), _Rep("d1", "decode", 0.25),
            _Rep("d2", "decode", 0.25)]
    plan = plan_handoff(reps)
    assert plan.prefill.name == "p1"
    assert plan.decode.name == "d1"      # tie → iteration order


def test_topology_labels():
    assert topology([]) == "homogeneous"
    assert topology(["both", "both", "both"]) == "homogeneous"
    assert topology(["prefill", "decode"]) == "prefill=1,decode=1"
    assert topology(["prefill", "prefill", "decode", "both"]) == \
        "prefill=2,decode=1,both=1"


# ---- unit tier: transfer ------------------------------------------------

def test_seal_and_checksum_tamper():
    payload = seal({"kind": "fstpu-kv-handoff", "request_id": "r-1",
                    "tokens": [1, 2, 3]})
    assert verify_checksum(payload)
    assert not verify_checksum(dict(payload, tokens=[1, 2, 4]))
    assert not verify_checksum({"tokens": [1, 2, 3]})
    # the checksum field itself is excluded from the hashed bytes
    assert seal(dict(payload))["checksum"] == payload["checksum"]


class _AckTransport:
    """Scripted peer for the push adopt-ack contract."""

    def __init__(self, status=200, body=None, exc=None):
        self.status, self.body, self.exc = status, body, exc
        self.calls = []

    def request(self, base_url, method, path, body, timeout_s):
        self.calls.append((base_url, method, path))
        if self.exc is not None:
            raise self.exc
        return self.status, self.body


def _push(t, **kw):
    payload = seal({"request_id": "r-1", "tokens": [1, 2]})
    return push_payload("http://d:1", "r-1", payload, transport=t, **kw)


def test_push_ack_contract():
    """200 + {"adopted": true} is the ONLY success; every failure mode
    maps to ONE KvPushError with the exact reason+sent the fallback
    counter labels."""
    ok = _AckTransport(200, {"adopted": True, "request_id": "r-1"})
    assert _push(ok)["adopted"] is True
    assert ok.calls == [("http://d:1", "PUT", "/kv/r-1")]

    with pytest.raises(KvPushError) as e:
        _push(_AckTransport(409, {"adopted": False, "reason": "shape"}))
    assert (e.value.reason, e.value.sent) == ("adopt_declined", True)

    # a well-formed decline is adopt_declined even on status 200
    with pytest.raises(KvPushError) as e:
        _push(_AckTransport(200, {"adopted": False, "reason": "x"}))
    assert e.value.reason == "adopt_declined"

    with pytest.raises(KvPushError) as e:
        _push(_AckTransport(500, {"error": "boom"}))
    assert (e.value.reason, e.value.sent) == ("http_500", True)

    with pytest.raises(KvPushError) as e:
        _push(_AckTransport(exc=TransportError("dead", sent=False)))
    assert (e.value.reason, e.value.sent) == ("connect", False)

    with pytest.raises(KvPushError) as e:
        _push(_AckTransport(exc=TransportError("hung", sent=True)))
    assert (e.value.reason, e.value.sent) == ("timeout", True)

    # the size cap trips BEFORE anything leaves the process
    capped = _AckTransport(200, {"adopted": True})
    with pytest.raises(KvPushError) as e:
        _push(capped, max_bytes=8)
    assert (e.value.reason, e.value.sent) == ("too_large", False)
    assert capped.calls == []


def test_disagg_router_half_is_jax_free(tmp_path):
    """The policy+transfer half rides in the fleet router process: the
    no-jax contract pinned on `fengshen_tpu.fleet` extends to
    `fengshen_tpu.disagg` (its __init__ and everything it imports)."""
    script = """
import sys
assert "jax" not in sys.modules
import fengshen_tpu.disagg as d
from fengshen_tpu.disagg import plan_handoff, seal, topology
assert "jax" not in sys.modules, "disagg router half must stay jax-free"

class R:
    def __init__(self, phase): self.phase = phase
    def occupancy(self): return 0.0

plan = plan_handoff([R("prefill"), R("decode")])
assert plan is not None
assert topology(["prefill", "decode"]) == "prefill=1,decode=1"
assert "checksum" in seal({"tokens": [1]})
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


# ---- engine tier: real handoff on a tiny llama --------------------------

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


_PROMPT = np.random.RandomState(0).randint(3, 96, 6).astype(np.int32)
_MAX_NEW = 12


def _mk_engine(tiny, **kw):
    model, params = tiny
    kw = dict({"num_slots": 2}, **kw)
    return ContinuousBatchingEngine(
        model, params,
        EngineConfig(buckets=(8,), max_new_tokens=_MAX_NEW,
                     pad_token_id=0, **kw))


def _prime(engine, ticks=4):
    """Submit the shared prompt and tick until mid-decode."""
    req = engine.submit(_PROMPT)
    engine.step()                       # admit + prefill + first token
    for _ in range(ticks):
        engine.step()
    assert req.state == "running"
    return req


@pytest.mark.parametrize("name,src_kw,dst_kw", [
    ("fp32slot->fp32slot", {}, {}),
    ("fp32slot->fp32paged", {}, PAGED),
    ("int8paged->fp32slot", dict(kv_dtype="int8", **PAGED), {}),
    ("int8slot->int8paged", dict(kv_dtype="int8"),
     dict(kv_dtype="int8", **PAGED)),
])
def test_handoff_token_identity(tiny, name, src_kw, dst_kw):
    """THE acceptance pin: a request primed on one engine, exported
    mid-decode, adopted by a second engine and decoded to completion
    produces tokens IDENTICAL to the single-engine fp32 baseline —
    across slot AND paged layouts on both ends, including the
    int8-for-transfer → fp32-decode path (the wire is always int8; on
    this fixture the per-(token, head) scales reproduce fp32 greedy
    exactly, and int8→int8 re-places the wire bits verbatim)."""
    model, params = tiny
    src = _mk_engine(tiny, **src_kw)
    dst = _mk_engine(tiny, **dst_kw)
    req = _prime(src)
    payload = handoff.export_lane(src, req.request_id)
    # int8-for-transfer even off an fp32 tier: the KV prefix rides
    # quantized with per-(token, head) scales
    assert payload["wire_dtype"] == "int8"
    assert all(layer["k"]["dtype"] == "int8"
               for layer in payload["layers"])
    assert verify_checksum(payload)
    adopted = handoff.adopt_lane(dst, payload)
    assert handoff.detach_lane(src, req.request_id, target="peer")
    assert req.state == "handed_off"
    dst.run_until_idle()
    assert adopted.state == "finished"
    assert adopted.tokens == _ref(model, params, _PROMPT, _MAX_NEW), name


def test_handoff_adds_zero_jitted_programs(tiny):
    """Export is an eager gather and adopt an eager scatter: after a
    full handoff the source holds exactly its pinned program set (one
    decode, one prefill bucket, one assign) and the receiver — which
    never ran a prefill — holds ONE decode program and nothing else."""
    src = _mk_engine(tiny)
    dst = _mk_engine(tiny)
    if not hasattr(src._decode_jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    req = _prime(src)
    payload = handoff.export_lane(src, req.request_id)
    adopted = handoff.adopt_lane(dst, payload)
    assert handoff.detach_lane(src, req.request_id, target="peer")
    dst.run_until_idle()
    assert adopted.state == "finished"
    assert src._decode_jit._cache_size() == 1
    assert src._prefill_jit._cache_size() == 1   # one per bucket
    assert src._assign_jit._cache_size() == 1
    assert dst._decode_jit._cache_size() == 1
    assert dst._prefill_jit._cache_size() == 0   # adopt never prefills
    assert dst._assign_jit._cache_size() == 0


def test_adopt_decline_reasons(tiny):
    """The header-validation matrix: each corruption declines with ITS
    exact reason (the label the source's fallback counter carries) and
    leaves the receiving engine untouched."""
    src = _mk_engine(tiny)
    dst = _mk_engine(tiny)
    req = _prime(src)
    payload = handoff.export_lane(src, req.request_id)

    def decline(p):
        before = dst.stats()["slots_active"]
        with pytest.raises(handoff.AdoptDecline) as e:
            handoff.adopt_lane(dst, p)
        assert dst.stats()["slots_active"] == before
        return e.value.reason

    assert decline(seal(dict(payload, version=99))) == "version"
    assert decline(dict(payload, pos=payload["pos"] + 1)) == "checksum"
    assert decline(seal(dict(payload, model_fingerprint="other"))) == \
        "model_fingerprint"
    controls = dict(payload["controls"], pad_token_id=7)
    assert decline(seal(dict(payload, controls=controls))) == \
        "controls"

    # a clean adopt succeeds once; the same request id again declines
    adopted = handoff.adopt_lane(dst, payload)
    assert decline(dict(payload)) == "duplicate_request_id"
    dst.run_until_idle()
    assert adopted.state == "finished"

    # a full engine declines with "no_free_slot" (header valid)
    full = _mk_engine(tiny, num_slots=1)
    _prime(full, ticks=1)
    with pytest.raises(handoff.AdoptDecline) as e:
        handoff.adopt_lane(full, payload)
    assert e.value.reason == "no_free_slot"


def test_export_and_detach_edges(tiny):
    """Export refuses unknown / not-yet-running / finished lanes with
    HandoffError; detach after a local finish returns False (the local
    result stands — the coordinator cancels the adopted twin)."""
    eng = _mk_engine(tiny)
    with pytest.raises(handoff.HandoffError):
        handoff.export_lane(eng, "nope")
    req = eng.submit(_PROMPT)            # queued, never ticked
    with pytest.raises(handoff.HandoffError):
        handoff.export_lane(eng, req.request_id)
    eng.run_until_idle()
    assert req.state == "finished"
    with pytest.raises(handoff.HandoffError):
        handoff.export_lane(eng, req.request_id)
    assert handoff.detach_lane(eng, req.request_id) is False


# ---- HTTP tier: real replicas, real router ------------------------------

def _start_phase_replica(tiny, phase, max_new, transport=None,
                         tick_delay_s=0.0):
    """One real stdlib replica with a disagg coordinator. Returns
    (server, engine, coordinator). `tick_delay_s` throttles the decode
    tick (the `_decode_jit` wrap idiom from the debug tests): the tiny
    model otherwise finishes a whole generation faster than the
    coordinator's prime-poll can observe it RUNNING — a pace no real
    model reaches — which would race every handoff into local_finish."""
    import time as _time

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)
    from fengshen_tpu.pipelines.text_generation import Pipeline
    model, params = tiny
    pipe = Pipeline(module=model, params=params, tokenizer=_IntTok(),
                    max_new_tokens=max_new, eos_token_id=None,
                    pad_token_id=0)
    engine = ContinuousBatchingEngine(
        model, params,
        EngineConfig(num_slots=2, buckets=(8,), max_new_tokens=max_new,
                     max_queue=32, pad_token_id=0))
    engine.warmup()
    if tick_delay_s:
        real = engine._tick

        def slow_tick(ahead):
            # BEFORE the tick takes `_cv` (as `test_evac.py`): a sleep
            # inside the decode call held the lock for the whole delay,
            # and the handoff's export then waited for it until the
            # lane had finished locally
            _time.sleep(tick_delay_s)
            return real(ahead)

        engine._tick = slow_tick
    engine.start()
    coord = DisaggCoordinator(engine, pipe, transport=transport)
    ready = threading.Event()
    ready.set()
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous",
                     phase=phase),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine, ready=ready, draining=threading.Event(),
        disagg=coord)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, engine, coord


def _labelled(counter):
    return {k[0]: int(c.value) for k, c in counter.children()
            if c.value}


def _events(base, rid):
    with urllib.request.urlopen(
            f"http://{base}/debug/requests/{rid}", timeout=10) as r:
        wf = json.loads(r.read())
    return [e["event"] for e in wf["events"]]


def test_disagg_http_end_to_end_token_identical(tiny):
    """Phase-aware placement over two REAL replicas: admissions land on
    the prefill tier, the primed lane is pushed to the decode tier, the
    router collects the redirect — every response is 200,
    token-identical to the single-engine baseline, and the assembled
    trace shows the handoff on BOTH replicas' waterfalls."""
    model, params = tiny
    max_new = 32
    fleet = [_start_phase_replica(
        tiny, phase, max_new,
        tick_delay_s=0.03 if phase == "prefill" else 0.0)
             for phase in ("prefill", "decode")]
    targets = [f"127.0.0.1:{s.server_address[1]}"
               for s, *_ in fleet]
    router = FleetRouter(
        FleetConfig(replicas=targets, recovery_probes=1,
                    backoff_base_s=0.0, request_timeout_s=60.0),
        transport=UrllibTransport(), sleep=lambda s: None)
    try:
        router.poll_once()
        assert router.healthy_count() == 2
        state = router.fleet_state()
        assert state["topology"] == "prefill=1,decode=1"
        rng = np.random.RandomState(1)
        prompts = [rng.randint(3, 96, n).astype(np.int32)
                   for n in (4, 6, 7)]
        bodies = []
        for p in prompts:
            code, body = router.route_generate(
                {"input_text": " ".join(str(t) for t in p)})
            assert code == 200, body
            bodies.append(body)
        refs = [" ".join(str(t) for t in _ref(model, params, p,
                                              max_new))
                for p in prompts]
        assert [b["result"] for b in bodies] == refs
        # every request went through a REAL handoff (collected from the
        # decode replica, not answered locally)
        assert all(b.get("adopted") is True for b in bodies)
        pre_coord, dec_coord = fleet[0][2], fleet[1][2]
        assert _labelled(pre_coord.registry.get(
            "fstpu_disagg_handoffs_total")) == {"redirected": 3}
        assert int(dec_coord.registry.get(
            "fstpu_disagg_adopted_total").value()) == 3
        assert dec_coord.adopted_count() == 0   # all collected
        # the assembled trace stitches BOTH replicas: the prefill
        # waterfall ends in the handoff, the decode one starts with
        # the adoption
        assembled = router.assemble(bodies[-1]["trace_id"])
        assert sorted(assembled["replicas"]) == sorted(targets)
        pre_wf = assembled["replicas"][targets[0]]["waterfall"]
        dec_wf = assembled["replicas"][targets[1]]["waterfall"]
        assert pre_wf["request_id"] == dec_wf["request_id"] == \
            bodies[-1]["request_id"]
        pre_ev = [e["event"] for e in pre_wf["events"]]
        dec_ev = [e["event"] for e in dec_wf["events"]]
        assert "handoff_export" in pre_ev and "handed_off" in pre_ev
        assert "adopted" in dec_ev and "finished" in dec_ev
    finally:
        for server, engine, _ in fleet:
            server.shutdown()
            server.server_close()
            engine.stop()


def test_disagg_handoff_faults_degrade_to_local(tiny):
    """THE degradation pin (ISSUE 13): kill, wedge, and adopt-decline
    faults at exact KV-push indices — every request still answers 200
    token-identical (local prefill-and-decode absorbed the failure,
    NEVER a client error), `fstpu_disagg_fallbacks_total{reason}`
    matches the injected faults EXACTLY, the wedge's adopted twin is
    cancelled, and the fallback is visible on the request's trace."""
    model, params = tiny
    max_new = 32
    plan = None                          # bound after ports are known
    holder = {}

    class _Lazy:
        """Defers to the fault-wrapped transport once built — the
        coordinators need a transport before the plan exists."""

        def request(self, *a, **kw):
            return holder["t"].request(*a, **kw)

    fleet = [_start_phase_replica(
        tiny, phase, max_new, transport=_Lazy(),
        tick_delay_s=0.03 if phase == "prefill" else 0.0)
             for phase in ("prefill", "decode")]
    targets = [f"127.0.0.1:{s.server_address[1]}"
               for s, *_ in fleet]
    plan = FleetFaultPlan(kv_kill_at={0: targets[1]},
                          kv_wedge_at={1: targets[1]},
                          kv_decline_at={2: targets[1]})
    transport = holder["t"] = plan.wrap(UrllibTransport())
    router = FleetRouter(
        FleetConfig(replicas=targets, recovery_probes=1,
                    backoff_base_s=0.0, request_timeout_s=60.0),
        transport=transport, sleep=lambda s: None)
    transport.bind(router)
    try:
        router.poll_once()
        assert router.healthy_count() == 2
        rng = np.random.RandomState(2)
        prompts = [rng.randint(3, 96, n).astype(np.int32)
                   for n in (5, 4, 6, 7)]
        bodies = []
        for p in prompts:
            code, body = router.route_generate(
                {"input_text": " ".join(str(t) for t in p)})
            assert code == 200, body     # zero client errors, ever
            bodies.append(body)
        refs = [" ".join(str(t) for t in _ref(model, params, p,
                                              max_new))
                for p in prompts]
        assert [b["result"] for b in bodies] == refs
        # the three faulted pushes answered locally; the fourth
        # redirected through the decode tier
        assert [b.get("adopted") for b in bodies] == \
            [None, None, None, True]
        assert plan.fired == [("kv_kill", 0, targets[1]),
                              ("kv_wedge", 1, targets[1]),
                              ("kv_decline", 2, targets[1])]
        # fallbacks counted per reason, matching the faults EXACTLY
        pre_coord, dec_coord = fleet[0][2], fleet[1][2]
        assert _labelled(pre_coord.registry.get(
            "fstpu_disagg_fallbacks_total")) == \
            {"connect": 1, "timeout": 1, "adopt_declined": 1}
        assert _labelled(pre_coord.registry.get(
            "fstpu_disagg_handoffs_total")) == \
            {"fallback": 3, "redirected": 1}
        # the wedge DELIVERED its adopt (plus the clean redirect), and
        # both twins are gone: cancelled on fallback, collected on
        # success — a request never decodes twice to completion
        assert int(dec_coord.registry.get(
            "fstpu_disagg_adopted_total").value()) == 2
        assert dec_coord.adopted_count() == 0
        # no router-level retries: handoff failure is the replica's to
        # absorb, invisible to rotation
        assert router.retries_total() == {}
        # the fallback is on the request's own trace: the prefill
        # replica's waterfall carries the handoff_fallback mark
        ev = _events(targets[0], bodies[0]["request_id"])
        assert "handoff_fallback" in ev and "finished" in ev
        ev_ok = _events(targets[0], bodies[3]["request_id"])
        assert "handed_off" in ev_ok
    finally:
        for server, engine, _ in fleet:
            server.shutdown()
            server.server_close()
            engine.stop()
