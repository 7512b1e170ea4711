"""Preemption-tolerant serving (ISSUE 16, docs/fault_tolerance.md
"Preemption runbook"): live lane evacuation on drain +
resume-from-token-k failover.

Four tiers:

- ENGINE tests over the drain/evacuation path: THE acceptance pin —
  a lane primed mid-decode, evacuated through
  `DisaggCoordinator.evacuate_all` (probe → rank → export → push →
  detach-as-evacuated) and finished by the adopter is token-identical
  to the single-engine baseline across slot AND paged layouts and the
  int8 wire, with compile counts pinned (evacuation adds ZERO jitted
  programs); plus the `begin_drain` queue-flush contract (queued
  requests reject as orderly "draining" NOW, without touching the
  pinned `rejected_draining` submit-refusal counter);
- RESUME tests over `submit(resume_tokens=...)`: prefilling
  prompt+committed-prefix and decoding only the remainder reproduces
  the undisturbed greedy output exactly, across layouts, again with
  pinned compile counts — and the journal ring (`partial()`) serves
  the snapshots that make it possible;
- HTTP tests over REAL stdlib replicas behind the REAL `FleetRouter`
  with a `FleetFaultPlan`: the `preempt` fault delivers a drain at an
  exact request index — every in-flight request answers 200
  token-identical through evacuation redirects (zero resumes, zero
  client errors) with `evacuated`/`adopted` on the two timelines; and
  the SIGKILL variant (adopter hard-killed right after adopting)
  recovers every request through the commit journal:
  `fstpu_resume_total{outcome="resumed"}` >= 1, zero journal misses,
  `resumed_from` on the rescuer's timeline, and ONE assembled trace
  stitching the drained and rescuing replicas.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.disagg.coordinator import DisaggCoordinator
from fengshen_tpu.fleet import (FleetConfig, FleetFaultPlan,
                                FleetRouter, UrllibTransport)
from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.pipelines.text_generation import Pipeline
from fengshen_tpu.serving import (ContinuousBatchingEngine,
                                  EngineConfig)
from fengshen_tpu.serving.engine import Draining
from fengshen_tpu.utils.generate import generate

PAGED = dict(kv_layout="paged", kv_block_size=8, kv_num_blocks=17)


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
    kw = dict({"num_slots": 2, "buckets": (8,)}, **kw)
    buckets = kw.pop("buckets")
    return ContinuousBatchingEngine(
        model, params,
        EngineConfig(buckets=buckets, max_new_tokens=_MAX_NEW,
                     pad_token_id=0, **kw))


def _prime(engine, ticks=4):
    req = engine.submit(_PROMPT)
    engine.step()                       # admit + prefill + first token
    for _ in range(ticks):
        engine.step()
    assert req.state == "running"
    return req


def _pipe(tiny, max_new=_MAX_NEW):
    model, params = tiny
    return Pipeline(module=model, params=params, tokenizer=_IntTok(),
                    max_new_tokens=max_new, eos_token_id=None,
                    pad_token_id=0)


def _labelled(counter):
    return {k[0]: int(c.value) for k, c in counter.children()
            if c.value}


class _Loopback:
    """In-process peer wire for `evacuate_all`: /stats probes, KV
    pushes, and twin deletes delivered straight to the destination
    coordinator — no sockets, no jax programs."""

    def __init__(self):
        self.peers = {}                 # base url -> coordinator

    def request(self, base_url, method, path, body, timeout_s):
        coord = self.peers[base_url.rstrip("/")]
        if method == "GET" and path == "/stats":
            st = coord.engine.stats()
            return 200, {
                "slots_active": int(st.get("slots_active") or 0),
                "queue_depth": int(st.get("queue_depth") or 0),
                "num_slots": coord.engine.config.num_slots,
                "draining": False, "phase": "both"}
        if method == "PUT" and path.startswith("/kv/"):
            return coord.handle_put(path[len("/kv/"):], body)
        if method == "DELETE" and path.startswith("/kv/"):
            return 200, {"deleted": True}
        return 404, {"error": "not found"}


# ---- engine tier: live lane evacuation ----------------------------------

@pytest.mark.parametrize("name,src_kw,dst_kw", [
    ("fp32slot->fp32slot", {}, {}),
    ("fp32slot->fp32paged", {}, PAGED),
    ("int8paged->fp32slot", dict(kv_dtype="int8", **PAGED), {}),
    ("int8slot->int8paged", dict(kv_dtype="int8"),
     dict(kv_dtype="int8", **PAGED)),
])
def test_evacuation_token_identity(tiny, name, src_kw, dst_kw):
    """THE acceptance pin: a draining engine's live lane, evacuated
    through the coordinator's probe→rank→export→push ladder and
    finished by the adopter, is token-identical to the single-engine
    baseline — across slot AND paged layouts on both ends and the
    int8-always wire."""
    model, params = tiny
    src = _mk_engine(tiny, **src_kw)
    dst = _mk_engine(tiny, **dst_kw)
    wire = _Loopback()
    src_coord = DisaggCoordinator(src, _pipe(tiny), transport=wire)
    dst_coord = DisaggCoordinator(dst, _pipe(tiny), transport=wire)
    wire.peers["http://peer"] = dst_coord
    req = _prime(src)
    prefix = list(req.tokens)
    src.begin_drain()
    summary = src_coord.evacuate_all(["http://peer"])
    assert summary == {"lanes": 1, "adopted": 1, "fallback": 0,
                       "local_finish": 0}, name
    assert req.state == "evacuated"
    assert req.finish_reason == "evacuated"
    assert req.evac_target == "http://peer"
    assert req.done                     # the blocked POST wakes NOW
    # the source's journal keeps serving the committed prefix — the
    # router's resume consult reads exactly this after a later SIGKILL
    part = src.partial(req.request_id)
    assert part["state"] == "evacuated"
    assert part["evac_target"] == "http://peer"
    assert len(part["tokens"]) >= len(prefix) >= 1
    ref = _ref(model, params, _PROMPT, _MAX_NEW)
    assert part["tokens"] == ref[:len(part["tokens"])]
    assert _labelled(src_coord.registry.get(
        "fstpu_evac_lanes_total")) == {"adopted": 1}
    adopted = next(r for r in dst._slot_req if r is not None)
    dst.run_until_idle()
    assert adopted.state == "finished"
    assert adopted.tokens == ref, name


def test_evacuation_adds_zero_jitted_programs(tiny):
    """Evacuation rides the eager export/adopt path: after a drain
    with one live lane the source holds exactly its pinned program set
    and the adopter — which never prefilled — holds ONE decode program
    and nothing else."""
    src = _mk_engine(tiny)
    dst = _mk_engine(tiny)
    if not hasattr(src._decode_jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    wire = _Loopback()
    src_coord = DisaggCoordinator(src, _pipe(tiny), transport=wire)
    wire.peers["http://peer"] = DisaggCoordinator(dst, _pipe(tiny))
    _prime(src)
    src.begin_drain()
    assert src_coord.evacuate_all(["http://peer"])["adopted"] == 1
    dst.run_until_idle()
    assert src._decode_jit._cache_size() == 1
    assert src._prefill_jit._cache_size() == 1   # one per bucket
    assert src._assign_jit._cache_size() == 1
    assert dst._decode_jit._cache_size() == 1
    assert dst._prefill_jit._cache_size() == 0   # adopt never prefills
    assert dst._assign_jit._cache_size() == 0


def test_begin_drain_flushes_queue_as_orderly_503(tiny):
    """Queued-but-unstarted requests must NOT wait out the drain: they
    reject NOW with reason "draining" (the API's orderly 503, so a
    router re-places them immediately) — without touching the pinned
    `rejected_draining` submit-refusal counter. The running lane keeps
    decoding: it is the evacuation candidate, not flush fodder."""
    eng = _mk_engine(tiny, num_slots=1)
    r1 = eng.submit(_PROMPT)
    eng.step()                          # r1 admitted and running
    r2 = eng.submit(_PROMPT)            # parked in the queue
    assert r2.state == "queued"
    eng.begin_drain()
    assert r2.state == "rejected"
    assert r2.finish_reason == "draining"
    assert r2.done                      # its blocked POST wakes NOW
    assert r1.state == "running"
    # the flush is not a submit refusal: the pinned counter only moves
    # when a NEW submission is turned away at the door
    assert eng.stats()["rejected_draining"] == 0
    with pytest.raises(Draining):
        eng.submit(_PROMPT)
    assert eng.stats()["rejected_draining"] == 1
    eng.run_until_idle()
    assert r1.state == "finished"


# ---- resume tier: resume-from-token-k + the commit journal --------------

@pytest.mark.parametrize("kw", [
    {}, PAGED, dict(kv_dtype="int8"), dict(kv_dtype="int8", **PAGED),
], ids=["fp32slot", "fp32paged", "int8slot", "int8paged"])
def test_resume_from_token_k_token_identity(tiny, kw):
    """A retried request carrying `resume_tokens` prefills
    prompt+prefix (all but the last resumed token, which the first
    tick re-commits) and decodes only the remainder — greedy output
    token-identical to the unkilled run for every cut point, across
    layouts and the int8 cache."""
    model, params = tiny
    ref = _ref(model, params, _PROMPT, _MAX_NEW)
    for k in (1, 3, 7):
        eng = _mk_engine(tiny, buckets=(8, 16), **kw)
        req = eng.submit(_PROMPT, resume_tokens=ref[:k],
                         resume_source="peer-a")
        eng.run_until_idle()
        assert req.state == "finished"
        assert req.tokens == ref, (kw, k)
        part = eng.partial(req.request_id)
        assert part["resumed_tokens"] == k
        assert part["resume_source"] == "peer-a"


def test_resume_adds_zero_jitted_programs(tiny):
    """The resume prefill rides the SAME bucketed prefill program as a
    fresh admission — recovering a request compiles nothing new."""
    model, params = tiny
    ref = _ref(model, params, _PROMPT, _MAX_NEW)
    eng = _mk_engine(tiny, buckets=(16,))
    if not hasattr(eng._decode_jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    req = eng.submit(_PROMPT, resume_tokens=ref[:3])
    eng.run_until_idle()
    assert req.tokens == ref
    assert eng._decode_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() == 1
    assert eng._assign_jit._cache_size() == 1


def test_resume_validation(tiny):
    """A resume prefix that already covers the token budget leaves
    nothing to decode — a bad request field (422 at the API layer),
    never an engine wedge."""
    eng = _mk_engine(tiny)
    with pytest.raises(ValueError):
        eng.submit(_PROMPT, max_new_tokens=3, resume_tokens=[5, 6, 7])
    with pytest.raises(ValueError):
        eng.submit(_PROMPT, max_new_tokens=2, resume_tokens=[5, 6, 7])


def test_commit_journal_partial_and_ring_bound(tiny):
    """`partial()` serves finished snapshots (tokens + metadata) from
    a ring bounded by `journal_ring` — the oldest entry ages out, an
    unknown id is None, and a live lane's snapshot grows as it
    commits."""
    model, params = tiny
    eng = _mk_engine(tiny, journal_ring=2)
    ref = _ref(model, params, _PROMPT, _MAX_NEW)
    reqs = []
    for _ in range(3):
        r = eng.submit(_PROMPT)
        eng.run_until_idle()
        reqs.append(r)
    assert eng.partial(reqs[0].request_id) is None   # aged out
    assert eng.partial("never-ran") is None
    for r in reqs[1:]:
        part = eng.partial(r.request_id)
        assert part["state"] == "finished"
        assert part["tokens"] == ref
        assert part["generated_tokens"] == _MAX_NEW
    live = _prime(eng)
    part = eng.partial(live.request_id)
    assert part["state"] == "running"
    assert 1 <= len(part["tokens"]) < _MAX_NEW
    assert part["tokens"] == ref[:len(part["tokens"])]
    eng.run_until_idle()


# ---- HTTP tier: preempt fault, evacuation, SIGKILL resume ---------------

_HTTP_MAX_NEW = 24


def _start_replica(tiny, max_new, tick_delay_s=0.0):
    """One real stdlib replica (phase "both") with its coordinator.
    `tick_delay_s` throttles the decode tick so lanes are reliably
    mid-decode when the preemption notice lands (the tiny model is
    otherwise faster than any real one)."""
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)
    model, params = tiny
    pipe = _pipe(tiny, max_new)
    engine = ContinuousBatchingEngine(
        model, params,
        EngineConfig(num_slots=4, buckets=(8, 40), max_new_tokens=max_new,
                     max_queue=32, pad_token_id=0))
    engine.warmup()
    if tick_delay_s:
        real = engine._tick

        def slow_tick(ahead):
            # BEFORE the tick takes `_cv`: a sleep inside the decode
            # call held the lock for the whole delay, and the drill's
            # poll, export and detach then waited many ticks for it
            time.sleep(tick_delay_s)
            return real(ahead)

        engine._tick = slow_tick
    engine.start()
    coord = DisaggCoordinator(engine, pipe)
    ready = threading.Event()
    ready.set()
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine, ready=ready, draining=threading.Event(),
        disagg=coord)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, engine, coord


def _stop_fleet(fleet):
    for server, engine, _ in fleet:
        server.shutdown()
        server.server_close()
        engine.stop()


def _route_many(router, prompts, width=4):
    texts = [" ".join(str(t) for t in p) for p in prompts]
    out = [None] * len(prompts)
    it = iter(range(len(prompts)))
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                i = next(it, None)
            if i is None:
                return
            out[i] = router.route_generate({"input_text": texts[i]})

    threads = [threading.Thread(target=worker) for _ in range(width)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def _events(base, rid):
    with urllib.request.urlopen(
            f"http://{base}/debug/requests/{rid}", timeout=10) as r:
        wf = json.loads(r.read())
    return [e["event"] for e in wf["events"]]


def _resume_totals(router):
    return {k[0]: int(c.value) for k, c in router._c_resume.children()
            if c.value}


def _preempt_cb(engine, coord, peers, max_new=_HTTP_MAX_NEW):
    """The preemption notice, as `install_drain_handler`'s waiter
    delivers it: flush the queue, then evacuate the live lanes. Waits
    briefly for a lane that is EARLY in its decode — a drill landing
    in the admission window has nothing to rescue, and one landing on
    a nearly-finished lane loses the adoption race to the local tick
    loop (a legitimate `local_finish`, but not the outcome this test
    pins)."""

    def fire():
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with engine._cv:
                early = [r for r in engine._slot_req
                         if r is not None and r.state == "running"
                         and 1 <= len(r.tokens) <= max_new - 16]
            if early:
                break
            time.sleep(0.005)
        engine.begin_drain()
        coord.evacuate_all(peers)

    return fire


def test_preempt_fault_evacuates_live_lanes_http(tiny):
    """THE integration pin (ISSUE 16): 2-replica fleet, `preempt`
    fault drains replica A at request index 4 mid-decode — every
    request answers 200, greedy token-identical to the undisturbed
    reference, at least one lane rode `fstpu_evac_lanes_total
    {outcome="adopted"}`, ZERO resume consults (evacuation answers
    through redirects, not regeneration), and both timelines show the
    rescue: terminal `evacuated` on the drained replica, `adopted` on
    the peer."""
    model, params = tiny
    fleet = [_start_replica(tiny, _HTTP_MAX_NEW, tick_delay_s=0.03)
             for _ in range(2)]
    targets = [f"127.0.0.1:{s.server_address[1]}"
               for s, *_ in fleet]
    plan = FleetFaultPlan(preempt_at={4: targets[0]})
    plan.preempt_with(targets[0], _preempt_cb(
        fleet[0][1], fleet[0][2], [f"http://{targets[1]}"]))
    transport = plan.wrap(UrllibTransport())
    router = FleetRouter(
        FleetConfig(replicas=targets, recovery_probes=1,
                    backoff_base_s=0.0, request_timeout_s=60.0),
        transport=transport, sleep=lambda s: None)
    transport.bind(router)
    try:
        router.poll_once()
        assert router.healthy_count() == 2
        rng = np.random.RandomState(3)
        prompts = [rng.randint(3, 96, 4 + (i % 3)).astype(np.int32)
                   for i in range(8)]
        out = _route_many(router, prompts, width=4)
        assert [code for code, _ in out] == [200] * len(prompts)
        refs = [" ".join(str(t) for t in
                         _ref(model, params, p, _HTTP_MAX_NEW))
                for p in prompts]
        assert [b["result"] for _, b in out] == refs
        assert plan.fired == [("preempt", 4, targets[0])]
        evac = _labelled(fleet[0][2].registry.get(
            "fstpu_evac_lanes_total"))
        assert evac.get("adopted", 0) >= 1, evac
        # drain-path rescue never consults the journal: nothing was
        # lost, so nothing resumes and nothing regenerates
        assert _resume_totals(router) == {}
        evac_rid = None
        for _, b in out:
            try:
                ev = _events(targets[0], b["request_id"])
            except urllib.error.HTTPError:
                continue
            if "evacuated" in ev:
                evac_rid = b["request_id"]
                assert ev[-1] == "evacuated"     # terminal event
                break
        assert evac_rid is not None
        peer_ev = _events(targets[1], evac_rid)
        assert "adopted" in peer_ev and "finished" in peer_ev
    finally:
        _stop_fleet(fleet)


def test_sigkill_adopter_resumes_from_journal_http(tiny):
    """The SIGKILL variant: A drains at index 4 and evacuates to B —
    then B goes dark (sticky transport kill) before its collects
    answer. The router's maybe-executed machinery consults the fleet's
    commit journals, reads the evacuated prefix off A (still draining,
    still serving `GET /partial/<id>`), and re-places the request on C
    with `resume_tokens` — every request 200, token-identical, at
    least one `fstpu_resume_total{outcome="resumed"}`, ZERO journal
    misses (nothing regenerated from token 0), `resumed_from` on C's
    timeline, and ONE assembled trace stitching A's and C's waterfalls
    under the same trace_id."""
    model, params = tiny
    fleet = [_start_replica(tiny, _HTTP_MAX_NEW, tick_delay_s=0.03)
             for _ in range(3)]
    targets = [f"127.0.0.1:{s.server_address[1]}"
               for s, *_ in fleet]
    a, b, c = targets
    plan = FleetFaultPlan(preempt_at={4: a}, kill_at={4: b})
    plan.preempt_with(a, _preempt_cb(
        fleet[0][1], fleet[0][2], [f"http://{b}"]))
    transport = plan.wrap(UrllibTransport())
    router = FleetRouter(
        FleetConfig(replicas=targets, recovery_probes=1,
                    backoff_base_s=0.0, request_timeout_s=60.0),
        transport=transport, sleep=lambda s: None)
    transport.bind(router)
    try:
        router.poll_once()
        assert router.healthy_count() == 3
        rng = np.random.RandomState(4)
        prompts = [rng.randint(3, 96, 4 + (i % 3)).astype(np.int32)
                   for i in range(10)]
        out = _route_many(router, prompts, width=5)
        assert [code for code, _ in out] == [200] * len(prompts)
        refs = [" ".join(str(t) for t in
                         _ref(model, params, p, _HTTP_MAX_NEW))
                for p in prompts]
        assert [b_["result"] for _, b_ in out] == refs
        assert ("preempt", 4, a) in plan.fired
        assert _labelled(fleet[0][2].registry.get(
            "fstpu_evac_lanes_total")).get("adopted", 0) >= 1
        resume = _resume_totals(router)
        assert resume.get("resumed", 0) >= 1, resume
        assert resume.get("miss", 0) == 0, resume
        # find the recovered request: resumed_from on C's timeline
        resumed_rid, resumed_body = None, None
        for _, body in out:
            try:
                ev = _events(c, body["request_id"])
            except urllib.error.HTTPError:
                continue
            if "resumed_from" in ev:
                resumed_rid, resumed_body = body["request_id"], body
                break
        assert resumed_rid is not None
        # the drained source still serves the journal it resumed from
        with urllib.request.urlopen(
                f"http://{a}/partial/{resumed_rid}", timeout=10) as r:
            part = json.loads(r.read())
        assert part["state"] == "evacuated"
        assert len(part["tokens"]) >= 1
        # the rescuer's journal holds the finished run, result decoded
        with urllib.request.urlopen(
                f"http://{c}/partial/{resumed_rid}", timeout=10) as r:
            part_c = json.loads(r.read())
        assert part_c["state"] == "finished"
        assert part_c["result"] == resumed_body["result"]
        assert _events(a, resumed_rid)[-1] == "evacuated"
        # ONE trace: the drained replica's waterfall and the rescuer's
        # joined under the same trace_id (the dead adopter degrades to
        # an error entry, never an unreadable trace)
        assembled = router.assemble(resumed_body["trace_id"])
        assert assembled is not None
        reps = assembled["replicas"]
        assert "waterfall" in reps[a] and "waterfall" in reps[c]
        assert reps[a]["waterfall"]["request_id"] == resumed_rid
        assert reps[c]["waterfall"]["request_id"] == resumed_rid
        a_ev = [e["event"]
                for e in reps[a]["waterfall"]["events"]]
        c_ev = [e["event"]
                for e in reps[c]["waterfall"]["events"]]
        assert "evacuated" in a_ev
        assert "resumed_from" in c_ev and "finished" in c_ev
    finally:
        _stop_fleet(fleet)
