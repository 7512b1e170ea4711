"""Streaming tier (ISSUE 20, docs/streaming.md): sampled speculative
serving delivered token-by-token over SSE with resume-from-token-k.

The load-bearing contracts:

- greedy STREAMED output is token-identical to the batch path on both
  KV layouts, with the one-decode-compile pin intact (streaming is
  delivery-only — it must never touch the decode graph);
- sampled decode with a pinned per-lane seed is reproducible
  run-to-run (same seed ⇒ byte-identical stream, twice; different
  seed ⇒ different stream), because the lane key derives from
  `(engine seed, request seed)` — never from placement or co-tenancy;
- the self-draft tower (draft layers sharing the target's embedding)
  verifies greedy token-identical to non-spec, keeps ONE decode
  compile, and beats prompt-lookup's committed/forward on
  non-repetitive traffic;
- a spec engine accepts `resume_tokens` (resume-from-token-k) and the
  resumed continuation is token-identical to the uninterrupted run;
- the SSE wire format round-trips; `Last-Event-ID` reconnect replays
  from token k+1 on the stdlib api path;
- the fleet router's streaming proxy survives a replica death
  mid-stream with a GAPLESS token-identical concatenated stream
  (journal resume + dedupe cursor), and follows an `evacuated`
  terminal event to the adopter transparently;
- `/stats` grows `streams_active` only after the first streamed
  request (never-streamed engines stay byte-shape-identical).
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig
from fengshen_tpu.streaming import (StreamBook, TokenStream,
                                    format_event, iter_sse)
from fengshen_tpu.utils.generate import generate

PAGED = dict(kv_layout="paged", kv_block_size=16)


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


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, 96, n).astype(np.int32) for n in lengths]


def _ref(model, params, prompt, max_new):
    out = np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                              max_new_tokens=max_new,
                              eos_token_id=None, pad_token_id=0))
    return out[0, len(prompt):].tolist()


def _stream_events(engine, prompts, seeds=None, **submit_kw):
    """Submit every prompt with stream=True, drain the engine, return
    each request's full event list."""
    reqs = []
    for i, p in enumerate(prompts):
        kw = dict(submit_kw)
        if seeds is not None:
            kw["seed"] = seeds[i]
        reqs.append(engine.submit(p, stream=True,
                                  request_id=f"sr{i}", **kw))
    streams = [engine.streams.get(r.request_id) for r in reqs]
    engine.run_until_idle()
    return [list(s.events(0, timeout=30.0)) for s in streams]


def _tokens_of(events):
    assert events[-1][0] == "done", events[-1]
    return [t for (kind, _i, t) in events if kind == "token"]


# ---- SSE wire format ----------------------------------------------------

def test_sse_roundtrip():
    frames = (format_event("token", {"token": 42}, event_id=0) +
              format_event("token", {"token": 7}, event_id=1) +
              format_event("done", {"finish_reason": "length"},
                           event_id=2))
    evs = list(iter_sse(frames.decode().splitlines()))
    assert [(e["event"], e["id"]) for e in evs] == \
        [("token", 0), ("token", 1), ("done", 2)]
    assert evs[0]["data"] == {"token": 42}
    assert evs[2]["data"] == {"finish_reason": "length"}


def test_iter_sse_tolerates_comments_and_split_data():
    raw = (": keep-alive\n\n"
           "id: 3\nevent: token\ndata: {\"to\ndata: ken\": 1}\n\n")
    evs = list(iter_sse(raw.splitlines()))
    assert evs == [{"event": "token", "id": 3, "data": {"token": 1}}]


def test_token_stream_replay_and_terminal():
    s = TokenStream()
    s.publish([5, 6])
    s.publish([5, 6, 7], finish_reason="length")
    evs = list(s.events(0, timeout=1.0))
    assert evs == [("token", 0, 5), ("token", 1, 6), ("token", 2, 7),
                   ("done", 3, "length")]
    # replay from k: the Last-Event-ID contract
    assert list(s.events(2, timeout=1.0)) == [
        ("token", 2, 7), ("done", 3, "length")]


# ---- greedy streamed == batch, both layouts, one compile ----------------

@pytest.mark.parametrize("layout_kw", [{}, PAGED],
                         ids=["slot", "paged"])
def test_greedy_streamed_token_identical(tiny, layout_kw):
    model, params = tiny
    prompts = _prompts((5, 11, 7))
    refs = [_ref(model, params, p, 8) for p in prompts]
    engine = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=2, buckets=(8, 16), max_new_tokens=8, max_queue=16,
        **layout_kw))
    events = _stream_events(engine, prompts)
    assert [_tokens_of(e) for e in events] == refs
    # event ids are the token indices, contiguous from 0
    for evs in events:
        assert [i for (k, i, _t) in evs if k == "token"] == \
            list(range(8))
    # streaming is delivery-only: the decode graph compiled ONCE
    assert engine._decode_jit._cache_size() == 1


# ---- pinned-seed sampled reproducibility --------------------------------

def test_sampled_stream_pinned_seed_reproducible(tiny):
    model, params = tiny
    prompts = _prompts((5, 11, 7))

    def run(seed0):
        engine = ContinuousBatchingEngine(model, params, EngineConfig(
            num_slots=2, buckets=(8, 16), max_new_tokens=8,
            max_queue=16, do_sample=True, temperature=0.9, top_k=20))
        return _stream_events(engine, prompts,
                              seeds=[seed0 + i for i in range(3)])

    a, b, c = run(7), run(7), run(11)
    # same pinned seed ⇒ byte-identical event streams, twice
    assert a == b
    assert [_tokens_of(e) for e in a] != [_tokens_of(e) for e in c]


def test_sampled_seed_default_derives_from_request_id(tiny):
    """No explicit seed: the lane key folds from the request id, so a
    retry under the SAME id reproduces the same stream — the fleet
    router's resubmit-and-dedupe path depends on this."""
    model, params = tiny
    prompt = _prompts((9,))[0]

    def run():
        engine = ContinuousBatchingEngine(model, params, EngineConfig(
            num_slots=2, buckets=(8, 16), max_new_tokens=8,
            max_queue=16, do_sample=True, temperature=0.9, top_k=20))
        req = engine.submit(prompt, request_id="pinned-id")
        engine.run_until_idle()
        return req.tokens

    assert run() == run()


# ---- self-draft tower ---------------------------------------------------

def test_self_draft_greedy_parity_one_compile(tiny):
    model, params = tiny
    prompts = _prompts((5, 11, 7))
    refs = [_ref(model, params, p, 8) for p in prompts]
    for layout_kw in ({}, PAGED):
        engine = ContinuousBatchingEngine(model, params, EngineConfig(
            num_slots=2, buckets=(8, 16), max_new_tokens=8,
            max_queue=16, spec_mode="self_draft", spec_gamma=4,
            spec_draft_layers=1, **layout_kw))
        assert engine.generate_all(prompts) == refs
        assert engine._decode_jit._cache_size() == 1


def test_self_draft_sampled_pinned_seed_reproducible(tiny):
    model, params = tiny
    prompts = _prompts((5, 11))

    def run():
        engine = ContinuousBatchingEngine(model, params, EngineConfig(
            num_slots=2, buckets=(8, 16), max_new_tokens=8,
            max_queue=16, spec_mode="self_draft", spec_gamma=4,
            spec_draft_layers=1, do_sample=True, temperature=0.9,
            top_k=20))
        return _stream_events(engine, prompts, seeds=[3, 4])

    assert run() == run()


def test_self_draft_beats_lookup_on_nonrepetitive(tiny):
    """The tentpole's acceptance direction: on uniform-random prompts
    (nothing for the ngram copy to find) the draft tower's acceptance
    must exceed prompt-lookup's on identical traffic."""
    model, params = tiny
    prompts = _prompts((16, 16, 16, 16), seed=3)

    def acceptance(mode, **extra):
        engine = ContinuousBatchingEngine(model, params, EngineConfig(
            num_slots=2, buckets=(16, 24), max_new_tokens=12,
            max_queue=8, spec_mode=mode, spec_gamma=4, **extra))
        engine.generate_all(prompts)
        return engine.stats()["spec_acceptance_rate"]

    assert acceptance("self_draft", spec_draft_layers=1) > \
        acceptance("prompt_lookup")


def test_spec_resume_token_identical(tiny):
    """Resume-from-token-k on a SPEC engine (the restriction this PR
    lifts): prefix from the journal + spec continuation must equal the
    uninterrupted spec run."""
    model, params = tiny
    prompt = _prompts((9,))[0]
    for mode, extra in (("prompt_lookup", {}),
                        ("self_draft", {"spec_draft_layers": 1})):
        cfg = dict(num_slots=2, buckets=(8, 16), max_new_tokens=10,
                   max_queue=8, spec_mode=mode, spec_gamma=4, **extra)
        e1 = ContinuousBatchingEngine(model, params,
                                      EngineConfig(**cfg))
        full = e1.generate_all([prompt])[0]
        e2 = ContinuousBatchingEngine(model, params,
                                      EngineConfig(**cfg))
        req = e2.submit(prompt, resume_tokens=full[:4],
                        resume_source="test")
        e2.run_until_idle()
        assert req.tokens == full, (mode, req.tokens, full)


# ---- /stats shape gating ------------------------------------------------

def test_stats_streams_key_gating(tiny):
    model, params = tiny
    prompts = _prompts((5,))
    cfg = EngineConfig(num_slots=2, buckets=(8,), max_new_tokens=4,
                       max_queue=8)
    plain = ContinuousBatchingEngine(model, params, cfg)
    plain.generate_all(prompts)
    assert "streams_active" not in plain.stats()

    streamed = ContinuousBatchingEngine(model, params, cfg)
    _stream_events(streamed, prompts)
    st = streamed.stats()
    assert st["streams_active"] == 0
    # only EXTENDS: every non-stream key the plain engine reports is
    # still present under the same name
    assert set(plain.stats()) <= set(st)


# ---- stdlib api path: SSE route + Last-Event-ID reconnect ---------------

class _IntTokenizer:
    eos_token_id = None
    pad_token_id = 0

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(int(t)) for t in ids)


def _sse_post(base, payload, headers=None, timeout=60):
    req = urllib.request.Request(
        f"{base}/api/text_generation/stream",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json",
                 **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.headers.get("Content-Type") == "text/event-stream"
        return list(iter_sse(r))


@pytest.fixture(scope="module")
def sse_replica(tiny):
    """The base url of one api server over a continuous engine."""
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server,
                                       start_continuous_engine)
    from fengshen_tpu.pipelines.text_generation import Pipeline

    model, params = tiny
    pipe = Pipeline(module=model, params=params,
                    tokenizer=_IntTokenizer(), max_new_tokens=6,
                    eos_token_id=None, pad_token_id=0)
    engine = start_continuous_engine(
        pipe, {"num_slots": 2, "buckets": (8,), "max_queue": 8})
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        yield f"http://127.0.0.1:{port}"
    finally:
        server.shutdown()
        server.server_close()
        engine.stop()


def test_stdlib_sse_route_and_reconnect(sse_replica):
    base = sse_replica
    # the non-streamed answer is the reference
    req = urllib.request.Request(
        f"{base}/api/text_generation",
        data=json.dumps({"input_text": "5 7 9",
                         "request_id": "batch-1"}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        ref = json.loads(r.read())["result"]

    evs = _sse_post(base, {"input_text": "5 7 9",
                           "request_id": "sse-1"})
    toks = [e["data"]["token"] for e in evs
            if e["event"] == "token"]
    ids = [e["id"] for e in evs if e["event"] == "token"]
    assert ids == list(range(6))
    assert evs[-1]["event"] == "done"
    assert evs[-1]["data"]["result"] == ref
    assert " ".join(str(t) for t in toks) == ref

    # Last-Event-ID reconnect (header path): replay from k+1
    evs2 = _sse_post(base, {"request_id": "sse-1"},
                     headers={"Last-Event-ID": "2"})
    assert [e["id"] for e in evs2 if e["event"] == "token"] == \
        [3, 4, 5]
    assert [e["data"]["token"] for e in evs2
            if e["event"] == "token"] == toks[3:]
    assert evs2[-1]["event"] == "done"

    # body-field reconnect is the same contract
    evs3 = _sse_post(base, {"request_id": "sse-1",
                            "last_event_id": 4})
    assert [e["id"] for e in evs3 if e["event"] == "token"] == [5]

    # unknown id reconnect: 404 before any stream byte
    with pytest.raises(urllib.error.HTTPError) as exc:
        _sse_post(base, {"request_id": "nope",
                         "last_event_id": 0})
    assert exc.value.code == 404

    # fresh submission without input_text: 422
    with pytest.raises(urllib.error.HTTPError) as exc:
        _sse_post(base, {"max_new_tokens": 3})
    assert exc.value.code == 422

    # reproducibility across the wire: same explicit seed twice
    s1 = _sse_post(base, {"input_text": "5 7 9", "seed": 13,
                          "request_id": "sse-s1"})
    s2 = _sse_post(base, {"input_text": "5 7 9", "seed": 13,
                          "request_id": "sse-s2"})
    assert ([e["data"] for e in s1 if e["event"] == "token"] ==
            [e["data"] for e in s2 if e["event"] == "token"])


# ---- fleet router: kill mid-stream, gapless resume ----------------------

class _ManualClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class _DyingStreamTransport:
    """Replica a:1 streams `die_after` tokens then dies mid-stream
    (maybe-executed); its committed prefix of `journal_len` tokens is
    journaled fleet-wide; b:2 serves the resumed request to the end,
    REPLAYING from token 0 like a real engine stream does."""

    def __init__(self, n_tokens=8, die_after=3, journal_len=5):
        from fengshen_tpu.fleet import TransportError
        self._err = TransportError
        self.n, self.die, self.jlen = n_tokens, die_after, journal_len
        self.bodies = []

    @staticmethod
    def _tok(i):
        return 100 + i

    def request(self, base_url, method, path, body, timeout_s):
        name = base_url.split("://", 1)[1]
        if path == "/healthz":
            return 200, {"ready": True}
        if path == "/stats":
            return 200, {"slots_active": 0, "queue_depth": 0,
                         "num_slots": 4, "draining": False}
        if path.startswith("/partial/"):
            if name == "b:2":
                return 200, {"state": "running",
                             "tokens": [self._tok(i)
                                        for i in range(self.jlen)]}
            raise self._err("dead", sent=False)
        return 404, {}

    def stream(self, base_url, method, path, body, timeout_s):
        name = base_url.split("://", 1)[1]
        self.bodies.append((name, dict(body)))
        if name == "a:1":
            for i in range(self.die):
                yield {"event": "token", "id": i,
                       "data": {"token": self._tok(i)}}
            raise self._err("connection reset mid-stream", sent=True)
        assert body.get("resume_tokens") == \
            [self._tok(i) for i in range(self.jlen)], body
        for i in range(self.n):
            yield {"event": "token", "id": i,
                   "data": {"token": self._tok(i)}}
        yield {"event": "done", "id": self.n,
               "data": {"request_id": body["request_id"],
                        "finish_reason": "length"}}


def test_router_stream_kill_gapless_resume():
    """The 2-replica kill-mid-stream pin: the client's concatenated
    stream has event ids exactly 0..n-1 (no gap, no duplicate) and the
    journaled committed prefix is delivered BEFORE the retry replica
    even answers."""
    from fengshen_tpu.fleet import FleetConfig, FleetRouter

    t = _DyingStreamTransport()
    router = FleetRouter(
        FleetConfig(replicas=("a:1", "b:2"), recovery_probes=1,
                    seed=0),
        transport=t, clock=_ManualClock(), sleep=lambda s: None)
    router.poll_once()
    code, body, frames = router.route_generate_stream(
        {"input_text": "x"})
    assert code == 200 and body is None
    evs = list(iter_sse(b"".join(frames).decode().splitlines()))
    toks = [(e["id"], e["data"]["token"]) for e in evs
            if e["event"] == "token"]
    assert toks == [(i, 100 + i) for i in range(8)]
    assert evs[-1]["event"] == "done"
    # a:1 saw the fresh body, b:2 the journal-resumed one
    assert [n for n, _b in t.bodies] == ["a:1", "b:2"]
    assert "resume_tokens" not in t.bodies[0][1]


def test_router_streams_a_replicas_sse_over_the_real_transport(
        sse_replica):
    """The transport the fleet runs with (`UrllibTransport.stream`)
    against a real replica: the router's frames are the replica's own
    events, and a refusal before the first stream byte (an HTTP status,
    not an exception) reaches the client as one `error` event."""
    from fengshen_tpu.fleet import FleetConfig, FleetRouter

    direct = _sse_post(sse_replica, {"input_text": "5 7 9",
                                     "request_id": "direct-1"})
    router = FleetRouter(FleetConfig(
        replicas=(sse_replica.split("://", 1)[1],), recovery_probes=1))
    try:
        router.poll_once()
        code, body, frames = router.route_generate_stream(
            {"input_text": "5 7 9", "request_id": "routed-1"})
        assert code == 200 and body is None
        evs = list(iter_sse(b"".join(frames).decode().splitlines()))
        tokens = lambda events: [  # noqa: E731
            (e["id"], e["data"]["token"]) for e in events
            if e["event"] == "token"]
        assert tokens(evs) == tokens(direct) and len(tokens(evs)) == 6
        assert evs[-1]["event"] == "done"
        assert evs[-1]["data"]["result"] == direct[-1]["data"]["result"]

        code, body, frames = router.route_generate_stream(
            {"request_id": "routed-2", "max_new_tokens": 3})
        (refusal,) = iter_sse(b"".join(frames).decode().splitlines())
        assert refusal["event"] == "error"
        assert refusal["data"]["status"] == 422
        assert refusal["data"]["error"] == "input_text required"
    finally:
        router.stop()


def test_router_stream_follows_evacuation():
    from fengshen_tpu.fleet import FleetConfig, FleetRouter

    class EvacTransport(_DyingStreamTransport):
        def stream(self, base_url, method, path, body, timeout_s):
            name = base_url.split("://", 1)[1]
            self.bodies.append((name, dict(body)))
            if name == "a:1":
                for i in range(2):
                    yield {"event": "token", "id": i,
                           "data": {"token": self._tok(i)}}
                yield {"event": "evacuated", "id": 2,
                       "data": {"request_id": body["request_id"],
                                "target": "http://b:2"}}
                return
            # the adopter sees a RECONNECT body, not a resubmit
            assert body.get("last_event_id") == 1, body
            assert "input_text" not in body
            for i in range(2, 6):
                yield {"event": "token", "id": i,
                       "data": {"token": self._tok(i)}}
            yield {"event": "done", "id": 6,
                   "data": {"request_id": body["request_id"],
                            "finish_reason": "eos"}}

    t = EvacTransport()
    router = FleetRouter(
        FleetConfig(replicas=("a:1", "b:2"), recovery_probes=1,
                    seed=0),
        transport=t, clock=_ManualClock(), sleep=lambda s: None)
    router.poll_once()
    _code, _body, frames = router.route_generate_stream(
        {"input_text": "x"})
    evs = list(iter_sse(b"".join(frames).decode().splitlines()))
    toks = [(e["id"], e["data"]["token"]) for e in evs
            if e["event"] == "token"]
    assert toks == [(i, 100 + i) for i in range(6)]
    assert evs[-1]["event"] == "done"


def test_router_stream_draining_refusal():
    from fengshen_tpu.fleet import FleetConfig, FleetRouter
    router = FleetRouter(
        FleetConfig(replicas=("a:1",), recovery_probes=1),
        transport=_DyingStreamTransport(), clock=_ManualClock(),
        sleep=lambda s: None)
    router.drain()
    code, body, frames = router.route_generate_stream(
        {"input_text": "x"})
    assert code == 503 and frames is None
    assert body["reason"] == "draining"
