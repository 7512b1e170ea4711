"""Per-request lifecycle tracing, flight recorder, and debug
introspection (ISSUE 8).

The load-bearing contracts:

- every request's timeline derives a waterfall whose queue_wait +
  prefill + decode phases SUM to its wall-clock latency, and timelines
  add only host-side work: greedy output stays token-identical to
  sequential generate with ONE decode compile, on non-spec AND spec
  engines;
- a faulted engine run dumps a complete post-mortem bundle (events
  jsonl, /stats snapshot, engine/model config, last-N request
  timelines), deterministically (byte-identical across
  PYTHONHASHSEED); a trainer step-guard rewind dumps the last window
  of step stats the same way;
- `GET /debug/requests[/<id>]` + `POST /debug/dump` work on the stdlib
  API path; `fstpu_http_request_seconds{route}` and
  `fstpu_request_phase_seconds{phase}` land in /metrics;
- /stats only EXTENDS (uptime_s, last_error as type+age — no
  traceback).
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.observability import (FlightRecorder, JsonlSink,
                                        RequestTimeline, get_registry)
from fengshen_tpu.serving import (ContinuousBatchingEngine, EngineConfig,
                                  QueueFull)
from fengshen_tpu.utils.generate import generate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, dtype="float32")
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, 96, n).astype(np.int32) for n in lengths]


def _ref(model, params, prompt, max_new, **kw):
    out = np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                              max_new_tokens=max_new, **kw))
    return out[0, len(prompt):].tolist()


class _FakeTokenizer:
    eos_token_id = None
    pad_token_id = 0

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(int(t)) for t in ids)


def _gen_pipeline(tiny, **kw):
    from fengshen_tpu.pipelines.text_generation import Pipeline
    model, params = tiny
    return Pipeline(module=model, params=params,
                    tokenizer=_FakeTokenizer(), **kw)


def _phase_sum_matches(d, tol=1e-3):
    ph = d["phases"]
    total = ph["queue_wait_s"] + ph["prefill_s"] + ph["decode_s"]
    assert abs(total - ph["total_s"]) <= tol, ph
    assert all(v >= 0 for v in ph.values()), ph


# ---- timeline unit behavior ---------------------------------------------

def test_timeline_phases_and_event_cap():
    tl = RequestTimeline(t0=100.0, max_events=4)
    tl.add(100.0, "enqueued", prompt_tokens=3)
    tl.add(100.5, "prefill_start", bucket=8)
    tl.add(101.0, "first_token")
    tl.add(102.0, "commit", n=1, tick_s=0.25)
    tl.add(102.5, "commit", n=1, tick_s=0.25)   # over cap: dropped
    assert tl.dropped == 1
    # the dropped commit's tick time still counts against stall, and a
    # TERMINAL event always lands even past the cap — a capped
    # timeline must keep its end mark
    tl.add(103.0, "finished", reason="length")
    assert [e[1] for e in tl.events][-1] == "finished"
    ph = tl.phases(now=999.0)                   # terminal wins over now
    assert ph == {"queue_wait_s": 0.5, "lock_wait_s": 0.0,
                  "prefill_s": 0.5,
                  "decode_s": 2.0, "decode_stall_s": 1.5,
                  "total_s": 3.0}
    # a terminal event pins the end regardless of `now`
    tl2 = RequestTimeline(t0=0.0)
    tl2.add(0.0, "enqueued")
    tl2.add(1.0, "rejected", reason="queue_full")
    ph2 = tl2.phases(now=50.0)
    assert ph2["total_s"] == 1.0
    assert ph2["queue_wait_s"] == 1.0      # never admitted: all wait
    assert ph2["prefill_s"] == 0.0 and ph2["decode_s"] == 0.0
    # `enqueued` is stamped once the scheduler's lock is held: submit
    # to enqueued is lock_wait_s, a PART of queue_wait_s (the three
    # telescoping phases do not change)
    tl3 = RequestTimeline(t0=10.0)
    tl3.add(10.4, "enqueued")
    tl3.add(11.0, "prefill_start")
    tl3.add(11.5, "first_token")
    tl3.add(12.0, "finished", reason="length")
    ph3 = tl3.phases()
    assert ph3["lock_wait_s"] == 0.4 and ph3["queue_wait_s"] == 1.0
    assert ph3["queue_wait_s"] + ph3["prefill_s"] + ph3["decode_s"] == \
        ph3["total_s"] == 2.0
    # a request rejected while it still waited has no `enqueued` mark
    assert RequestTimeline(t0=0.0).phases(now=3.0)["lock_wait_s"] == 0.0


# ---- engine waterfall + parity (the tentpole contract) ------------------

def test_waterfall_phases_sum_to_latency(tiny):
    """Every finished request's derived phases partition its wall-clock
    latency; the lifecycle marks are all present and ordered."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=16))
    reqs = [eng.submit(p) for p in _prompts((5, 11, 16, 7))]
    eng.run_until_idle()
    for req in reqs:
        d = eng.debug_request(req.request_id)
        assert d is not None and d["state"] == "finished"
        _phase_sum_matches(d)
        names = [e["event"] for e in d["events"]]
        for mark in ("enqueued", "admitted", "prefill_start",
                     "first_token", "commit", "finished"):
            assert mark in names
        assert names[0] == "enqueued" and names[-1] == "finished"
        # commits carry the per-tick token counts: prefill commits the
        # first token, ticks the other max_new-1
        committed = sum(e["n"] for e in d["events"]
                        if e["event"] == "commit")
        assert committed == len(req.tokens) - 1
        # ttft == queue_wait + prefill by construction
        ph = d["phases"]
        assert abs(d["ttft_s"] -
                   (ph["queue_wait_s"] + ph["prefill_s"])) <= 1e-3


def test_timeline_parity_and_one_compile(tiny):
    """Timelines must not add traced work: with tracing active, greedy
    output is still token-identical to sequential generate under
    staggered admission, with exactly ONE decode compile — on the
    non-spec AND the spec engine."""
    model, params = tiny
    prompts = _prompts((5, 11, 16, 7))
    refs = [_ref(model, params, p, 8) for p in prompts]
    for extra in ({}, {"spec_mode": "prompt_lookup", "spec_gamma": 2,
                       "spec_ngram": 2}):
        eng = ContinuousBatchingEngine(
            model, params,
            EngineConfig(num_slots=2, buckets=(8, 16),
                         max_new_tokens=8, max_queue=16, **extra))
        if not hasattr(eng._decode_jit, "_cache_size"):
            pytest.skip("jit cache introspection unavailable")
        reqs = [eng.submit(p) for p in prompts[:2]]
        for _ in range(3):
            eng.step()
        reqs += [eng.submit(p) for p in prompts[2:]]
        eng.run_until_idle()
        for req, ref in zip(reqs, refs):
            assert req.tokens == ref
            d = eng.debug_request(req.request_id)
            _phase_sum_matches(d)
            commits = [e for e in d["events"] if e["event"] == "commit"]
            assert sum(e["n"] for e in commits) == len(ref) - 1
            if extra:
                # spec commits carry accept counts for the waterfall
                assert all("accepted" in e for e in commits)
        assert eng._decode_jit._cache_size() == 1


def test_debug_requests_ring_and_rejections(tiny):
    """The list endpoint surfaces in-flight + recent; queue-full
    rejections join the ring with reason and phases; the ring is
    bounded by debug_ring; unknown ids return None."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=2, max_queue=2,
                                    debug_ring=3))
    for p in _prompts((4, 5)):
        eng.submit(p)
    with pytest.raises(QueueFull):
        eng.submit(_prompts((6,))[0], request_id="rejected-1")
    dbg = eng.debug_requests()
    assert len(dbg["in_flight"]) == 2
    rej = [r for r in dbg["recent"] if r["request_id"] == "rejected-1"]
    assert rej and rej[0]["state"] == "rejected"
    assert rej[0]["finish_reason"] == "queue_full"
    d = eng.debug_request("rejected-1")
    assert d["events"][-1]["event"] == "rejected"
    # 413-class rejections (the lane cannot hold it) join the ring too — a
    # burst of 413s must be diagnosable, not invisible
    from fengshen_tpu.serving import PromptTooLong
    with pytest.raises(PromptTooLong):
        eng.submit(_prompts((70,))[0], request_id="too-long-1")
    d413 = eng.debug_request("too-long-1")
    assert d413["state"] == "rejected"
    assert d413["finish_reason"] == "prompt_too_long"
    assert d413["events"][-1]["prompt_tokens"] == 70
    eng.run_until_idle()
    dbg = eng.debug_requests()
    assert not dbg["in_flight"]
    assert len(dbg["recent"]) == 3          # bounded: oldest aged out
    assert eng.debug_request("never-existed") is None


def test_stats_uptime_and_last_error(tiny):
    """/stats gains uptime_s and last_error (type + age only — never a
    traceback payload); a serve-loop tick error populates it and the
    phase histograms stay renderable."""
    from fengshen_tpu.observability import render_prometheus

    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=2, max_queue=4))
    stats = eng.stats()
    assert stats["uptime_s"] >= 0 and stats["last_error"] is None
    real = eng._decode_jit
    boom = [True]

    def flaky(*args):
        if boom[0]:
            boom[0] = False
            raise RuntimeError("transient XLA failure")
        return real(*args)

    eng._decode_jit = flaky
    eng.start()
    try:
        failed = eng.submit(_prompts((5,))[0])
        assert failed.wait(timeout=60)
        assert failed.finish_reason == "engine_error"
    finally:
        eng.stop()
    stats = eng.stats()
    assert stats["last_error"] == {"type": "RuntimeError",
                                   "age_s": stats["last_error"]["age_s"]}
    assert stats["last_error"]["age_s"] >= 0
    # the failed request's timeline landed in the ring
    d = eng.debug_request(failed.request_id)
    assert d["state"] == "expired"
    text = render_prometheus(eng.metrics.registry)
    assert 'fstpu_request_phase_seconds' in text


def test_engine_tick_error_dumps_postmortem(tiny, tmp_path):
    """The acceptance bar: a faulted engine run produces a complete
    bundle — manifest, events jsonl (with the tick error), and the
    engine provider's stats/config/last-N request timelines."""
    model, params = tiny
    rec = FlightRecorder(dump_dir=str(tmp_path))
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=2, max_queue=4),
        recorder=rec)
    real = eng._decode_jit
    boom = [True]

    def flaky(*args):
        if boom[0]:
            boom[0] = False
            raise RuntimeError("injected fault")
        return real(*args)

    eng._decode_jit = flaky
    eng.start()
    try:
        failed = eng.submit(_prompts((5,))[0], request_id="victim")
        assert failed.wait(timeout=60)
        assert failed.finish_reason == "engine_error"
        ok = eng.submit(_prompts((5,))[0])
        assert ok.wait(timeout=60)
    finally:
        eng.stop()
    bundles = sorted(os.listdir(tmp_path))
    assert bundles and bundles[0].endswith("engine_tick_error")
    bundle = tmp_path / bundles[0]
    manifest = json.loads((bundle / "manifest.json").read_text())
    assert manifest["reason"] == "engine_tick_error"
    assert manifest["extra"]["error_type"] == "RuntimeError"
    assert sorted(manifest["files"]) == ["engine.json", "events.jsonl"]
    assert not manifest["provider_errors"]
    events = [json.loads(line) for line in
              (bundle / "events.jsonl").read_text().splitlines()]
    assert any(e.get("event") == "serving_tick_error" for e in events)
    assert any(e.get("event") == "metrics_snapshot" for e in events)
    engine_dump = json.loads((bundle / "engine.json").read_text())
    assert engine_dump["stats"]["expired"] >= 1
    assert "EngineConfig" in engine_dump["engine_config"]
    victims = [r for r in engine_dump["requests"]
               if r["request_id"] == "victim"]
    assert victims and victims[0]["state"] == "expired"
    assert victims[0]["events"]             # the full timeline rode along


# ---- flight recorder unit behavior --------------------------------------

def test_flight_recorder_ring_capacity_and_providers(tmp_path):
    clock = iter(float(i) for i in range(10_000))
    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path),
                         clock=lambda: next(clock))
    for i in range(20):
        rec.record({"event": "tick", "i": i})
    rec.attach("good", lambda: {"b": 2, "a": 1})
    rec.attach("bad", lambda: 1 / 0)
    b1 = rec.dump("first", extra={"k": "v"})
    b2 = rec.dump("first")
    assert os.path.basename(b1) == "dump-0000-first"
    assert os.path.basename(b2) == "dump-0001-first"   # seq, not clobber
    events = [json.loads(line) for line in
              open(os.path.join(b1, "events.jsonl"))]
    assert len(events) == 8                            # bounded ring
    assert [e["i"] for e in events] == list(range(12, 20))
    manifest = json.loads(
        open(os.path.join(b1, "manifest.json")).read())
    assert manifest["files"] == ["events.jsonl", "good.json"]
    assert manifest["provider_errors"]["bad"].startswith(
        "ZeroDivisionError")
    assert manifest["extra"] == {"k": "v"}
    assert json.load(open(os.path.join(b1, "good.json"))) == \
        {"a": 1, "b": 2}


def test_flight_recorder_restart_never_clobbers_prior_bundles(tmp_path):
    """A restarted process (fresh seq counter) must skip past the
    bundles its predecessor left — a crash-restart-crash loop keeps
    EVERY post-mortem."""
    first = FlightRecorder(dump_dir=str(tmp_path))
    b0 = first.dump("crash")
    marker = os.path.join(b0, "manifest.json")
    before = open(marker).read()
    second = FlightRecorder(dump_dir=str(tmp_path))   # "restart"
    second.record({"event": "new_life"})
    b1 = second.dump("crash")
    assert b1 != b0
    assert os.path.basename(b1) == "dump-0001-crash"
    assert open(marker).read() == before              # untouched
    assert sorted(os.listdir(tmp_path)) == ["dump-0000-crash",
                                            "dump-0001-crash"]


def test_flight_recorder_snapshot_rate_limit(tmp_path):
    t = [0.0]
    rec = FlightRecorder(dump_dir=str(tmp_path), clock=lambda: t[0],
                         snapshot_interval_s=10.0)
    reg = get_registry()
    assert rec.snapshot_metrics([reg]) is True
    t[0] = 5.0
    assert rec.snapshot_metrics([reg]) is False        # rate-limited
    assert rec.snapshot_metrics([reg], force=True) is True
    t[0] = 16.0
    assert rec.snapshot_metrics([reg]) is True


def test_flight_recorder_sigterm_chains_previous_handler(tmp_path):
    import signal
    rec = FlightRecorder(dump_dir=str(tmp_path))
    fired = []
    original = signal.getsignal(signal.SIGTERM)
    try:
        signal.signal(signal.SIGTERM, lambda s, f: fired.append(s))
        assert rec.install_sigterm()
        signal.raise_signal(signal.SIGTERM)
        assert fired == [signal.SIGTERM]               # chained, not lost
        assert any(b.endswith("sigterm") for b in os.listdir(tmp_path))
    finally:
        signal.signal(signal.SIGTERM, original)


def test_flight_recorder_sigterm_default_disposition_still_dies(tmp_path):
    """With SIG_DFL as the previous handler, the dump must not turn
    SIGTERM into a no-op: the process dumps, then still terminates."""
    script = r"""
import os, signal, sys
from fengshen_tpu.observability import FlightRecorder
signal.signal(signal.SIGTERM, signal.SIG_DFL)
rec = FlightRecorder(dump_dir=sys.argv[1])
assert rec.install_sigterm()
signal.raise_signal(signal.SIGTERM)
print("UNREACHABLE")           # the re-delivered default must kill us
"""
    out = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == -15, (out.returncode, out.stdout)
    assert "UNREACHABLE" not in out.stdout
    assert any(b.endswith("sigterm") for b in os.listdir(tmp_path))


def test_flight_recorder_bundle_deterministic_across_hashseed(tmp_path):
    """Same inputs + injected clock => byte-identical bundles, no
    matter the hash seed (the post-mortem diff workflow depends on
    it)."""
    script = r"""
import hashlib, json, os, sys
from fengshen_tpu.observability import FlightRecorder
clock = iter(float(i) / 10 for i in range(1000))
rec = FlightRecorder(capacity=16, dump_dir=sys.argv[1],
                     clock=lambda: next(clock))
for i in range(20):
    rec.record({"event": "tick", "zz": i, "aa": -i, "mm": {"x": 1, "b": 2}})
rec.attach("prov_b", lambda: {"zeta": 1, "alpha": {"q": 3, "a": 4}})
rec.attach("prov_a", lambda: {"rows": [{"m": i, "z": -i} for i in range(5)]})
bundle = rec.dump("determinism", extra={"b": 2, "a": 1})
h = hashlib.sha256()
for name in sorted(os.listdir(bundle)):
    h.update(name.encode())
    h.update(open(os.path.join(bundle, name), "rb").read())
print(h.hexdigest())
"""
    digests = []
    for seed in ("0", "1"):
        out = subprocess.run(
            [sys.executable, "-c", script,
             str(tmp_path / f"seed{seed}")],
            env={**os.environ, "PYTHONHASHSEED": seed,
                 "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, cwd=REPO)
        assert out.returncode == 0, out.stderr
        digests.append(out.stdout.strip())
    assert digests[0] == digests[1]


# ---- jsonl sink rotation ------------------------------------------------

def test_jsonl_sink_size_rotation(tmp_path):
    """Opt-in max_bytes rotates path -> path.1 -> path.2; every line
    survives somewhere in the chain, byte-identical format."""
    path = str(tmp_path / "metrics.jsonl")
    sink = JsonlSink(path=path, max_bytes=120, backups=2)
    entries = [{"event": "step", "step": i, "loss": float(i)}
               for i in range(12)]
    for e in entries:
        sink(e)
    assert os.path.exists(path) and os.path.exists(path + ".1")
    assert os.path.getsize(path) <= 120
    kept = []
    for name in (path + ".2", path + ".1", path):
        if os.path.exists(name):
            kept += [json.loads(line) for line in open(name)]
    # the chain holds a contiguous SUFFIX of the stream (oldest file
    # may have been dropped), with the exact original payloads
    assert kept == entries[-len(kept):]
    assert len(kept) >= 6
    # no rotation configured -> single unbounded file, unchanged format
    p2 = str(tmp_path / "plain.jsonl")
    s2 = JsonlSink(path=p2)
    for e in entries:
        s2(e)
    assert [json.loads(line) for line in open(p2)] == entries
    assert not os.path.exists(p2 + ".1")


def test_jsonl_sink_rotation_under_concurrent_writers(tmp_path):
    """ISSUE 11 satellite: two threads logging across rotation
    boundaries — every surviving line parses (no interleaved/corrupt
    writes), no line is lost from the retained window, and every
    backup in the chain is well-formed jsonl. The sink's internal lock
    is what makes the multi-step rotate-then-append atomic; without it
    a racing writer can append to the file mid-rename and lose its
    line."""
    path = str(tmp_path / "metrics.jsonl")
    # small cap + a backup chain deep enough for the WHOLE stream:
    # every line survives somewhere, so lost writes are detectable,
    # not masked by legitimate aging-out (2x100 lines x ~60 B ≈ 12 KB
    # « 64 backups x 256 B + slack)
    sink = JsonlSink(path=path, max_bytes=256, backups=64)
    n_per_thread = 100
    errors = []

    def writer(tag):
        try:
            for i in range(n_per_thread):
                sink({"event": "step", "writer": tag, "i": i,
                      "pad": "x" * (i % 7)})
        except Exception as e:  # noqa: BLE001 — surface in-thread
            errors.append(e)    # failures as test failures

    threads = [threading.Thread(target=writer, args=(t,))
               for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    kept = []
    chain = [path] + [f"{path}.{i}" for i in range(1, 65)]
    for name in chain:
        if not os.path.exists(name):
            continue
        with open(name) as f:
            for line in f:
                entry = json.loads(line)     # well-formed or it raises
                assert entry["event"] == "step"
                kept.append(entry)
    # zero lost lines: both writers' full sequences are present
    assert len(kept) == 2 * n_per_thread
    for tag in ("a", "b"):
        seq = sorted(e["i"] for e in kept if e["writer"] == tag)
        assert seq == list(range(n_per_thread))


# ---- API surface (stdlib path) ------------------------------------------

def test_debug_endpoints_and_http_latency_stdlib(tiny, tmp_path):
    """GET /debug/requests[/<id>], POST /debug/dump, and the
    fstpu_http_request_seconds{route} histogram on the stdlib server."""
    import urllib.error
    import urllib.request

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server,
                                       start_continuous_engine)

    pipe = _gen_pipeline(tiny, max_new_tokens=4)
    rec = FlightRecorder(dump_dir=str(tmp_path))
    engine = start_continuous_engine(
        pipe, {"num_slots": 2, "buckets": (8,), "max_queue": 8},
        recorder=rec)
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine, recorder=rec)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"
    try:
        req = urllib.request.Request(
            f"{base}/api/text_generation",
            data=json.dumps({"input_text": "5 7 9"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            rid = json.loads(r.read())["request_id"]
        with urllib.request.urlopen(f"{base}/debug/requests",
                                    timeout=10) as r:
            listing = json.loads(r.read())
        assert any(e["request_id"] == rid for e in listing["recent"])
        with urllib.request.urlopen(f"{base}/debug/requests/{rid}",
                                    timeout=10) as r:
            d = json.loads(r.read())
        assert d["state"] == "finished"
        _phase_sum_matches(d)
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}/debug/requests/nope",
                                   timeout=10)
        assert exc.value.code == 404
        dump_req = urllib.request.Request(f"{base}/debug/dump",
                                          data=b"", method="POST")
        with urllib.request.urlopen(dump_req, timeout=10) as r:
            bundle = json.loads(r.read())["bundle"]
        assert os.path.exists(os.path.join(bundle, "manifest.json"))
        engine_dump = json.loads(
            open(os.path.join(bundle, "engine.json")).read())
        assert any(q["request_id"] == rid
                   for q in engine_dump["requests"])
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
        assert 'fstpu_http_request_seconds_bucket' in text
        assert 'route="/debug/requests"' in text
        assert 'fstpu_request_phase_seconds_bucket' in text
        assert 'phase="decode"' in text
    finally:
        server.shutdown()
        engine.stop()


def test_debug_endpoints_simple_engine(tiny):
    """The simple path keeps the payload shape (empty lifecycle) and
    404s /debug/dump without a recorder."""
    import urllib.error
    import urllib.request

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)

    pipe = _gen_pipeline(tiny, max_new_tokens=2)
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0),
        PipelineConfig(task="text_generation"), pipeline=pipe)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/requests",
                timeout=10) as r:
            assert json.loads(r.read()) == {
                "in_flight": [], "recent": [], "debug_ring": 0}
        dump_req = urllib.request.Request(
            f"http://127.0.0.1:{port}/debug/dump", data=b"",
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(dump_req, timeout=10)
        assert exc.value.code == 404
    finally:
        server.shutdown()


# ---- trainer wiring -----------------------------------------------------

def test_trainer_rewind_dumps_postmortem(tmp_path):
    """A FaultPlan-driven step-guard rewind leaves a post-mortem bundle
    under <root>/flightrec whose event ring holds the step-stats
    entries (tokens/s, mfu, goodput) leading into the divergence."""
    import argparse

    from fengshen_tpu.data import UniversalDataModule
    from fengshen_tpu.models.model_utils import add_module_args
    from fengshen_tpu.resilience import FaultPlan
    from fengshen_tpu.trainer import Trainer, add_trainer_args
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.utils import UniversalCheckpoint

    parser = argparse.ArgumentParser()
    add_module_args(parser)
    add_trainer_args(parser)
    UniversalDataModule.add_data_specific_args(parser)
    UniversalCheckpoint.add_argparse_args(parser)
    ck = tmp_path / "ck"
    args = parser.parse_args(
        ["--train_batchsize", "4", "--learning_rate", "1e-3",
         "--warmup_steps", "1", "--log_every_n_steps", "1",
         "--default_root_dir", str(tmp_path),
         "--max_steps", "4", "--every_n_train_steps", "2",
         "--max_consecutive_bad_steps", "2",
         "--save_ckpt_path", str(ck), "--load_ckpt_path", str(ck)])
    cfg = LlamaConfig(vocab_size=64, hidden_size=16,
                      intermediate_size=32, num_hidden_layers=1,
                      num_attention_heads=2,
                      max_position_embeddings=32, dtype="float32")
    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(0, 63, 16).tolist()}
            for _ in range(64)]

    class DS:
        def __len__(self):
            return len(rows)

        def __getitem__(self, i):
            return rows[i]

    module = CausalLMModule(args, LlamaForCausalLM(cfg), cfg)
    dm = UniversalDataModule(args=args, datasets={"train": DS()})
    trainer = Trainer(args)
    trainer.callbacks.append(UniversalCheckpoint(args))
    FaultPlan(nan_loss_at_steps={1, 2}).install(trainer)
    try:
        state = trainer.fit(module, dm)
    finally:
        # don't leak the trainer's mesh into later sharding-sensitive
        # tests (the documented subset-ordering flake)
        from fengshen_tpu.parallel import set_mesh
        set_mesh(None)
    assert int(state.step) == 4

    flight = tmp_path / "flightrec"
    bundles = sorted(os.listdir(flight))
    assert bundles and bundles[0].endswith("rewind")
    bundle = flight / bundles[0]
    manifest = json.loads((bundle / "manifest.json").read_text())
    assert manifest["reason"] == "rewind"
    assert manifest["extra"]["from_step"] == 3
    assert manifest["extra"]["to_step"] == 2
    events = [json.loads(line) for line in
              (bundle / "events.jsonl").read_text().splitlines()]
    # the last window of step stats rode along in the ring
    steps = [e for e in events if "tokens_per_sec" in e]
    assert steps and all("mfu" in e and "goodput" in e for e in steps)
    assert any(e.get("event") == "rewind" for e in events)
    assert any(e.get("event") == "metrics_snapshot" for e in events)
    trainer_dump = json.loads((bundle / "trainer.json").read_text())
    assert trainer_dump["step"] == 2
    assert trainer_dump["args"]["max_consecutive_bad_steps"] == 2
