"""Continuous-batching serving engine (fengshen_tpu/serving/).

The load-bearing contract: greedy decode through the slot pool is
TOKEN-IDENTICAL to sequential `utils.generate.generate`, for requests
admitted at different ticks, across slot reclaim, with ONE decode
compilation for the whole lifetime of the engine. Plus the scheduler's
fast-lane behaviors: bucket selection, queue overflow → rejection,
cancellation and deadlines freeing slots, metrics/stats.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.observability import current_span_stack
from fengshen_tpu.serving import (ContinuousBatchingEngine, EngineConfig,
                                  BucketLadder, PromptTooLong, QueueFull,
                                  rollback_slots)
from fengshen_tpu.utils.generate import generate


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
    """Sequential baseline: batch-1 unpadded generate, trimmed to the
    generated region (and through eos, which the engine includes)."""
    out = np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                              max_new_tokens=max_new, **kw))
    toks = out[0, len(prompt):].tolist()
    eos = kw.get("eos_token_id")
    if eos is not None and eos in toks:
        toks = toks[:toks.index(eos) + 1]
    return toks


# ---- bucket ladder ------------------------------------------------------

def test_bucket_ladder_selection_and_padding():
    ladder = BucketLadder((8, 16, 32))
    assert ladder.bucket_for(1) == 8
    assert ladder.bucket_for(8) == 8
    assert ladder.bucket_for(9) == 16
    assert ladder.bucket_for(32) == 32
    assert ladder.bucket_for(33) is None  # reject, don't truncate
    ids, mask = ladder.pad_prompt([5, 6, 7], 8, pad_token_id=1)
    assert ids.tolist() == [1, 1, 1, 1, 1, 5, 6, 7]  # LEFT pad
    assert mask.tolist() == [0, 0, 0, 0, 0, 1, 1, 1]


def test_bucket_ladder_validation():
    with pytest.raises(ValueError):
        BucketLadder(())
    with pytest.raises(ValueError):
        BucketLadder((16, 8))
    with pytest.raises(ValueError):
        BucketLadder((8, 8))


# ---- greedy parity (the tentpole contract) ------------------------------

def test_greedy_parity_staggered_admission(tiny):
    """Requests admitted at different ticks, spanning both buckets and
    a slot-pool smaller than the request count, decode token-identical
    to sequential generate."""
    model, params = tiny
    prompts = _prompts((5, 11, 16, 7))
    refs = [_ref(model, params, p, 10) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=10, max_queue=16))
    r0 = eng.submit(prompts[0])
    r1 = eng.submit(prompts[1])
    for _ in range(3):
        eng.step()
    r2 = eng.submit(prompts[2])
    r3 = eng.submit(prompts[3])
    eng.run_until_idle()
    for req, ref in zip((r0, r1, r2, r3), refs):
        assert req.tokens == ref
        assert req.state == "finished"
        assert req.finish_reason == "length"
        assert req.ttft_s is not None and req.ttft_s >= 0


def test_greedy_parity_with_eos(tiny):
    """eos mid-stream finishes the request early with identical tokens
    (eos included, as generate does before padding)."""
    model, params = tiny
    prompt = _prompts((9,), seed=3)[0]
    free_run = _ref(model, params, prompt, 12)
    eos = free_run[3]  # force an eos hit on the 4th generated token
    ref = _ref(model, params, prompt, 12, eos_token_id=eos)
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(16,),
                                    max_new_tokens=12, max_queue=4,
                                    eos_token_id=eos))
    req = eng.submit(prompt)
    eng.run_until_idle()
    assert req.tokens == ref
    assert req.tokens[-1] == eos
    assert req.finish_reason == "eos"


def test_greedy_parity_with_repetition_penalty(tiny):
    """The engine reuses apply_logits_controls with per-slot cursors —
    the penalized decode must still match sequential generate."""
    model, params = tiny
    prompts = _prompts((6, 13), seed=5)
    refs = [_ref(model, params, p, 8, repetition_penalty=1.5)
            for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=8, max_queue=4,
                                    repetition_penalty=1.5))
    outs = eng.generate_all(prompts)
    assert outs == refs


def test_decode_compiles_once_across_reclaim(tiny):
    """THE perf contract: one decode program for the whole engine
    lifetime — across staggered admission, slot reclaim, and both
    prefill buckets (which compile once each)."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=16))
    if not hasattr(eng._decode_jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    eng.warmup()
    prompts = _prompts((5, 11, 16, 7, 3, 9))
    reqs = [eng.submit(p) for p in prompts[:3]]
    for _ in range(4):
        eng.step()
    reqs += [eng.submit(p) for p in prompts[3:]]
    eng.run_until_idle()
    assert all(r.state == "finished" for r in reqs)
    assert eng._decode_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() == 2  # one per bucket
    assert eng._assign_jit._cache_size() == 1


# ---- scheduler fast lane ------------------------------------------------

def test_slot_reclaim_serves_queue_through_one_slot(tiny):
    model, params = tiny
    prompts = _prompts((5, 6, 7), seed=1)
    refs = [_ref(model, params, p, 5) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=5, max_queue=8))
    reqs = [eng.submit(p) for p in prompts]
    eng.step()
    # one slot: exactly one running, rest queued
    assert [r.state for r in reqs].count("running") == 1
    eng.run_until_idle()
    assert [r.tokens for r in reqs] == refs
    stats = eng.stats()
    assert stats["completed"] == 3
    assert stats["prefills_per_bucket"] == {8: 3}


def test_queue_overflow_rejects_with_429_semantics(tiny):
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=4, max_queue=2))
    p = _prompts((4,))[0]
    eng.submit(p)
    eng.submit(p)
    with pytest.raises(QueueFull):
        eng.submit(p)
    assert eng.stats()["rejected_queue_full"] == 1
    assert eng.stats()["admitted"] == 2


def test_prompt_too_long_rejected(tiny):
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8, 16),
                                    max_new_tokens=4, max_queue=2))
    with pytest.raises(PromptTooLong):
        # past the largest bucket a prompt goes by windows; what is
        # refused is a prompt past the LANE (max_position_embeddings 64)
        eng.submit(np.arange(1, 71, dtype=np.int32))
    assert eng.stats()["rejected_prompt_too_long"] == 1


def test_no_headroom_rejected(tiny):
    """A bucket that fills max_position_embeddings leaves no room to
    decode — reject instead of silently clamping the cache write."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8, 64),
                                    max_new_tokens=4, max_queue=2))
    with pytest.raises(PromptTooLong):
        eng.submit(np.arange(1, 50, dtype=np.int32))  # bucket 64 == max


def test_cancel_queued_request(tiny):
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=4, max_queue=4))
    req = eng.submit(_prompts((4,))[0])
    assert eng.cancel(req.request_id) is True
    assert req.state == "cancelled"
    assert req.done
    assert eng.cancel("nonexistent") is False
    assert eng.stats()["cancelled"] == 1


def test_cancel_running_request_frees_slot(tiny):
    """Cancelling an in-flight request releases its lane to the next
    queued request at the following tick."""
    model, params = tiny
    prompts = _prompts((5, 6), seed=2)
    ref1 = _ref(model, params, prompts[1], 4)
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=50, max_queue=4))
    r0 = eng.submit(prompts[0], max_new_tokens=50)
    r1 = eng.submit(prompts[1], max_new_tokens=4)
    eng.step()
    assert r0.state == "running" and r1.state == "queued"
    eng.cancel(r0.request_id)
    eng.run_until_idle()
    assert r0.state == "cancelled"
    assert r0.finish_reason == "cancelled"
    assert r1.state == "finished"
    assert r1.tokens == ref1  # reclaimed lane decodes untainted
    assert eng.stats()["cancelled"] == 1


def test_deadline_expires_queued_and_running(tiny):
    model, params = tiny
    now = [0.0]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=50, max_queue=4),
        clock=lambda: now[0])
    running = eng.submit(_prompts((5,))[0], deadline_s=10.0)
    queued = eng.submit(_prompts((6,))[0], deadline_s=1.0)
    eng.step()
    assert running.state == "running"
    now[0] = 5.0   # queued's deadline passed; running's has not
    eng.step()
    assert queued.state == "expired"
    assert running.state == "running"
    now[0] = 50.0
    eng.step()
    assert running.state == "expired"
    assert running.finish_reason == "deadline"
    assert eng.stats()["expired"] == 2


def test_ngram_blocklist_config_rejected(tiny):
    model, params = tiny
    with pytest.raises(ValueError, match="no_repeat_ngram_size"):
        ContinuousBatchingEngine(
            model, params, EngineConfig(no_repeat_ngram_size=2))


def test_background_thread_serving(tiny):
    """The API-layer mode: a daemon thread ticks the engine; submitters
    just wait on their request events."""
    model, params = tiny
    prompts = _prompts((5, 9, 14), seed=4)
    refs = [_ref(model, params, p, 6) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=8))
    eng.start()
    try:
        reqs = [eng.submit(p) for p in prompts]
        assert all(r.wait(timeout=60) for r in reqs)
        assert [r.tokens for r in reqs] == refs
    finally:
        eng.stop()


def test_engine_log_events(tiny):
    """Resilience-style structured log events (loader.py conventions)."""
    model, params = tiny
    events = []
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=3, max_queue=2),
        log=events.append)
    eng.warmup()
    eng.generate_all(_prompts((4,)))
    kinds = [e["event"] for e in events]
    # engine startup states its kernel dispatch decision first
    # (docs/kernels.md), then warmup reports
    assert kinds[0] == "kernel_dispatch"
    assert kinds[1] == "serving_warmup"
    assert "serving_admit" in kinds
    assert "serving_finish" in kinds


def test_rollback_slots_per_lane(tiny):
    """The per-slot analog of _rollback_cache lowers each lane's write
    cursor independently."""
    from fengshen_tpu.serving import init_slot_cache
    from fengshen_tpu.utils.generate import is_cache_index_path
    model, _ = tiny
    cache = init_slot_cache(model, 3)
    cache = jax.tree_util.tree_map_with_path(
        lambda p, l: l + 7 if is_cache_index_path(p) else l, cache)
    rolled = rollback_slots(cache, jnp.asarray([1, 2, 3]))

    def check(path, leaf):
        if is_cache_index_path(path):
            np.testing.assert_array_equal(np.asarray(leaf), [6, 5, 4])
        return leaf
    jax.tree_util.tree_map_with_path(check, rolled)


# ---- API integration ----------------------------------------------------

class _FakeTokenizer:
    """Whitespace-int tokenizer: '5 7 9' <-> [5, 7, 9]."""

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


def test_text_generation_pipeline_legacy_path(tiny):
    model, params = tiny
    pipe = _gen_pipeline(tiny, max_new_tokens=5)
    prompt = "5 7 9 11"
    ref = _ref(model, params, np.asarray([5, 7, 9, 11], np.int32), 5)
    assert pipe(prompt) == " ".join(str(t) for t in ref)


def test_api_stdlib_server_continuous_engine(tiny):
    """End-to-end: POST through the stdlib server is served by the
    engine thread; /stats exposes engine metrics; queue-full maps to
    429."""
    import json as json_mod
    import urllib.error
    import urllib.request

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server,
                                       start_continuous_engine)

    model, params = tiny
    pipe = _gen_pipeline(tiny, max_new_tokens=5)
    engine = start_continuous_engine(
        pipe, {"num_slots": 2, "buckets": (8,), "max_queue": 8})
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=engine)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        ref = _ref(model, params, np.asarray([5, 7, 9], np.int32), 5)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/text_generation",
            data=json_mod.dumps({"input_text": "5 7 9"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json_mod.loads(r.read())
        assert out["result"] == " ".join(str(t) for t in ref)
        assert out["finish_reason"] == "length"
        assert out["ttft_s"] >= 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10) as r:
            stats = json_mod.loads(r.read())
        assert stats["completed"] >= 1
        assert stats["num_slots"] == 2
        # prompt longer than the lane (64 positions) → 413
        too_long = " ".join(["3"] * 70)
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/text_generation",
            data=json_mod.dumps({"input_text": too_long}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(bad, timeout=60)
        assert exc.value.code == 413
    finally:
        server.shutdown()
        engine.stop()


def test_api_stdlib_server_queue_full_429(tiny):
    import json as json_mod
    import urllib.error
    import urllib.request

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)

    pipe = _gen_pipeline(tiny, max_new_tokens=4)
    # fill the 1-deep queue and start NO engine thread: nothing drains,
    # so the HTTP submit is deterministically backpressured
    eng = ContinuousBatchingEngine(
        pipe.module, pipe.params,
        EngineConfig(num_slots=1, buckets=(8,), max_new_tokens=4,
                     max_queue=1, pad_token_id=0))
    eng.submit(np.asarray([5, 7], np.int32))
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=eng)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/text_generation",
            data=json_mod.dumps({"input_text": "5 7"}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=30)
        assert exc.value.code == 429
    finally:
        server.shutdown()


def test_warmup_pipeline_logs_seconds(tiny, capsys):
    from fengshen_tpu.api.main import warmup_pipeline

    calls = []

    def fake_pipeline(text):
        calls.append(text)
        return "ok"

    dt = warmup_pipeline(fake_pipeline, "text_generation")
    assert dt is not None and dt >= 0
    assert calls == ["warmup"]
    assert "compiled+ran" in capsys.readouterr().out

    def broken(text):
        raise RuntimeError("no params")

    # a pipeline that cannot answer its warmup request cannot answer a
    # user's either: the failure propagates (and keeps /healthz at 503)
    with pytest.raises(RuntimeError, match="no params"):
        warmup_pipeline(broken, "t")


# ---- code-review hardening ----------------------------------------------

def test_submit_invalid_max_new_tokens(tiny):
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=4, max_queue=2))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(_prompts((4,))[0], max_new_tokens=0)
    # not a 413-class rejection: the prompt itself was fine
    assert eng.stats()["rejected_prompt_too_long"] == 0


def test_server_config_rejects_unknown_engine():
    from fengshen_tpu.api.main import ServerConfig
    with pytest.raises(ValueError, match="unknown engine"):
        ServerConfig(engine="continous")  # typo must fail at startup


def test_serve_loop_survives_tick_error(tiny):
    """A mid-tick exception must not leave waiters hanging for their
    full timeout: in-flight requests fail loudly with 'engine_error',
    the pool is rebuilt, and the NEXT request is served correctly."""
    model, params = tiny
    prompt = _prompts((5,), seed=9)[0]
    ref = _ref(model, params, prompt, 4)
    events = []
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=4, max_queue=4),
        log=events.append)
    real_decode = eng._decode_jit
    boom = [True]

    def flaky(*args):
        if boom[0]:
            boom[0] = False
            raise RuntimeError("transient XLA failure")
        return real_decode(*args)

    eng._decode_jit = flaky
    eng.start()
    try:
        failed = eng.submit(prompt)
        assert failed.wait(timeout=60)
        assert failed.finish_reason == "engine_error"
        ok = eng.submit(prompt)
        assert ok.wait(timeout=60)
        assert ok.tokens == ref  # rebuilt pool decodes untainted
    finally:
        eng.stop()
    assert any(e["event"] == "serving_tick_error" for e in events)


def test_legacy_path_honors_max_new_tokens(tiny):
    """The simple engine must respect the per-request cap too."""
    import json as json_mod
    import urllib.request

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)

    model, params = tiny
    pipe = _gen_pipeline(tiny, max_new_tokens=6)
    ref = _ref(model, params, np.asarray([5, 7, 9], np.int32), 2)
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0),
        PipelineConfig(task="text_generation"), pipeline=pipe)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/text_generation",
            data=json_mod.dumps({"input_text": "5 7 9",
                                 "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json_mod.loads(r.read())
        assert out["result"] == " ".join(str(t) for t in ref)
    finally:
        server.shutdown()


def test_engine_server_422_on_bad_max_new_tokens(tiny):
    import json as json_mod
    import urllib.error
    import urllib.request

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)

    pipe = _gen_pipeline(tiny, max_new_tokens=4)
    eng = ContinuousBatchingEngine(
        pipe.module, pipe.params,
        EngineConfig(num_slots=1, buckets=(8,), max_new_tokens=4,
                     max_queue=4))
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine="continuous"),
        PipelineConfig(task="text_generation"), pipeline=pipe,
        engine=eng)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/text_generation",
            data=json_mod.dumps({"input_text": "5 7",
                                 "max_new_tokens": 0}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=30)
        assert exc.value.code == 422
    finally:
        server.shutdown()


def test_engine_config_rejects_zero_queue(tiny):
    with pytest.raises(ValueError, match="max_queue"):
        EngineConfig(max_queue=0)


def test_pipeline_honors_cli_args(tiny):
    """fengshen-pipeline parses flags into `args`; the pipeline must
    read them, not silently fall back to its defaults."""
    import argparse

    from fengshen_tpu.pipelines.text_generation import Pipeline

    parser = argparse.ArgumentParser()
    Pipeline.add_pipeline_specific_args(parser)
    args = parser.parse_args(["--max_new_tokens", "3",
                              "--temperature", "0.7"])
    model, params = tiny
    pipe = Pipeline(args=args, module=model, params=params,
                    tokenizer=_FakeTokenizer())
    assert pipe.max_new_tokens == 3
    assert pipe.sample_kw["temperature"] == 0.7
    assert len(pipe("5 7 9").split()) == 3


# ---- spans and counters at the host/device boundaries (ISSUE 24) --------

def _span_counts():
    from fengshen_tpu.observability import get_registry
    metric = get_registry().get("fstpu_span_seconds")
    if metric is None:
        return {}
    return {values[0]: child.count for values, child in metric.children()}


def test_scheduler_spans_cover_the_tick_and_keep_the_old_names(tiny):
    """The new spans are children or siblings of the ones the
    benchmark's readers match by exact name, never parents: after a few
    ticks every new label is there and the old ones are, letter for
    letter. Spans add no traced work: still one decode program."""
    import time
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=16))
    before = _span_counts()
    reqs = [eng.submit(p) for p in _prompts((5, 11, 16))]
    eng.run_until_idle()
    offline_ticks = eng.stats()["decode_ticks"]
    # no serve thread, no `serving/tail`: its span stack would keep it
    assert eng._tail is None and current_span_stack() == ()
    assert _span_counts().get("serving/tail", 0) == \
        before.get("serving/tail", 0)
    eng.start()                  # the serve loop: lock and idle waits
    try:
        late = eng.submit(_prompts((7,))[0])
        assert late.wait(timeout=60)
        deadline = time.monotonic() + 10
        while _span_counts().get("serving/idle_wait", 0) == \
                before.get("serving/idle_wait", 0):
            assert time.monotonic() < deadline, "no idle wait recorded"
            time.sleep(0.01)
    finally:
        eng.stop()
    assert all(r.state == "finished" for r in reqs + [late])
    grew = {k for k, n in _span_counts().items() if n > before.get(k, 0)}
    assert {"serving/decode", "serving/prefill", "serving/admit"} <= grew
    assert {"serving/decode/dispatch", "serving/decode/fetch",
            "serving/commit", "serving/assign", "serving/lock_wait",
            "serving/idle_wait", "serving/admit/lock_wait"} <= grew
    # the cycle's cover (ISSUE 34): siblings and children again
    assert {"serving/reclaim", "serving/tail",
            "serving/decode/dispatch/call",
            "serving/decode/dispatch/copy_back"} <= grew
    # nothing was renamed by a span opened around it
    assert not {k for k in grew if k.startswith("serving/")} - {
        "serving/admit", "serving/admit/lock_wait", "serving/lock_wait",
        "serving/reclaim", "serving/prepare", "serving/prefill",
        "serving/assign",
        "serving/decode", "serving/decode/dispatch",
        "serving/decode/dispatch/call",
        "serving/decode/dispatch/copy_back", "serving/decode/fetch",
        "serving/commit", "serving/tail", "serving/idle_wait"}
    after = _span_counts()
    ticks = eng.stats()["decode_ticks"]
    for child in ("serving/decode/dispatch", "serving/decode/fetch",
                  "serving/commit", "serving/decode/dispatch/call",
                  "serving/decode/dispatch/copy_back"):
        assert after[child] - before.get(child, 0) == ticks
    # one tail a tick the serve thread committed, one reclaim a cycle
    assert after["serving/tail"] - before.get("serving/tail", 0) == \
        ticks - offline_ticks
    assert after["serving/reclaim"] - before.get("serving/reclaim", 0) \
        >= after["serving/decode"] - before.get("serving/decode", 0)
    # one `serving/decode` a tick, holding the next tick's dispatch and
    # this tick's fetch; each of the three busy stretches (two lanes
    # that end together, the third prompt, the late one) opens with a
    # span that holds a dispatch alone and closes with a fetch alone
    assert after["serving/decode"] - \
        before.get("serving/decode", 0) == ticks + 3
    assert after["serving/assign"] - before.get("serving/assign", 0) == 4
    if hasattr(eng._decode_jit, "_cache_size"):
        assert eng._decode_jit._cache_size() == 1


_SCHED = {k: f"fstpu_serving_{k}_total" for k in (
    "scheduler_wall_seconds", "scheduler_cpu_seconds",
    "scheduler_wait_seconds", "lock_wait_seconds", "dispatch_seconds",
    "dispatch_cpu_seconds", "commit_seconds", "commit_cpu_seconds")}


def _sched(eng) -> dict:
    return {k: eng.metrics.registry.get(name).value()
            for k, name in _SCHED.items()}


def test_scheduler_thread_accounts_its_wall_cpu_and_declared_wait(tiny):
    """wall = cpu + declared wait + what was taken from the thread:
    the serve loop credits the first three once an iteration; `step()`
    and `run_until_idle` are nobody's scheduler thread and credit
    nothing but the dispatch and commit pairs."""
    import time
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=16))
    assert set(_sched(eng).values()) == {0.0}
    first = eng.submit(_prompts((5,))[0])
    while not first.done:
        eng.step()
    eng.generate_all(_prompts((7, 9), seed=1))
    offline = _sched(eng)
    for k in ("scheduler_wall_seconds", "scheduler_cpu_seconds",
              "scheduler_wait_seconds", "lock_wait_seconds"):
        assert offline[k] == 0.0
    slack = 1e-4                     # the two clocks are read in turn
    assert 0.0 < offline["dispatch_cpu_seconds"] <= \
        offline["dispatch_seconds"] + slack
    assert 0.0 < offline["commit_cpu_seconds"] <= \
        offline["commit_seconds"] + slack

    def sound(c):
        assert c["scheduler_cpu_seconds"] > 0.0
        assert c["scheduler_wait_seconds"] > 0.0
        assert c["scheduler_wall_seconds"] + slack >= \
            c["scheduler_cpu_seconds"] + c["scheduler_wait_seconds"]
        assert 0.0 < c["lock_wait_seconds"] <= c["scheduler_wait_seconds"]
        assert c["dispatch_cpu_seconds"] <= c["dispatch_seconds"] + slack
        assert c["commit_cpu_seconds"] <= c["commit_seconds"] + slack

    eng.start()
    try:
        for r in [eng.submit(p) for p in _prompts((5, 11, 16), seed=2)]:
            assert r.wait(timeout=60)
        time.sleep(0.05)             # an idle wait or two
        mid = _sched(eng)
        sound(mid)
        for r in [eng.submit(p) for p in _prompts((6, 12), seed=3)]:
            assert r.wait(timeout=60)
    finally:
        eng.stop()
    end = _sched(eng)
    sound(end)
    for k in _SCHED:
        assert offline[k] <= mid[k] <= end[k]
    assert mid["scheduler_wall_seconds"] < end["scheduler_wall_seconds"]
    # the thread lived about as long as its iterations say
    assert end["scheduler_wall_seconds"] >= 0.05
    if hasattr(eng._decode_jit, "_cache_size"):
        assert eng._decode_jit._cache_size() == 1


def test_a_cpu_clock_that_runs_ahead_never_stops_the_serve_loop(
        tiny, monkeypatch):
    """On a sandboxed host the thread's CPU clock is coarse and may
    read ahead of the wall clock over a short span: the account clamps,
    the serve thread lives and its counters only go up."""
    import fengshen_tpu.observability.tracing as tracing
    real = tracing.time.thread_time
    fake = type("T", (), {
        "perf_counter": staticmethod(tracing.time.perf_counter),
        # 4 ms steps, rounded up and then some
        "thread_time": staticmethod(
            lambda: (int(real() / 0.004) + 1) * 0.004 + 10 * real())})
    monkeypatch.setattr(tracing, "time", fake)
    # this thread's spans read the fake clock too, and `thread_times()`
    # never goes behind an earlier reading: left behind, a reading at
    # eleven times the truth held every later span of this process's
    # main thread at 0.0 CPU seconds (tests/test_observability.py, two
    # files on the same worker). The undo puts the real one back.
    monkeypatch.setattr(tracing._local, "cpu_anchor", None, raising=False)
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=12, max_queue=16))
    eng.start()
    try:
        seen = [_sched(eng)]
        for seed in (4, 5):
            for r in [eng.submit(p) for p in _prompts((5, 11), seed=seed)]:
                assert r.wait(timeout=60) and r.state == "finished"
            seen.append(_sched(eng))
        assert eng._thread.is_alive()
    finally:
        eng.stop()
    for before, after in zip(seen, seen[1:]):
        assert all(before[k] <= after[k] for k in _SCHED)
    assert seen[-1]["scheduler_wall_seconds"] > 0.0
    assert seen[-1]["scheduler_wait_seconds"] >= 0.0


def test_block_allocation_has_a_span_a_request_also_when_deferred(tiny):
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=4, buckets=(8,),
                                    max_new_tokens=8, max_queue=16,
                                    kv_layout="paged", kv_block_size=16,
                                    kv_num_blocks=3))
    before = _span_counts().get("serving/alloc", 0)
    reqs = [eng.submit(p) for p in _prompts((6, 6, 6), seed=2)]
    eng.step()
    # two admitted, the third deferred inside its own span
    assert _span_counts()["serving/alloc"] - before == 3
    assert eng.stats()["deferred_admissions"] == 1
    assert [r.request_id for r in eng._queue] == [reqs[2].request_id]
    eng.run_until_idle()
    assert all(r.state == "finished" for r in reqs)
    assert eng.stats()["kv_blocks_used"] == 0
    assert "serving/alloc/serving/prefill" not in _span_counts()


@pytest.mark.parametrize("kw, prompts, windows", [
    ({"buckets": (8, 16)}, (5, 11, 16), 0),
    ({"buckets": (8,), "kv_layout": "paged", "kv_block_size": 16},
     (5, 13), 2),
], ids=["whole_prompt", "windowed"])
def test_the_stretch_between_alloc_and_prefill_has_a_span_an_admission(
        tiny, kw, prompts, windows):
    """`serving/prepare` (ISSUE 50): the padded row or the window's, the
    key and the two timeline events, once a request admitted: a sibling
    of `serving/alloc` and `serving/prefill`, so neither is renamed; a
    request past the ladder (windowed prefill) has one too."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, max_new_tokens=4,
                                    max_queue=16, **kw))
    before = _span_counts()
    reqs = [eng.submit(p) for p in _prompts(prompts, seed=5)]
    eng.run_until_idle()
    assert all(r.state == "finished" for r in reqs)
    after = _span_counts()
    grew = {k: n - before.get(k, 0) for k, n in after.items()
            if n > before.get(k, 0)}
    assert grew["serving/prepare"] == grew["serving/prefill"] == len(reqs)
    assert grew.get("serving/prefill/window", 0) == windows
    assert not [k for k in grew if k.startswith("serving/prepare/")
                or k.endswith("/serving/prepare")]
    # the timeline events it covers are still each request's
    for r in reqs:
        marks = [e["event"] for e in eng.debug_request(
            r.request_id)["events"]]
        assert marks.index("admitted") < marks.index("prefill_start")


def test_the_unread_decode_seconds_are_gone_from_stats_and_metrics(tiny):
    from fengshen_tpu.observability import render_prometheus
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=3, max_queue=4))
    req = eng.submit(_prompts((5,))[0])
    eng.run_until_idle()
    stats = eng.stats()
    assert "decode_tokens_per_sec" not in stats
    assert stats["decode_ticks"] == 2 and stats["decode_tokens"] == 2
    text = render_prometheus(eng.metrics.registry)
    assert "decode_seconds" not in text
    assert "fstpu_serving_decode_ticks_total 2" in text
    # the timelines keep `tick_s`, documented for operators
    commits = [e for e in eng.debug_request(req.request_id)["events"]
               if e["event"] == "commit"]
    assert commits and all(e["tick_s"] >= 0 for e in commits)


def test_submit_lock_wait_is_measured_and_enqueued_is_stamped_after_it(
        tiny):
    """A submitter that waits for the scheduler's lock shows the wait:
    `enqueued` carries the clock read AFTER the lock is held,
    `lock_wait_s` is that wait and a part of `queue_wait_s`, and the
    histogram on /metrics records it."""
    model, params = tiny
    now = [0.0]
    first_read = threading.Event()

    def clock():
        first_read.set()
        return now[0]

    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=3, max_queue=4),
        clock=clock)
    free = eng.submit(_prompts((5,))[0], request_id="free")
    first_read.clear()
    out = {}
    with eng._cv:                       # the scheduler mid-tick
        t = threading.Thread(target=lambda: out.update(req=eng.submit(
            _prompts((6,))[0], request_id="starved")))
        t.start()
        assert first_read.wait(timeout=30)     # submit read its clock
        now[0] = 2.0                           # ... and waits 2 s
    t.join(timeout=30)
    assert not t.is_alive()
    now[0] = 3.0
    eng.run_until_idle()
    starved = eng.debug_request("starved")
    assert starved["events"][0] == {
        "t_s": 2.0, "event": "enqueued", "prompt_tokens": 6, "bucket": 8,
        "queue_depth": 2}
    assert starved["phases"]["lock_wait_s"] == 2.0
    assert 2.0 <= starved["phases"]["queue_wait_s"]
    ph = eng.debug_request("free")["phases"]
    assert ph["lock_wait_s"] == 0.0 <= ph["queue_wait_s"]
    for d in (starved, eng.debug_request("free")):
        p = d["phases"]
        assert abs(p["queue_wait_s"] + p["prefill_s"] + p["decode_s"]
                   - p["total_s"]) <= 1e-3
    hist = eng.metrics.registry.get(
        "fstpu_serving_submit_lock_wait_seconds")
    assert hist.window_values() == [0.0, 2.0]
    assert free.state == out["req"].state == "finished"


@pytest.mark.parametrize("extra", [
    {}, {"kv_layout": "paged", "kv_block_size": 16},
    {"spec_mode": "prompt_lookup", "spec_gamma": 4}],
    ids=["plain", "paged", "speculative"])
def test_prefill_and_attended_token_counters_equal_hand_sums(tiny, extra):
    """Real and padded prompt tokens prefilled, and the real cached
    tokens each tick's attention reads (the logical cursor + 1, padding
    out), against sums made by hand from the prompts, their buckets and
    the committed tokens of every tick."""
    model, params = tiny
    lengths, buckets = (5, 11, 16, 7), (8, 16)
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=buckets,
                                    max_new_tokens=6, max_queue=16,
                                    **extra))
    reqs = [eng.submit(p) for p in _prompts(lengths)]
    eng.run_until_idle()
    reg = eng.metrics.registry
    assert reg.get("fstpu_serving_prefill_tokens_total").value() == \
        sum(lengths) == 39
    assert reg.get("fstpu_serving_prefill_padded_tokens_total").value() \
        == sum(min(b for b in buckets if b >= n) for n in lengths) == 48
    # a tick reads, for each lane, the prompt and every token committed
    # before it, the one it decodes from included
    attended = 0
    for req, n in zip(reqs, lengths):
        held = 1                         # the prefill's token
        for e in eng.debug_request(req.request_id)["events"]:
            if e["event"] == "commit":
                attended += n + held
                held += e["n"]
        assert held == len(req.tokens) == 6
    assert reg.get("fstpu_serving_kv_tokens_attended_total").value() == \
        attended
    if "spec_mode" not in extra:
        # one token a tick: 5 ticks a request at P+1 .. P+5
        assert attended == sum(5 * n + 15 for n in lengths) == 255
        assert eng.stats()["decode_tokens"] == 20


@pytest.mark.parametrize("layout", ["paged", "slot", "speculative"])
def test_live_and_tabled_block_counters_equal_hand_sums(tiny, layout):
    """What the paged decode kernel walks of what its table rows name,
    counted per tick from the host's PHYSICAL cursors over ALL lanes:
    one lane inside its first block, one whose bucket ends ON a block
    boundary, one that holds no request (its one null block). A verify
    window reaches `spec_gamma` further; the slot layout has no
    table."""
    model, params = tiny
    extra = {"slot": {}, "paged": {"kv_layout": "paged",
                                   "kv_block_size": 16},
             "speculative": {"kv_layout": "paged", "kv_block_size": 16,
                             "spec_mode": "prompt_lookup",
                             "spec_gamma": 4}}[layout]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=3, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=16,
                                    **extra))
    reqs = [eng.submit(p) for p in _prompts((5, 11))]
    eng.run_until_idle()
    reg = eng.metrics.registry
    live = reg.get("fstpu_serving_kv_blocks_live_total").value()
    tabled = reg.get("fstpu_serving_kv_blocks_tabled_total").value()
    ticks = reg.get("fstpu_serving_decode_ticks_total").value()
    if layout == "slot":
        assert (live, tabled) == (0, 0) and ticks == 5
        return
    assert tabled == ticks * 3 * eng.max_blocks_per_slot
    if layout == "paged":
        # five ticks: the first lane's cursor runs 8..12 (one block of
        # 16), the second's 16..20 (its bucket filled block 0 to the
        # end: two), the free lane's stays 0 (the null block)
        assert ticks == 5 and live == 5 * (1 + 2 + 1) == 20
        return
    # each tick's window reaches cursor + 4; the cursors from the
    # committed tokens of the ticks before
    want = 0
    for t in range(int(ticks)):
        for req, bucket in zip(reqs, (8, 16)):
            commits = [e["n"] for e in eng.debug_request(
                req.request_id)["events"] if e["event"] == "commit"]
            phys = bucket + sum(commits[:t]) if t < len(commits) else 0
            want += (phys + 4) // 16 + 1
        want += 1
    assert live == want


def test_resumed_prefill_counts_its_committed_prefix(tiny):
    """A resumed request prefills prompt + resume[:-1] in one bucket:
    the real-token counter counts what was prefilled."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=4))
    eng.submit(_prompts((5,))[0], resume_tokens=[7, 8, 9, 10])
    eng.run_until_idle()
    reg = eng.metrics.registry
    assert reg.get("fstpu_serving_prefill_tokens_total").value() == 5 + 3
    assert reg.get(
        "fstpu_serving_prefill_padded_tokens_total").value() == 8


def test_serving_programs_and_decode_attention_keep_their_trace_names(
        tiny):
    """The trace's module line names a program after its function and
    the decode attention after `TRACE_NAME`, whichever lowering the
    dispatch seam took: both are what the benchmark's readers match."""
    from fengshen_tpu.ops.pallas.decode_attention import (TRACE_NAME,
                                                          decode_attention)
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8,),
                                    max_new_tokens=4, max_queue=4))
    eng.submit(_prompts((5,))[0])
    eng.step()
    decode = eng._decode_jit.lower(
        eng.params, eng._cache, eng._history, eng._mask, eng._last_tok,
        eng._pos, eng._phys, eng._active, eng._keys
    ).as_text(debug_info=True)
    assert "module @jit_decode_fn" in decode
    assert TRACE_NAME == "fstpu_decode_attention" and TRACE_NAME in decode
    assert eng._prefill_jit.__name__ == "prefill_fn"
    assert eng._assign_jit.__name__ == "assign_fn"

    q = jnp.zeros((2, 1, 8, 128), jnp.float32)
    kv = jnp.zeros((3, 128, 8, 128), jnp.float32)
    valid = jnp.ones((2, 1, 256), bool)
    table = jnp.zeros((2, 2), jnp.int32)
    for impl in ("pallas", "xla"):
        text = jax.jit(lambda q, k, v, m, t, impl=impl: decode_attention(
            q, k, v, m, block_table=t, impl=impl, interpret=True)).lower(
            q, kv, kv, valid, table).as_text(debug_info=True)
        assert TRACE_NAME in text, impl
