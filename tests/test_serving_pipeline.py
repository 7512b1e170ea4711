"""One decode tick in flight (fengshen_tpu/serving/engine.py).

The serve loop and `run_until_idle` enqueue tick k+1 from the token
array tick k left on the device before they fetch tick k; `step()` runs
the same two calls back to back. Both orders must serve the same
tokens, count the same tokens, free every block once and leave nothing
in flight where the engine promises a committed state.
"""

import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.models.joyai import JoyAIConfig, JoyAIForCausalLM
from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.observability import render_prometheus
from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig

PAGED = dict(kv_layout="paged", kv_block_size=16)
SAMPLED = dict(do_sample=True, temperature=0.9, top_k=20, seed=7)


@pytest.fixture(scope="module")
def llama():
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, dtype="float32")
    model = LlamaForCausalLM(cfg)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))["params"]


@pytest.fixture(scope="module")
def joyai():
    model = JoyAIForCausalLM(JoyAIConfig.small_test_config(dtype="float32"))
    return model, model.init(jax.random.PRNGKey(1),
                             jnp.zeros((1, 8), jnp.int32))["params"]


def _prompts(lengths, vocab=96, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, vocab, n).astype(np.int32) for n in lengths]


def _engine(made, **kw):
    model, params = made
    kw = {**dict(num_slots=2, buckets=(8, 16), max_new_tokens=6,
                 max_queue=16), **kw}
    return ContinuousBatchingEngine(model, params, EngineConfig(**kw))


def _counters(eng) -> dict:
    return {name: float(value) for name, _, value in
            (line.partition(" ") for line in
             render_prometheus(eng.metrics.registry).splitlines())
            if name.startswith("fstpu_") and "{" not in name}


def _counted_is_delivered(eng, reqs) -> None:
    """Every token but a request's first (the prefill's) is a decode
    tick's: the engine's counter equals what the clients hold."""
    assert _counters(eng)["fstpu_serving_decode_tokens_total"] == \
        sum(len(r.tokens) - 1 for r in reqs if r.tokens)


# ---- (a) the two orders of the same calls serve the same tokens ---------

@pytest.mark.parametrize("family,layout,sampling", [
    ("llama", {}, {}), ("llama", {}, SAMPLED),
    ("llama", PAGED, {}), ("llama", PAGED, SAMPLED),
    ("joyai", PAGED, {}), ("joyai", PAGED, SAMPLED)],
    ids=["llama-slot-greedy", "llama-slot-sampled", "llama-paged-greedy",
         "llama-paged-sampled", "joyai-paged-greedy",
         "joyai-paged-sampled"])
def test_one_ahead_serves_the_tokens_of_the_serial_tick(
        request, family, layout, sampling):
    """`step()` by hand (fetch what was just enqueued), `run_until_idle`
    and the serve thread (both one ahead) over staggered prompts and a
    pool smaller than the request count: the same tokens, request by
    request, greedy and with pinned seeds, and the routed model's
    histogram recorded once a tick in either order."""
    made = request.getfixturevalue(family)
    prompts = _prompts((5, 11, 16, 7, 9))
    seeds = [11, 12, 13, 14, 15]

    def submit_all(eng):
        return [eng.submit(p, seed=s) for p, s in zip(prompts, seeds)]

    serial = _engine(made, **layout, **sampling)
    want = submit_all(serial)
    while not serial.idle():
        serial.step()
        assert serial._inflight is None      # step()'s contract
    assert all(len(r.tokens) == 6 for r in want)
    assert _counters(serial)[
        "fstpu_serving_decode_ticks_ahead_total"] == 0

    offline = _engine(made, **layout, **sampling)
    got = submit_all(offline)
    offline.run_until_idle()
    assert offline._inflight is None
    assert [r.tokens for r in got] == [r.tokens for r in want]
    _counted_is_delivered(offline, got)
    assert _counters(offline)["fstpu_serving_decode_ticks_ahead_total"] > 0

    served = _engine(made, **layout, **sampling)
    served.start()
    try:
        live = submit_all(served)
        assert all(r.wait(timeout=120) for r in live)
    finally:
        served.stop()
    assert [r.tokens for r in live] == [r.tokens for r in want]
    _counted_is_delivered(served, live)

    if family == "joyai":
        for eng in (serial, offline, served):
            c = _counters(eng)
            assert c["fstpu_moe_layer_ticks_total"] == \
                eng._moe_shape[0] * c["fstpu_serving_decode_ticks_total"]
            # every delivered token made top_k picks in each layer
            assert c["fstpu_moe_assignments_total"] >= \
                eng._moe_shape[0] * c["fstpu_serving_decode_tokens_total"]


# ---- (b) an EOS is learnt one tick late ---------------------------------

@pytest.mark.parametrize("layout", [{}, PAGED], ids=["slot", "paged"])
def test_eos_truncates_where_it_did_and_its_extra_tick_is_dropped(
        llama, layout):
    """The lane that emits EOS at tick k is already in tick k+1 when the
    host sees it. Its tokens end at the EOS as in the serial run, the
    extra token reaches neither the request, its stream nor the
    counter, and every block comes back once."""
    prompts = _prompts((9, 12), seed=3)
    free = _engine(llama, max_new_tokens=12, **layout)
    free_run = [free.submit(p) for p in prompts]
    free.run_until_idle()
    eos = free_run[0].tokens[3]           # hit on the 4th token of lane 0
    assert eos not in free_run[1].tokens  # lane 1 runs to its length

    def run(drive):
        eng = _engine(llama, max_new_tokens=12, eos_token_id=eos, **layout)
        start_free = eng._allocator.free_blocks if eng.paged else None
        reqs = [eng.submit(p, stream=True) for p in prompts]
        drive(eng)
        if eng.paged:
            assert eng._allocator.free_blocks == start_free
        _counted_is_delivered(eng, reqs)
        for r in reqs:
            assert eng.streams.get(r.request_id).tokens() == r.tokens
        return reqs

    def serially(eng):
        while not eng.idle():
            eng.step()

    want = run(serially)
    got = run(lambda eng: eng.run_until_idle())
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert got[0].tokens == free_run[0].tokens[:4]
    assert got[0].finish_reason == "eos" and got[0].tokens[-1] == eos
    assert got[1].tokens == free_run[1].tokens
    assert got[1].finish_reason == "length"


def test_an_eos_on_the_only_lane_drops_the_tick_in_flight(llama):
    """With no lane left the tick in flight has no reader: it is
    dropped unfetched, and neither its tick nor its token is counted."""
    prompt = _prompts((9,), seed=3)[0]
    free = _engine(llama, max_new_tokens=12)
    free_req = free.submit(prompt)
    free.run_until_idle()
    eng = _engine(llama, max_new_tokens=12,
                  eos_token_id=free_req.tokens[3])
    req = eng.submit(prompt)
    eng.run_until_idle()
    assert req.tokens == free_req.tokens[:4] and eng._inflight is None
    c = _counters(eng)
    assert c["fstpu_serving_decode_ticks_total"] == 3
    assert c["fstpu_serving_decode_tokens_total"] == 3


# ---- (c) cancel and deadline while a tick is in flight ------------------

@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_release_with_a_tick_in_flight_drops_its_token_and_frees_once(
        llama, how):
    now = [0.0]
    model, params = llama
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=10, max_queue=4, **PAGED),
        clock=lambda: now[0])
    start_free = eng._allocator.free_blocks
    victim = eng.submit(_prompts((5,))[0], deadline_s=5.0)
    other = eng.submit(_prompts((11,))[0])
    for _ in range(3):
        eng._tick(ahead=True)
    assert eng._inflight is not None
    assert victim.slot in eng._inflight.lanes
    held = len(victim.tokens)
    if how == "cancel":
        assert eng.cancel(victim.request_id)
    else:
        now[0] = 6.0
    eng._tick(ahead=True)        # releases at its head, then commits
    assert victim.done and len(victim.tokens) == held
    assert victim.finish_reason == (
        "cancelled" if how == "cancel" else "deadline")
    # freed once: a second free of the same blocks raises in the allocator
    assert eng._allocator.used_blocks == len(eng._slot_blocks[other.slot])
    eng.run_until_idle()
    assert len(victim.tokens) == held and len(other.tokens) == 10
    assert eng._allocator.free_blocks == start_free
    _counted_is_delivered(eng, [victim, other])


# ---- (d) errors, stop and drain with a tick in flight -------------------

def test_a_tick_error_with_a_tick_in_flight_resets_and_keeps_serving(llama):
    prompt = _prompts((5,), seed=9)[0]
    ref = _engine(llama, num_slots=1, buckets=(8,))
    want = ref.submit(prompt)
    ref.run_until_idle()
    events = []
    model, params = llama
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=6, max_queue=4),
        log=events.append)
    real, calls = eng._decode_jit, [0]

    def flaky(*args):
        calls[0] += 1
        if calls[0] == 3:        # ticks 1 and 2 enqueued, 1 fetched
            assert eng._inflight is not None
            raise RuntimeError("transient XLA failure")
        return real(*args)

    eng._decode_jit = flaky
    eng.start()
    try:
        failed = eng.submit(prompt)
        assert failed.wait(timeout=60)
        assert failed.finish_reason == "engine_error"
        ok = eng.submit(prompt)
        assert ok.wait(timeout=60)
        assert ok.tokens == want.tokens
    finally:
        eng.stop()
    assert eng._inflight is None
    assert any(e["event"] == "serving_tick_error" for e in events)
    _counted_is_delivered(eng, [failed, ok])


@pytest.mark.parametrize("how", ["stop", "begin_drain", "export_lane"])
def test_nothing_stays_in_flight_where_committed_state_is_read(llama, how):
    """`stop()`, `begin_drain()` and a lane export commit the tick in
    flight: cursors, tokens and the counter agree at that instant, and
    decoding goes on from there to the same tokens."""
    prompts = _prompts((5, 11))
    ref = _engine(llama, max_new_tokens=10, **PAGED)
    want = [ref.submit(p) for p in prompts]
    ref.run_until_idle()
    eng = _engine(llama, max_new_tokens=10, **PAGED)
    reqs = [eng.submit(p) for p in prompts]
    for _ in range(4):
        eng._tick(ahead=True)
    assert eng._inflight is not None
    if how == "stop":
        eng.stop()
    elif how == "begin_drain":
        eng.begin_drain()
    else:
        from fengshen_tpu.serving.handoff import export_lane
        payload = export_lane(eng, reqs[0].request_id)
        assert payload["tokens"] == reqs[0].tokens
        assert payload["last_tok"] == reqs[0].tokens[-1]
    assert eng._inflight is None
    _counted_is_delivered(eng, reqs)
    for r in reqs:      # the committed position, bucket included
        assert eng._pos[r.slot] == len(r.prompt) + len(r.tokens) - 1
    eng.run_until_idle()
    assert [r.tokens for r in reqs] == [r.tokens for r in want]


# ---- (e) still one decode program, nothing compiled after warm-up -------

def test_one_decode_program_and_no_compile_after_warmup(llama):
    eng = _engine(llama, **PAGED)
    if not hasattr(eng._decode_jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    eng.warmup()
    assert eng._decode_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() == 2
    reqs = [eng.submit(p) for p in _prompts((5, 11, 16, 7))]
    eng.run_until_idle()
    eng.start()
    try:
        late = eng.submit(_prompts((9,))[0])
        assert late.wait(timeout=60)
    finally:
        eng.stop()
    assert all(r.state == "finished" for r in reqs + [late])
    assert eng._decode_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() == 2
    assert eng._assign_jit._cache_size() == 1


# ---- (f) the counter of ticks enqueued one ahead ------------------------

def test_ticks_ahead_is_ticks_less_those_after_an_admission_or_an_empty_pool(
        llama):
    eng = _engine(llama, max_new_tokens=8)
    not_ahead = 0
    reqs = []

    def tick():
        nonlocal not_ahead
        prefills = sum(eng.stats()["prefills_per_bucket"].values())
        empty = eng._inflight is None
        enqueued = eng._inflight
        eng._tick(ahead=True)
        admitted = sum(eng.stats()["prefills_per_bucket"].values()) \
            > prefills
        if eng._inflight is not None and eng._inflight is not enqueued \
                and (empty or admitted):
            not_ahead += 1

    reqs.append(eng.submit(_prompts((5,))[0]))
    for _ in range(3):
        tick()                       # empty pool, then two ahead
    reqs.append(eng.submit(_prompts((11,))[0]))
    for _ in range(3):
        tick()                       # an admission, then two ahead
    while not eng.idle():
        tick()
    reqs.append(eng.submit(_prompts((7,))[0]))
    while not eng.idle():
        tick()                       # the pool had emptied
    c = _counters(eng)
    assert not_ahead == 3
    assert c["fstpu_serving_decode_ticks_total"] == 10 + 7
    assert c["fstpu_serving_decode_ticks_ahead_total"] == \
        c["fstpu_serving_decode_ticks_total"] - not_ahead
    _counted_is_delivered(eng, reqs)


@pytest.mark.parametrize("mode", ["prompt_lookup", "self_draft"])
def test_a_speculative_engine_never_runs_ahead(llama, mode):
    """Its next cursors are the fetched accept counts: the same two
    calls, always back to back."""
    eng = _engine(llama, max_new_tokens=10, spec_mode=mode, spec_gamma=2,
                  spec_draft_layers=1)
    reqs = [eng.submit(p) for p in _prompts((5, 11, 16))]
    for _ in range(2):
        eng._tick(ahead=True)
        assert eng._inflight is None
    eng.run_until_idle()
    plain = _engine(llama, max_new_tokens=10)
    want = [plain.submit(p) for p in _prompts((5, 11, 16))]
    plain.run_until_idle()
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    c = _counters(eng)
    assert c["fstpu_serving_decode_ticks_total"] > 0
    assert c["fstpu_serving_decode_ticks_ahead_total"] == 0
    assert c["fstpu_serving_decode_tokens_total"] == \
        sum(len(r.tokens) - 1 for r in reqs)


# ---- (g) under threads: submitters, cancels and the serve loop ----------

def test_counter_blocks_and_flight_record_survive_a_threaded_run(llama):
    """More submitters than cores with a short switch interval: every
    request ends, every block is back, nothing is in flight, and the
    decode-token counter equals what the clients were handed."""
    eng = _engine(llama, num_slots=3, max_new_tokens=8, max_queue=64,
                  **PAGED)
    start_free = eng._allocator.free_blocks
    done, lock = [], threading.Lock()

    def client(seed):
        rng = np.random.RandomState(seed)
        for n in rng.randint(4, 16, 5):
            req = eng.submit(_prompts((int(n),), seed=seed)[0])
            if rng.rand() < 0.3:
                eng.cancel(req.request_id)
            assert req.wait(timeout=120)
            with lock:
                done.append(req)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    eng.start()
    try:
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        eng.stop()
        sys.setswitchinterval(interval)
    assert len(done) == 60 and eng.idle()
    assert eng._allocator.free_blocks == start_free
    _counted_is_delivered(eng, done)
    for r in done:
        assert r.state in ("finished", "cancelled")
        if r.state == "finished":
            assert len(r.tokens) == 8
