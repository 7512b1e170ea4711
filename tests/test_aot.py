"""AOT compile-cache subsystem (fengshen_tpu/aot/, docs/aot_cache.md).

The load-bearing contracts:

- greedy decode through DESERIALIZED cached executables is
  TOKEN-IDENTICAL to freshly compiled ones (the PR-3 parity harness,
  re-run against a warm cache);
- the cache can never break a job: corrupt blobs, jax-version drift
  inside a blob, and store failures all fall back to a fresh compile,
  visible in `fstpu_aot_cache_errors_total`;
- warmup manifests record every compile site and replay (adopting by
  key under a matching code+env+config fingerprint, re-lowering
  otherwise);
- the LRU size cap, the CLI, the /healthz readiness gate, and the
  warmup/build-info gauges.
"""

import json
import os
import pickle
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.aot import (AotConfig, AotSetup, CachedFunction,
                              ExecutableCache, WarmupManifest,
                              cached_compile, decode_avals,
                              encode_avals)
from fengshen_tpu.observability import MetricsRegistry
from fengshen_tpu.serving import ContinuousBatchingEngine, EngineConfig


@pytest.fixture(scope="module")
def tiny():
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
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


def _refs(model, params, prompts, max_new):
    from fengshen_tpu.utils.generate import generate
    outs = []
    for p in prompts:
        out = np.asarray(generate(model, params, jnp.asarray(p)[None],
                                  max_new_tokens=max_new))
        outs.append(out[0, len(p):].tolist())
    return outs


def _counts(registry, metric):
    m = registry.get(metric)
    if m is None:
        return {}
    return {k[0]: c.value for k, c in m.children()}


def _engine(tiny, tmp, registry=None, log=None, **aot_kw):
    model, params = tiny
    aot = AotSetup(AotConfig(cache_dir=str(tmp), **aot_kw),
                   registry=registry, log=log)
    return ContinuousBatchingEngine(
        model, params,
        EngineConfig(num_slots=2, buckets=(8, 16), max_new_tokens=10,
                     max_queue=16),
        aot=aot)


# ---- cached_compile core ------------------------------------------------

def test_cached_compile_miss_then_hit(tmp_path):
    reg = MetricsRegistry()
    cache = ExecutableCache(str(tmp_path), registry=reg)

    def f(a, b):
        return a @ b + 1.0

    avals = (jax.ShapeDtypeStruct((4, 4), jnp.float32),
             jax.ShapeDtypeStruct((4,), jnp.float32))
    exe1 = cached_compile(f, "t/f", *avals, cache=cache, registry=reg)
    assert _counts(reg, "fstpu_aot_cache_misses_total") == {"t/f": 1}
    files = [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]
    assert len(files) == 1
    exe2 = cached_compile(f, "t/f", *avals, cache=cache, registry=reg)
    assert _counts(reg, "fstpu_aot_cache_hits_total") == {"t/f": 1}
    a = jnp.eye(4)
    b = jnp.arange(4.0)
    np.testing.assert_allclose(np.asarray(exe1(a, b)),
                               np.asarray(exe2(a, b)))
    np.testing.assert_allclose(np.asarray(exe2(a, b)),
                               np.asarray(b + 1.0))


def test_cache_key_changes_with_program_and_options(tmp_path):
    from fengshen_tpu.aot import cache_key

    def f(x):
        return x * 2

    def g(x):
        return x * 3

    aval = jax.ShapeDtypeStruct((4,), jnp.float32)
    low_f = jax.jit(f).lower(aval)
    low_g = jax.jit(g).lower(aval)
    assert cache_key("n", low_f) == cache_key("n", low_f)
    assert cache_key("n", low_f) != cache_key("n", low_g)
    assert cache_key("n", low_f) != cache_key("m", low_f)
    assert cache_key("n", low_f) != cache_key(
        "n", low_f, compiler_options={"xla_cpu_enable_fast_math": True})


def test_cached_function_store_failure_still_returns_result(
        tmp_path, monkeypatch):
    """A failing store (full disk, read-only dir) degrades to
    compile-every-time — counted, never raised."""
    reg = MetricsRegistry()
    cache = ExecutableCache(str(tmp_path), registry=reg)
    import fengshen_tpu.aot.cache as cache_mod

    def boom(compiled):
        raise OSError("disk full")

    monkeypatch.setattr(
        "jax.experimental.serialize_executable.serialize", boom)
    cf = CachedFunction(lambda x: x + 1, "t/s", cache=cache,
                        registry=reg)
    out = cf(jnp.arange(3.0))
    np.testing.assert_allclose(np.asarray(out), [1.0, 2.0, 3.0])
    assert _counts(reg, cache_mod.ERRORS_METRIC) == {"t/s": 1}
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]


# ---- the parity contract ------------------------------------------------

def test_engine_parity_through_deserialized_cache(tiny, tmp_path):
    """Populate the cache with one engine, then serve a FRESH engine
    entirely from deserialized executables: greedy decode must be
    token-identical to sequential generate (the acceptance bar couples
    the cold-start win to decode parity)."""
    model, params = tiny
    prompts = _prompts((5, 11, 16, 7))
    refs = _refs(model, params, prompts, 10)
    reg = MetricsRegistry()

    e1 = _engine(tiny, tmp_path, registry=reg)
    e1.warmup()
    assert e1.generate_all(prompts) == refs
    stored = [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]
    assert len(stored) >= 4   # 2 prefill buckets + assign + decode

    reg2 = MetricsRegistry()
    e2 = _engine(tiny, tmp_path, registry=reg2)
    e2.warmup()
    assert e2.generate_all(prompts) == refs
    hits = _counts(reg2, "fstpu_aot_cache_hits_total")
    assert sum(hits.values()) >= 4, hits
    assert _counts(reg2, "fstpu_aot_cache_misses_total") == {}


def test_corrupt_blob_never_fails_job(tiny, tmp_path):
    """Truncate/garble every blob: the engine must warm up by
    recompiling, errors_total must show it, and parity must hold."""
    model, params = tiny
    prompts = _prompts((5, 12))
    refs = _refs(model, params, prompts, 8)
    e1 = _engine(tiny, tmp_path)
    e1.warmup()
    e1.generate_all(prompts)
    for fn in os.listdir(tmp_path):
        if fn.endswith(".aotx"):
            with open(os.path.join(tmp_path, fn), "wb") as f:
                f.write(b"not a pickle")
    reg = MetricsRegistry()
    events = []
    e2 = _engine(tiny, tmp_path, registry=reg, log=events.append)
    e2.warmup()
    outs = [t[:8] for t in e2.generate_all(prompts, max_new_tokens=8)]
    assert outs == refs
    errors = _counts(reg, "fstpu_aot_cache_errors_total")
    assert sum(errors.values()) >= 1, errors
    assert any(e.get("event") == "aot_cache_error" for e in events)
    # the corrupt files were replaced by fresh compiles
    e3_reg = MetricsRegistry()
    e3 = _engine(tiny, tmp_path, registry=e3_reg)
    e3.warmup()
    assert sum(_counts(e3_reg,
                       "fstpu_aot_cache_hits_total").values()) >= 4


def test_jax_version_mismatch_blob_recompiles(tmp_path):
    """A blob whose header names a different jax version must load as
    an error (counted) and recompile — never crash, never run a
    foreign executable."""
    reg = MetricsRegistry()
    cache = ExecutableCache(str(tmp_path), registry=reg)

    def f(x):
        return x - 5.0

    aval = jax.ShapeDtypeStruct((3,), jnp.float32)
    cached_compile(f, "t/v", aval, cache=cache, registry=reg)
    (path,) = [os.path.join(tmp_path, fn) for fn in os.listdir(tmp_path)
               if fn.endswith(".aotx")]
    with open(path, "rb") as fh:
        blob = pickle.load(fh)
    blob["jax"] = "0.0.0-from-the-past"
    with open(path, "wb") as fh:
        pickle.dump(blob, fh)
    exe = cached_compile(f, "t/v", aval, cache=cache, registry=reg)
    np.testing.assert_allclose(np.asarray(exe(jnp.zeros(3))),
                               [-5.0, -5.0, -5.0])
    assert _counts(reg, "fstpu_aot_cache_errors_total") == {"t/v": 1}
    assert _counts(reg, "fstpu_aot_cache_misses_total") == {"t/v": 2}


# ---- warmup manifest ----------------------------------------------------

def test_avals_encode_decode_roundtrip():
    args = ({"w": np.zeros((3, 4), np.float32),
             "b": jnp.ones((4,), jnp.int32)},
            np.int32(7), [np.zeros((2,), bool), None],
            (np.float64(1.5),))
    dec = decode_avals(encode_avals(args))
    assert isinstance(dec, tuple) and isinstance(dec[2], list)
    assert dec[0]["w"].shape == (3, 4)
    assert str(dec[0]["w"].dtype) == "float32"
    assert dec[1].shape == () and str(dec[1].dtype) == "int32"
    assert str(dec[2][0].dtype) == "bool" and dec[2][1] is None
    assert str(dec[3][0].dtype) == "float64"


def test_manifest_records_and_replays(tmp_path):
    reg = MetricsRegistry()
    setup = AotSetup(AotConfig(cache_dir=str(tmp_path)), registry=reg)
    cf = setup.wrap(lambda a, b: a * b, "t/mul")
    cf(jnp.arange(4.0), jnp.ones(4))
    man = json.load(open(os.path.join(tmp_path,
                                      "warmup_manifest.json")))
    assert len(man["entries"]) == 1
    entry = man["entries"][0]
    assert entry["name"] == "t/mul"
    assert entry["key"] and entry["fingerprint"]

    # fresh "process": trusted replay adopts by key — no lower, no miss
    reg2 = MetricsRegistry()
    setup2 = AotSetup(AotConfig(cache_dir=str(tmp_path)),
                      registry=reg2)
    cf2 = setup2.wrap(lambda a, b: a * b, "t/mul")
    summary = setup2.replay({"t/mul": cf2})
    assert summary["adopted"] == 1 and summary["failed"] == 0
    assert cf2._cache_size() == 1
    assert _counts(reg2, "fstpu_aot_cache_misses_total") == {}
    out = cf2(jnp.arange(4.0), jnp.full((4,), 2.0))
    np.testing.assert_allclose(np.asarray(out), [0.0, 2.0, 4.0, 6.0])


def test_manifest_fingerprint_drift_demotes_to_verified_replay(
        tmp_path):
    """A tampered/stale fingerprint must NOT adopt by key — replay
    falls back to lower-and-hash (still warming the function)."""
    setup = AotSetup(AotConfig(cache_dir=str(tmp_path)))
    cf = setup.wrap(lambda x: x + 2, "t/add")
    cf(jnp.arange(3.0))
    mpath = os.path.join(tmp_path, "warmup_manifest.json")
    man = json.load(open(mpath))
    man["entries"][0]["fingerprint"] = "stale-code-digest"
    with open(mpath, "w") as f:
        json.dump(man, f)

    reg = MetricsRegistry()
    setup2 = AotSetup(AotConfig(cache_dir=str(tmp_path)), registry=reg)
    cf2 = setup2.wrap(lambda x: x + 2, "t/add")
    summary = setup2.replay({"t/add": cf2})
    assert summary["adopted"] == 0 and summary["replayed"] == 1
    # verified path re-lowered and HIT the cache by content address
    assert sum(_counts(reg, "fstpu_aot_cache_hits_total").values()) == 1
    assert cf2._cache_size() == 1


def test_manifest_corrupt_file_starts_empty(tmp_path):
    path = os.path.join(tmp_path, "m.json")
    with open(path, "w") as f:
        f.write("{broken json")
    events = []
    man = WarmupManifest(path, record=True, log=events.append)
    assert len(man) == 0
    assert any(e.get("event") == "aot_manifest_error" for e in events)
    assert man.record("t/x", (np.zeros((2,), np.float32),))
    assert len(WarmupManifest(path)) == 1


def test_replay_skips_unknown_functions(tmp_path):
    setup = AotSetup(AotConfig(cache_dir=str(tmp_path)))
    cf = setup.wrap(lambda x: x, "t/known")
    cf(jnp.zeros(2))
    man = setup.manifest
    man.record("t/unknown", (np.zeros((2,), np.float32),))
    summary = man.replay({"t/known": cf}, trusted=False)
    assert summary["skipped"] == 1 and summary["failed"] == 0


# ---- LRU size cap -------------------------------------------------------

def test_lru_purge_evicts_least_recently_used(tmp_path):
    cache = ExecutableCache(str(tmp_path))
    for i, name in enumerate(("a", "b", "c")):
        p = cache.path_for(name, "k" * 8)
        with open(p, "wb") as f:
            f.write(b"x" * 100)
        os.utime(p, (1000 + i, 1000 + i))   # a oldest, c newest
    removed = cache.purge(max_bytes=250)
    assert [e.name for e in removed] == ["a"]
    assert {e.name for e in cache.entries()} == {"b", "c"}
    removed = cache.purge(drop_all=True)
    assert len(removed) == 2 and cache.entries() == []


def test_store_triggers_size_cap(tmp_path):
    reg = MetricsRegistry()
    cache = ExecutableCache(str(tmp_path), max_bytes=1, registry=reg)

    def f(x):
        return x * 2

    cached_compile(f, "t/cap", jax.ShapeDtypeStruct((2,), jnp.float32),
                   cache=cache, registry=reg)
    # the just-stored blob immediately exceeds the 1-byte cap
    assert cache.entries() == []


# ---- CLI ----------------------------------------------------------------

def test_cli_ls_and_purge(tmp_path, capsys):
    from fengshen_tpu.aot.__main__ import main as cli
    d = str(tmp_path / "cache")
    cache = ExecutableCache(d)
    cached_compile(lambda x: x + 1, "t/cli",
                   jax.ShapeDtypeStruct((2,), jnp.float32), cache=cache)
    assert cli(["ls", "--cache-dir", d]) == 0
    out = capsys.readouterr().out
    assert "t-cli" in out and "total: 1 executables" in out
    assert cli(["ls", "--cache-dir", d, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["entries"][0]["name"] == "t-cli"
    assert doc["total_bytes"] > 0
    assert cli(["purge", "--cache-dir", d, "--all"]) == 0
    assert "purged 1 executables" in capsys.readouterr().out
    assert cli(["ls", "--cache-dir", d]) == 0
    assert "empty" in capsys.readouterr().out


def test_cli_purge_requires_a_mode(tmp_path):
    from fengshen_tpu.aot.__main__ import main as cli
    assert cli(["purge", "--cache-dir", str(tmp_path)]) == 2


def test_cli_warm_usage_errors(tmp_path):
    from fengshen_tpu.aot.__main__ import main as cli
    assert cli(["warm", "--config",
                str(tmp_path / "missing.json")]) == 2
    cfg = tmp_path / "server.json"
    cfg.write_text(json.dumps({"PIPELINE": {"task": "text_generation"}}))
    # no AOT block and no --cache-dir override → nothing to pre-bake
    assert cli(["warm", "--config", str(cfg)]) == 2


# ---- /healthz readiness -------------------------------------------------

class _DummyPipeline:
    def __call__(self, text, **kw):
        return "ok:" + text


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_healthz_503_until_ready_stdlib():
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)
    ready = threading.Event()
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0),
        PipelineConfig(task="text_classification"),
        pipeline=_DummyPipeline(), ready=ready)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        code, body = _get(f"http://127.0.0.1:{port}/healthz")
        assert code == 503 and body["status"] == "warming"
        # ISSUE 10: the 503 body names the ready/reason contract the
        # fleet router keys on (warmup = the way in, vs draining)
        assert body["ready"] is False and body["reason"] == "warmup"
        ready.set()
        code, body = _get(f"http://127.0.0.1:{port}/healthz")
        assert code == 200 and body["status"] == "ok"
        assert body["ready"] is True
    finally:
        server.shutdown()


def test_healthz_defaults_to_ready_stdlib():
    """ready=None (every existing caller) keeps the old always-200
    behavior."""
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0),
        PipelineConfig(task="text_classification"),
        pipeline=_DummyPipeline())
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        code, body = _get(f"http://127.0.0.1:{port}/healthz")
        assert code == 200 and body["status"] == "ok"
    finally:
        server.shutdown()


def test_healthz_503_until_ready_fastapi():
    fastapi = pytest.importorskip("fastapi")  # noqa: F841
    from fastapi.testclient import TestClient

    from fengshen_tpu.api.main import PipelineConfig, build_app
    ready = threading.Event()
    app = build_app(PipelineConfig(task="text_classification"),
                    pipeline=_DummyPipeline(), ready=ready)
    client = TestClient(app)
    r = client.get("/healthz")
    assert r.status_code == 503 and r.json()["status"] == "warming"
    # the fastapi path mirrors the stdlib ready/reason body (ISSUE 10)
    assert r.json()["ready"] is False and r.json()["reason"] == "warmup"
    ready.set()
    r = client.get("/healthz")
    assert r.status_code == 200 and r.json()["ready"] is True


# ---- warmup + build-info gauges ----------------------------------------

def test_build_info_and_warmup_gauges():
    from fengshen_tpu.observability import (get_registry,
                                            record_build_info,
                                            record_warmup_seconds)
    record_build_info()
    g = get_registry().get("fstpu_build_info")
    children = dict(g.children())
    assert (jax.__version__, jax.default_backend()) in children
    assert children[(jax.__version__, jax.default_backend())].value == 1

    record_warmup_seconds("test_phase", 1.25)
    w = get_registry().get("fstpu_warmup_seconds")
    assert dict(w.children())[("test_phase",)].value == 1.25


def test_engine_warmup_sets_global_gauge(tiny):
    from fengshen_tpu.observability import get_registry
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=4, max_queue=4))
    dt = eng.warmup()
    w = get_registry().get("fstpu_warmup_seconds")
    recorded = dict(w.children())[("engine",)].value
    assert recorded == pytest.approx(dt, rel=0.2)


def test_warmup_pipeline_sets_gauge():
    from fengshen_tpu.api.main import warmup_pipeline
    from fengshen_tpu.observability import get_registry
    dt = warmup_pipeline(_DummyPipeline(), "dummy")
    assert dt is not None
    w = get_registry().get("fstpu_warmup_seconds")
    assert ("pipeline",) in dict(w.children())


# ---- AOT config block plumbing -----------------------------------------

def test_server_config_aot_block(tmp_path):
    from fengshen_tpu.api.main import load_config
    cfg = tmp_path / "server.json"
    cfg.write_text(json.dumps({
        "SERVER": {"engine": "continuous"},
        "PIPELINE": {"task": "text_generation"},
        "AOT": {"cache_dir": "/tmp/x", "record": False}}))
    server_cfg, _ = load_config(str(cfg))
    assert server_cfg.aot_args == {"cache_dir": "/tmp/x",
                                   "record": False}
    # no AOT block → empty dict, engine runs plain jit
    cfg.write_text(json.dumps({"PIPELINE": {"task": "t"}}))
    server_cfg, _ = load_config(str(cfg))
    assert server_cfg.aot_args == {}


def test_create_continuous_engine_wires_aot(tiny, tmp_path):
    from fengshen_tpu.aot import CachedFunction as CF
    from fengshen_tpu.api.main import create_continuous_engine
    from fengshen_tpu.pipelines.text_generation import Pipeline

    model, params = tiny

    class Tok:
        eos_token_id = None
        pad_token_id = 0

        def encode(self, text):
            return [int(t) for t in text.split()]

        def decode(self, ids):
            return " ".join(str(t) for t in ids)

    pipe = Pipeline(module=model, params=params, tokenizer=Tok(),
                    max_new_tokens=4)
    engine = create_continuous_engine(
        pipe, {"num_slots": 1, "buckets": (8,)},
        aot_args={"cache_dir": str(tmp_path)})
    assert isinstance(engine._decode_jit, CF)
    engine2 = create_continuous_engine(pipe, {"num_slots": 1,
                                              "buckets": (8,)})
    assert not isinstance(engine2._decode_jit, CF)


def test_unpicklable_treedef_falls_back_to_flat_blob(tmp_path):
    """A program whose out tree carries unpicklable static metadata
    (the TrainState-with-optax-closures case) must still round-trip
    through the cache — stored flat, re-wrapped from the loader's
    Lowered — and stay invisible to the caller."""

    @jax.tree_util.register_pytree_node_class
    class Box:
        def __init__(self, x, fn):
            self.x, self.fn = x, fn

        def tree_flatten(self):
            return (self.x,), self.fn

        @classmethod
        def tree_unflatten(cls, aux, children):
            return cls(children[0], aux)

    local_fn = lambda v: v  # noqa: E731 — deliberately unpicklable aux

    def f(b, y):
        return Box(b.x + y, b.fn), (b.x * 2).sum()

    reg = MetricsRegistry()
    cache = ExecutableCache(str(tmp_path), registry=reg)
    box_aval = Box(jax.ShapeDtypeStruct((3,), jnp.float32), local_fn)
    y_aval = jax.ShapeDtypeStruct((3,), jnp.float32)
    cached_compile(f, "t/flat", box_aval, y_aval, cache=cache,
                   registry=reg)
    (path,) = [os.path.join(tmp_path, fn) for fn in os.listdir(tmp_path)
               if fn.endswith(".aotx")]
    with open(path, "rb") as fh:
        blob = pickle.load(fh)
    assert blob["tree_mode"] == "flat"
    assert blob["n_in"] == 2 and blob["n_out"] == 2

    exe = cached_compile(f, "t/flat", box_aval, y_aval, cache=cache,
                         registry=reg)
    assert _counts(reg, "fstpu_aot_cache_hits_total") == {"t/flat": 1}
    out_box, total = exe(Box(jnp.arange(3.0), local_fn), jnp.ones(3))
    assert isinstance(out_box, Box) and out_box.fn is local_fn
    np.testing.assert_allclose(np.asarray(out_box.x), [1.0, 2.0, 3.0])
    assert float(total) == 6.0

    # a flat blob is NOT adoptable without a Lowered (trusted replay
    # declines it) — and declining is a miss, not an error
    cf = CachedFunction(f, "t/flat", cache=cache, registry=reg)
    assert cf.adopt((box_aval, y_aval), blob["key"]) is False
    assert _counts(reg, "fstpu_aot_cache_errors_total") == {}


def test_failed_engine_warmup_keeps_the_gate_shut(tiny, capsys):
    """A warmup that raises (a program that did not compile) must not
    turn /healthz green: the ready event stays unset, /healthz keeps
    answering 503 with the error, and the serve loop is not started —
    serving on would re-raise the same failure one request at a time."""
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       _start_warmup_thread,
                                       build_stdlib_server)
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=4, max_queue=4))
    eng.warmup = lambda: (_ for _ in ()).throw(
        RuntimeError("Mosaic failed to compile"))
    server_cfg = ServerConfig(host="127.0.0.1", port=0,
                              engine="continuous")
    pipeline_cfg = PipelineConfig(task="text_generation")
    ready = _start_warmup_thread(server_cfg, pipeline_cfg, None, eng)
    assert ready.settled.wait(30)
    assert not ready.is_set()
    assert ready.error == "RuntimeError: Mosaic failed to compile"
    assert eng._thread is None          # no serve loop behind the gate
    server = build_stdlib_server(server_cfg, pipeline_cfg,
                                 pipeline=_DummyPipeline(), engine=eng,
                                 ready=ready)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        code, body = _get(
            "http://127.0.0.1:%d/healthz" % server.server_address[1])
        assert code == 503 and body["ready"] is False
        assert body["reason"] == "warmup_failed"
        assert "Mosaic failed to compile" in body["error"]
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=10)
    assert "warmup failed" in capsys.readouterr().out
