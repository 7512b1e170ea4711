"""The readiness gate and the warm-up gauges.

- `/healthz` answers 503 until the warm-up thread sets the gate, and
  stays 503 with the error when the warm-up raises;
- `fstpu_build_info` and `fstpu_warmup_seconds{phase}` are recorded by
  the engine's and the pipeline's warm-up.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

import jax
import jax.numpy as jnp

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
