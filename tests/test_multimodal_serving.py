"""Multimodal serving engines (serving/multimodal.py) and the api
engine-type dispatch (docs/serving.md "Multimodal engines").

The contracts pinned here:

- `MicroBatchEngine` actually micro-batches (requests inside one gather
  window ride one `run_batch` launch) and honors the continuous
  engine's admission surface — QueueFull, Draining, DuplicateRequest —
  so the fleet router's retry contract holds across engine types;
- `_multimodal_generate` maps those to the same HTTP codes the text
  path uses (429/503/409/422) and the 200 body carries `engine_type`;
- the server dispatches on `engine.engine_type` — a
  batch_image/embedding engine behind `POST /api/<task>` answers
  through the micro-batch path, and `/stats` exposes the micro-batch
  block.

The engine/dispatch unit tests run on a fake pipeline so the machinery
is pinned fast and deterministically; the real towers (small-test
Taiyi-SD denoise loop + VAE decode, Taiyi-CLIP text embeddings) are
exercised end-to-end — pipeline → engine → HTTP server — by the
tests at the bottom.
"""

import json
import threading
import time

import pytest

from fengshen_tpu.serving import (Draining, DuplicateRequest, QueueFull,
                                  BatchImageEngine, EmbeddingEngine,
                                  MULTIMODAL_ENGINE_TYPES,
                                  create_multimodal_engine)
from fengshen_tpu.serving.multimodal import (
    MM_CANCELLED, MM_FAILED, MM_FINISHED)


class FakePipeline:
    """Stands in for pipelines/{image_generation,embedding}: records
    the batches the engine launches."""

    def __init__(self, fail=False, delay_s=0.0):
        self.batches = []
        self.fail = fail
        self.delay_s = delay_s

    def warmup_input(self):
        return "warmup"

    def run_batch(self, inputs):
        if self.delay_s:
            time.sleep(self.delay_s)
        self.batches.append(list(inputs))
        if self.fail:
            raise RuntimeError("tower exploded")
        return [{"result_for": text} for text in inputs]


def _engine(cls=EmbeddingEngine, pipeline=None, **kw):
    kw.setdefault("gather_ms", 20.0)
    eng = cls(pipeline if pipeline is not None else FakePipeline(), **kw)
    return eng


def test_engine_requires_run_batch_pipeline():
    class TextPipeline:
        def __call__(self, text):
            return text

    with pytest.raises(ValueError, match="run_batch"):
        EmbeddingEngine(TextPipeline())


def test_create_multimodal_engine_table():
    assert set(MULTIMODAL_ENGINE_TYPES) == {"batch_image", "embedding"}
    pipe = FakePipeline()
    eng = create_multimodal_engine("batch_image", pipe,
                                   {"max_batch": 3, "gather_ms": 0.0})
    assert isinstance(eng, BatchImageEngine)
    assert eng.engine_type == "batch_image"
    assert eng.max_batch == 3 and eng.gather_ms == 0.0
    with pytest.raises(ValueError, match="unknown multimodal engine"):
        create_multimodal_engine("continuous", pipe)


def test_submit_wait_finish_roundtrip():
    pipe = FakePipeline()
    eng = _engine(pipeline=pipe)
    eng.start()
    try:
        req = eng.submit("你好")
        assert req.wait(timeout=10)
        assert req.state == MM_FINISHED
        assert req.result == {"result_for": "你好"}
        assert req.request_id.startswith("embedding-")
    finally:
        eng.stop()
    assert eng.idle()


def test_requests_in_gather_window_ride_one_batch():
    pipe = FakePipeline()
    eng = _engine(pipeline=pipe, max_batch=4, gather_ms=200.0)
    reqs = [eng.submit(f"p{i}") for i in range(3)]
    eng.start()
    try:
        for r in reqs:
            assert r.wait(timeout=10) and r.state == MM_FINISHED
    finally:
        eng.stop()
    assert pipe.batches == [["p0", "p1", "p2"]]
    stats = eng.stats()
    assert stats["batches_total"] == 1
    assert stats["avg_batch"] == 3.0


def test_batch_never_exceeds_max_batch():
    pipe = FakePipeline()
    eng = _engine(pipeline=pipe, max_batch=2, gather_ms=50.0)
    reqs = [eng.submit(f"p{i}") for i in range(5)]
    eng.start()
    try:
        for r in reqs:
            assert r.wait(timeout=10) and r.state == MM_FINISHED
    finally:
        eng.stop()
    assert all(len(b) <= 2 for b in pipe.batches)
    assert sum(len(b) for b in pipe.batches) == 5


def test_admission_contract_queue_full_duplicate_drain():
    eng = _engine(max_queue=2)  # worker NOT started: nothing drains
    eng.submit("a", request_id="r1")
    with pytest.raises(DuplicateRequest):
        eng.submit("a again", request_id="r1")
    eng.submit("b")
    with pytest.raises(QueueFull):
        eng.submit("c")
    with pytest.raises(ValueError, match="empty input"):
        eng.submit("   ")
    eng.begin_drain()
    with pytest.raises(Draining):
        eng.submit("d", request_id="r9")
    assert eng.stats()["draining"] is True


def test_cancel_queued_request():
    eng = _engine()
    req = eng.submit("a", request_id="doomed")
    assert eng.cancel("doomed") is True
    assert req.state == MM_CANCELLED
    assert eng.cancel("doomed") is False        # already gone
    assert eng.cancel("never-existed") is False
    # the id is free again after cancel (dedupe map must not leak)
    eng.submit("retry", request_id="doomed")


def test_batch_failure_answers_requests_not_worker():
    pipe = FakePipeline(fail=True)
    eng = _engine(pipeline=pipe)
    eng.start()
    try:
        req = eng.submit("a")
        assert req.wait(timeout=10)
        assert req.state == MM_FAILED
        assert "tower exploded" in req.error
        # the worker thread survived the batch failure
        pipe.fail = False
        ok = eng.submit("b")
        assert ok.wait(timeout=10) and ok.state == MM_FINISHED
    finally:
        eng.stop()


def test_stop_cancels_queued_requests():
    eng = _engine()
    req = eng.submit("never served")
    eng.stop()
    assert req.state == MM_CANCELLED
    assert req.error == "engine stopped"


def test_warmup_runs_max_batch_and_stats_record_it():
    pipe = FakePipeline()
    eng = _engine(pipeline=pipe, max_batch=3)
    dt = eng.warmup()
    assert dt >= 0
    assert pipe.batches == [["warmup"] * 3]
    stats = eng.stats()
    assert stats["engine_type"] == "embedding"
    assert stats["warmup_s"] == dt
    assert stats["max_batch"] == 3
    assert stats["queue_depth"] == 0 and stats["in_flight"] == 0


# ---- the HTTP mapping ---------------------------------------------------

def _mm_generate(engine, req, timeout_s=10.0):
    from fengshen_tpu.api.main import _multimodal_generate
    return _multimodal_generate(engine, None, req, timeout_s)


def test_multimodal_generate_success_carries_engine_type():
    eng = _engine(cls=BatchImageEngine)
    eng.start()
    try:
        code, body = _mm_generate(eng, {"input_text": "一只猫"})
        assert code == 200
        assert body["result"] == {"result_for": "一只猫"}
        assert body["engine_type"] == "batch_image"
        assert body["request_id"]
    finally:
        eng.stop()


def test_multimodal_generate_backpressure_codes():
    eng = _engine(max_queue=1)  # no worker: deterministic backpressure
    eng.submit("filler", request_id="dup")
    code, body = _mm_generate(eng, {"input_text": "x",
                                    "request_id": "dup"})
    assert code == 409
    code, body = _mm_generate(eng, {"input_text": "x"})
    assert code == 429
    code, body = _mm_generate(eng, {"input_text": "  "})
    assert code == 422
    eng.begin_drain()
    code, body = _mm_generate(eng, {"input_text": "x"})
    assert code == 503 and body["reason"] == "draining"


def test_multimodal_generate_timeout_cancels_and_503s():
    eng = _engine()  # no worker: wait() can never be satisfied
    code, body = _mm_generate(eng, {"input_text": "x"}, timeout_s=0.05)
    assert code == 503 and "timed out" in body["error"]
    # the timed-out request was cancelled out of the queue
    assert eng.idle()


def test_multimodal_generate_failed_batch_maps_503():
    eng = _engine(pipeline=FakePipeline(fail=True))
    eng.start()
    try:
        code, body = _mm_generate(eng, {"input_text": "x"})
        assert code == 503
        assert "failed" in body["error"] and "tower exploded" in \
            body["error"]
    finally:
        eng.stop()


# ---- server dispatch ---------------------------------------------------

def _stdlib_server(engine, task):
    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)
    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0, engine=engine.engine_type),
        PipelineConfig(task=task), pipeline=engine.pipeline,
        engine=engine)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, server.server_address[1]


@pytest.mark.parametrize("cls,task", [
    (EmbeddingEngine, "embedding"),
    (BatchImageEngine, "image_generation")],
    ids=["embedding", "batch_image"])
def test_stdlib_server_dispatches_multimodal_engine(cls, task):
    import urllib.error
    import urllib.request

    eng = _engine(cls=cls)
    eng.start()
    server, port = _stdlib_server(eng, task)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/{task}",
            data=json.dumps({"input_text": "测试"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["engine_type"] == cls.engine_type
        assert out["result"] == {"result_for": "测试"}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10) as r:
            stats = json.loads(r.read())
        assert stats["engine_type"] == cls.engine_type
        assert stats["requests_total"] >= 1
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/{task}",
            data=json.dumps({"input_text": "  "}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(bad, timeout=30)
        assert exc.value.code == 422
    finally:
        server.shutdown()
        eng.stop()


def test_server_config_accepts_multimodal_engine_names():
    from fengshen_tpu.api.main import ServerConfig
    for name in ("simple", "continuous", "batch_image", "embedding"):
        ServerConfig(engine=name)
    with pytest.raises(ValueError, match="batch_image"):
        ServerConfig(engine="micro")


# ---- real towers end-to-end (pipeline → engine → stdlib HTTP) -----------

def test_embedding_tower_serves_end_to_end():
    import urllib.request

    from fengshen_tpu.pipelines.embedding import Pipeline

    pipe = Pipeline(small_test=True, seed=0)
    eng = EmbeddingEngine(pipe, max_batch=2, gather_ms=2.0)
    eng.warmup()
    eng.start()
    server, port = _stdlib_server(eng, "embedding")
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/embedding",
            data=json.dumps({"input_text": "今天天气真好"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
        assert out["engine_type"] == "embedding"
        emb = out["result"]["embedding"]
        assert len(emb) == out["result"]["dim"] > 0
        # the tower L2-normalizes (CLIP contract)
        assert abs(sum(x * x for x in emb) - 1.0) < 1e-3
    finally:
        server.shutdown()
        eng.stop()


def test_batch_image_tower_serves_end_to_end():
    import base64
    import urllib.request

    from fengshen_tpu.pipelines.image_generation import Pipeline

    pipe = Pipeline(small_test=True, seed=0)
    eng = BatchImageEngine(pipe, max_batch=2, gather_ms=2.0)
    eng.warmup()
    eng.start()
    server, port = _stdlib_server(eng, "image_generation")
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/image_generation",
            data=json.dumps({"input_text": "一只橘猫"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            out = json.loads(r.read())
        assert out["engine_type"] == "batch_image"
        result = out["result"]
        assert result["dtype"] == "uint8"
        h, w, c = result["shape"]
        raw = base64.b64decode(result["image_b64"])
        assert len(raw) == h * w * c and c == 3
    finally:
        server.shutdown()
        eng.stop()
