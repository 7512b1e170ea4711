"""Pipeline / CLI / metrics / CRF tests."""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # full-fit/e2e lane: run with -m slow or no -m filter


def _bert_tokenizer(tmp_path):
    from transformers import BertTokenizer
    chars = list("今天天气很好坏非常糟糕开心难过测试句子北京上海人名地名")
    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + \
        sorted(set(chars))
    vf = tmp_path / "vocab.txt"
    vf.write_text("\n".join(vocab))
    return BertTokenizer(str(vf))


# -- metrics --------------------------------------------------------------

def test_metrics_mlm_acc():
    from fengshen_tpu.metrics import metrics_mlm_acc
    logits = np.zeros((1, 3, 4))
    logits[0, 0, 1] = 9
    logits[0, 1, 2] = 9
    logits[0, 2, 3] = 9
    labels = np.array([[1, 2, -100]])
    assert metrics_mlm_acc(logits, labels) == 1.0
    labels2 = np.array([[1, 0, -100]])
    assert metrics_mlm_acc(logits, labels2) == 0.5


def test_seq_entity_score_bio():
    from fengshen_tpu.metrics import SeqEntityScore
    id2label = {0: "O", 1: "B-PER", 2: "I-PER", 3: "B-LOC"}
    score = SeqEntityScore(id2label, markup="bio")
    score.update([[1, 2, 0, 3]], [[1, 2, 0, 3]])
    overall, per_class = score.result()
    assert overall["f1"] == 1.0
    score.reset()
    score.update([[1, 2, 0, 3]], [[1, 2, 0, 0]])
    overall, _ = score.result()
    assert 0 < overall["f1"] < 1.0


def test_get_entities_bios():
    from fengshen_tpu.metrics import get_entities
    tags = ["B-PER", "I-PER", "O", "S-LOC"]
    ents = get_entities(tags, markup="bios")
    assert ["PER", 0, 1] in ents and ["LOC", 3, 3] in ents


def test_bert_extract_item():
    from fengshen_tpu.metrics import bert_extract_item
    start = np.zeros((6, 3))
    end = np.zeros((6, 3))
    start[2, 1] = 9  # inner position 1 (after [CLS] strip)
    end[3, 1] = 9
    spans = bert_extract_item(start, end)
    assert spans == [(1, 1, 2)]


# -- CRF ------------------------------------------------------------------

def test_crf_loglik_and_decode():
    from fengshen_tpu.models.tagging import CRF
    crf = CRF(num_tags=4)
    rng = jax.random.PRNGKey(0)
    emissions = jnp.asarray(np.random.RandomState(0).randn(2, 6, 4),
                            jnp.float32)
    tags = jnp.asarray(np.random.RandomState(1).randint(0, 4, (2, 6)))
    mask = jnp.asarray([[1, 1, 1, 1, 1, 0], [1, 1, 1, 0, 0, 0]], jnp.int32)
    params = crf.init(rng, emissions, tags, mask)
    nll = crf.apply(params, emissions, tags, mask)
    assert np.isfinite(float(nll)) and float(nll) > 0

    decoded = crf.apply(params, emissions, mask, method=CRF.decode)
    assert decoded.shape == (2, 6)
    # brute-force check best path for the first (length-5) sequence
    import itertools
    p = params["params"]
    best_score, best_path = -1e30, None
    em = np.asarray(emissions)[0]
    for path in itertools.product(range(4), repeat=5):
        s = float(p["start_transitions"][path[0]]) + em[0, path[0]]
        for t in range(1, 5):
            s += float(p["transitions"][path[t - 1], path[t]]) + \
                em[t, path[t]]
        s += float(p["end_transitions"][path[4]])
        if s > best_score:
            best_score, best_path = s, path
    np.testing.assert_array_equal(np.asarray(decoded)[0][:5], best_path)


def test_crf_normalizer_brute_force():
    from fengshen_tpu.models.tagging import CRF
    import itertools
    crf = CRF(num_tags=3)
    emissions = jnp.asarray(np.random.RandomState(2).randn(1, 4, 3),
                            jnp.float32)
    tags = jnp.zeros((1, 4), jnp.int32)
    params = crf.init(jax.random.PRNGKey(0), emissions, tags)
    p = params["params"]
    em = np.asarray(emissions)[0]
    scores = []
    for path in itertools.product(range(3), repeat=4):
        s = float(p["start_transitions"][path[0]]) + em[0, path[0]]
        for t in range(1, 4):
            s += float(p["transitions"][path[t - 1], path[t]]) + \
                em[t, path[t]]
        s += float(p["end_transitions"][path[3]])
        scores.append(s)
    from scipy.special import logsumexp
    ref_z = logsumexp(scores)
    # nll of the all-zeros path
    s0 = float(p["start_transitions"][0]) + em[0, 0] + sum(
        float(p["transitions"][0, 0]) + em[t, 0] for t in range(1, 4)) + \
        float(p["end_transitions"][0])
    ref_nll = -(s0 - ref_z)
    nll = crf.apply(params, emissions, tags)
    np.testing.assert_allclose(float(nll), ref_nll, atol=1e-4)


# -- pipelines ------------------------------------------------------------

def test_text_classification_pipeline_train_and_predict(tmp_path, mesh8):
    from fengshen_tpu.pipelines.text_classification import (
        TextClassificationPipeline)
    from fengshen_tpu.models.megatron_bert import MegatronBertConfig

    tok = _bert_tokenizer(tmp_path)
    parser = argparse.ArgumentParser()
    parser = TextClassificationPipeline.add_pipeline_specific_args(parser)
    args = parser.parse_args([
        "--max_length", "16", "--train_batchsize", "4", "--max_steps", "2",
        "--log_every_n_steps", "1", "--warmup_steps", "1",
        "--default_root_dir", str(tmp_path / "runs"),
        "--save_ckpt_path", str(tmp_path / "ckpt"),
        "--load_ckpt_path", str(tmp_path / "none")])

    cfg = MegatronBertConfig(
        vocab_size=len(tok), hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, dtype="float32", num_labels=2)
    pipe = TextClassificationPipeline(args=args, tokenizer=tok, config=cfg)

    data = [{"sentence": "今天天气很好", "label": 1},
            {"sentence": "非常糟糕难过", "label": 0}] * 8

    class DS:
        def __len__(self):
            return len(data)

        def __getitem__(self, i):
            return data[i]

    pipe.train({"train": DS()})
    result = pipe("今天天气很好")
    assert set(result) == {"label", "score"}
    results = pipe(["今天天气很好", "非常糟糕"])
    assert len(results) == 2


def test_sequence_tagging_pipeline_predict(tmp_path):
    from fengshen_tpu.pipelines.sequence_tagging import (
        SequenceTaggingPipeline)
    from fengshen_tpu.models.megatron_bert import MegatronBertConfig
    tok = _bert_tokenizer(tmp_path)
    cfg = MegatronBertConfig(
        vocab_size=len(tok), hidden_size=32, num_hidden_layers=1,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, dtype="float32")
    pipe = SequenceTaggingPipeline(
        args=None, tokenizer=tok, config=cfg,
        labels=["O", "B-LOC", "I-LOC"])
    out = pipe("北京上海")
    assert isinstance(out, list)
    for ent in out:
        assert set(ent) == {"entity", "type", "start", "end"}


# -- CLI ------------------------------------------------------------------

def test_cli_usage_and_unknown_task(capsys):
    from fengshen_tpu.cli.fengshen_pipeline import main
    assert main([]) == 2
    assert main(["text_classification", "explode"]) == 2
    with pytest.raises(SystemExit, match="unknown task"):
        main(["not_a_task", "predict"])


# -- API ------------------------------------------------------------------

def test_api_stdlib_server_roundtrip():
    """The server's surface: POST /api/<task> + GET /healthz."""
    import json as json_mod
    import threading
    import urllib.request

    from fengshen_tpu.api.main import (PipelineConfig, ServerConfig,
                                       build_stdlib_server)

    calls = []

    def fake_pipeline(text):
        calls.append(text)
        return [{"label": "1", "score": 0.9}]

    server = build_stdlib_server(
        ServerConfig(host="127.0.0.1", port=0),
        PipelineConfig(task="text_classification"),
        pipeline=fake_pipeline)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            health = json_mod.loads(r.read())
        assert health == {"status": "ok", "task": "text_classification",
                          "ready": True}

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/text_classification",
            data=json_mod.dumps({"input_text": "天气很好"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            out = json_mod.loads(r.read())
        assert out["result"][0]["label"] == "1"
        assert calls == ["天气很好"]

        # missing field → 422, wrong path → 404
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/text_classification",
            data=b"{}", headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(bad, timeout=10)
            assert False, "expected 422"
        except urllib.error.HTTPError as e:
            assert e.code == 422
    finally:
        server.shutdown()
