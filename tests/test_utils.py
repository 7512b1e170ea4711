"""Checkpoint round-trip, generation parity, and collator-stack tests."""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest


# -- checkpoint ----------------------------------------------------------

def test_checkpoint_save_restore_roundtrip(tmp_path, mesh8):
    import optax
    from fengshen_tpu.trainer.train_state import TrainState
    from fengshen_tpu.utils.universal_checkpoint import UniversalCheckpoint

    params = {"w": jnp.arange(16.0).reshape(4, 4), "b": jnp.ones((4,))}
    tx = optax.adamw(1e-3)
    state = TrainState.create(apply_fn=lambda: None, params=params, tx=tx)
    state = state.apply_gradients(
        jax.tree_util.tree_map(jnp.ones_like, params))

    parser = argparse.ArgumentParser()
    UniversalCheckpoint.add_argparse_args(parser)
    args = parser.parse_args(["--save_ckpt_path", str(tmp_path / "ck"),
                              "--load_ckpt_path", str(tmp_path / "ck")])

    class FakeTrainer:
        global_step = 7
        consumed_samples = 700

    cb = UniversalCheckpoint(args)
    cb.save(state, FakeTrainer())

    fresh = TrainState.create(apply_fn=lambda: None,
                              params=jax.tree_util.tree_map(
                                  jnp.zeros_like, params), tx=tx)
    t2 = FakeTrainer()
    t2.global_step = 0
    t2.consumed_samples = 0
    restored = cb.maybe_restore(fresh, t2)
    np.testing.assert_allclose(restored.params["w"], state.params["w"])
    assert t2.global_step == 7 and t2.consumed_samples == 700
    assert int(restored.step) == 7


def test_checkpoint_weights_only_restore_into_full_run(tmp_path, mesh8):
    """A --save_weights_only checkpoint restored by a run WITHOUT that flag
    must silently keep the fresh optimizer state (ADVICE r1)."""
    import optax
    from fengshen_tpu.trainer.train_state import TrainState
    from fengshen_tpu.utils.universal_checkpoint import UniversalCheckpoint

    params = {"w": jnp.arange(16.0).reshape(4, 4), "b": jnp.ones((4,))}
    tx = optax.adamw(1e-3)
    state = TrainState.create(apply_fn=lambda: None, params=params, tx=tx)

    parser = argparse.ArgumentParser()
    UniversalCheckpoint.add_argparse_args(parser)
    save_args = parser.parse_args(
        ["--save_ckpt_path", str(tmp_path / "ck"),
         "--load_ckpt_path", str(tmp_path / "ck"), "--save_weights_only"])

    class FakeTrainer:
        global_step = 3
        consumed_samples = 30

    UniversalCheckpoint(save_args).save(state, FakeTrainer())

    load_args = parser.parse_args(
        ["--save_ckpt_path", str(tmp_path / "ck"),
         "--load_ckpt_path", str(tmp_path / "ck")])  # full run, no flag
    fresh = TrainState.create(apply_fn=lambda: None,
                              params=jax.tree_util.tree_map(
                                  jnp.zeros_like, params), tx=tx)
    t2 = FakeTrainer()
    restored = UniversalCheckpoint(load_args).maybe_restore(fresh, t2)
    np.testing.assert_allclose(restored.params["w"], state.params["w"])
    # optimizer state falls back to the freshly initialized one
    chex = __import__("chex")
    chex.assert_trees_all_equal(restored.opt_state, fresh.opt_state)


def test_checkpoint_files_stay_under_twice_the_target(tmp_path, mesh8,
                                                     monkeypatch):
    """No checkpoint file may outgrow 2 x DATA_FILE_BYTES: a machine with
    a per-file size limit refuses a larger one with EFBIG mid-save. The
    callback that saved then restores weights-only AND in full."""
    import os

    import optax
    from fengshen_tpu.trainer.train_state import TrainState
    from fengshen_tpu.utils import universal_checkpoint as uc

    target = 64 * 1024
    monkeypatch.setattr(uc, "DATA_FILE_BYTES", target)
    # incompressible, and several times the target per array
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    params = {"w": jax.random.normal(keys[0], (256, 512)),
              "v": jax.random.normal(keys[1], (128, 512))}
    tx = optax.adamw(1e-3)
    state = TrainState.create(apply_fn=lambda: None, params=params, tx=tx)
    state = state.apply_gradients(grads=params)

    parser = argparse.ArgumentParser()
    uc.UniversalCheckpoint.add_argparse_args(parser)
    args = parser.parse_args(["--save_ckpt_path", str(tmp_path / "ck"),
                              "--load_ckpt_path", str(tmp_path / "ck")])

    class FakeTrainer:
        global_step = 2
        consumed_samples = 20

    cb = uc.UniversalCheckpoint(args)
    cb.save(state, FakeTrainer())
    sizes = [os.path.getsize(os.path.join(root, name))
             for root, _, names in os.walk(tmp_path / "ck")
             for name in names]
    assert sum(sizes) > 8 * target        # the bound was exercised
    assert max(sizes) <= 2 * target, sorted(sizes)[-3:]

    fresh = TrainState.create(apply_fn=lambda: None,
                              params=jax.tree_util.tree_map(
                                  jnp.zeros_like, params), tx=tx)
    weights = cb.maybe_restore(fresh, FakeTrainer(), weights_only=True)
    np.testing.assert_array_equal(weights.params["w"], state.params["w"])
    full = cb.maybe_restore(fresh, FakeTrainer())
    np.testing.assert_array_equal(full.params["v"], state.params["v"])
    chex = __import__("chex")
    chex.assert_trees_all_equal(full.opt_state, state.opt_state)


def test_checkpoint_missing_load_path_silently_skipped(tmp_path):
    import optax
    from fengshen_tpu.trainer.train_state import TrainState
    from fengshen_tpu.utils.universal_checkpoint import UniversalCheckpoint
    parser = argparse.ArgumentParser()
    UniversalCheckpoint.add_argparse_args(parser)
    args = parser.parse_args(["--load_ckpt_path",
                              str(tmp_path / "missing")])
    state = TrainState.create(apply_fn=lambda: None,
                              params={"w": jnp.ones((2,))},
                              tx=optax.sgd(1e-3))
    cb = UniversalCheckpoint(args)

    class T:
        global_step = 0
        consumed_samples = 0

    out = cb.maybe_restore(state, T())
    assert out is state  # reference behaviour: drop missing path silently


# -- generation ----------------------------------------------------------

def test_greedy_generate_matches_hf():
    torch = pytest.importorskip("torch")
    import transformers
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.models.llama.convert import torch_to_params
    from fengshen_tpu.utils.generate import generate

    hf_cfg = transformers.LlamaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=64, attn_implementation="eager",
        tie_word_embeddings=False)
    torch.manual_seed(3)
    tm = transformers.LlamaForCausalLM(hf_cfg).eval()
    cfg = LlamaConfig(vocab_size=96, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, dtype="float32")
    params = torch_to_params(tm.state_dict(), cfg)
    model = LlamaForCausalLM(cfg)

    prompt = np.array([[5, 11, 42, 7]], dtype=np.int64)
    with torch.no_grad():
        ref = tm.generate(torch.tensor(prompt), max_new_tokens=8,
                          do_sample=False).numpy()
    out = generate(model, params, jnp.asarray(prompt, jnp.int32),
                   max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(out)[0], ref[0])


def test_generate_left_padded_batch():
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.utils.generate import generate

    cfg = LlamaConfig.small_test_config(dtype="float32")
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    # single sequence vs the same sequence left-padded in a batch
    seq = np.array([9, 4, 77, 31], dtype=np.int32)
    single = generate(model, params, jnp.asarray(seq[None]),
                      max_new_tokens=4)
    padded = np.concatenate([[0, 0], seq]).astype(np.int32)
    mask = np.array([[0, 0, 1, 1, 1, 1]], dtype=np.int32)
    batch = generate(model, params, jnp.asarray(padded[None]),
                     attention_mask=jnp.asarray(mask), max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(batch)[0, -4:],
                                  np.asarray(single)[0, -4:])


def test_top_k_top_p_filters():
    from fengshen_tpu.utils.generate import top_k_logits, top_p_logits
    logits = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    k2 = top_k_logits(logits, k=2)
    assert np.asarray(k2)[0, 0] < -1e8 and np.asarray(k2)[0, 1] < -1e8
    assert np.asarray(k2)[0, 3] == 4.0
    # p small → only the top token survives
    p = top_p_logits(jnp.asarray([[0.0, 0.0, 5.0, 0.0]]), p=0.1)
    kept = np.asarray(p)[0] > -1e8
    assert kept.tolist() == [False, False, True, False]


# -- collator stack -------------------------------------------------------

def test_sentence_split():
    from fengshen_tpu.data.data_utils import ChineseSentenceSplitter
    s = ChineseSentenceSplitter()
    out = s.tokenize("今天天气很好。我们去公园吧！好吗？然后回家")
    assert out == ["今天天气很好。", "我们去公园吧！", "好吗？", "然后回家"]


def test_sop_pairing():
    from fengshen_tpu.data.data_utils import get_a_and_b_segments
    rng = np.random.RandomState(0)
    sents = [[1, 2], [3, 4], [5, 6]]
    a, b, swapped = get_a_and_b_segments(sents, rng)
    assert sorted(a + b) == [1, 2, 3, 4, 5, 6]
    if not swapped:
        assert a[0] == 1
    else:
        assert b[0] == 1


def test_truncate_segments():
    from fengshen_tpu.data.data_utils import truncate_segments
    rng = np.random.RandomState(1)
    a, b = list(range(10)), list(range(10, 18))
    truncated = truncate_segments(a, b, len(a), len(b), 12, rng)
    assert truncated and len(a) + len(b) == 12


def test_tokens_and_tokentypes():
    from fengshen_tpu.data.data_utils import create_tokens_and_tokentypes
    toks, types = create_tokens_and_tokentypes([5, 6], [7], cls_id=1,
                                               sep_id=2)
    assert toks == [1, 5, 6, 2, 7, 2]
    assert types == [0, 0, 0, 0, 1, 1]


def test_masked_lm_predictions_bert():
    from fengshen_tpu.data.data_utils import create_masked_lm_predictions
    vocab = {i: f"tok{i}" for i in range(100)}
    vocab[1], vocab[2], vocab[3] = "[CLS]", "[SEP]", "[MASK]"
    tokens = [1] + list(range(10, 30)) + [2]
    rng = np.random.RandomState(0)
    out, positions, labels = create_masked_lm_predictions(
        tokens, list(vocab), vocab, masked_lm_prob=0.3, cls_id=1, sep_id=2,
        mask_id=3, max_predictions_per_seq=6, np_rng=rng)
    assert len(positions) == len(labels) > 0
    assert 0 not in positions and len(tokens) - 1 not in positions
    for pos, label in zip(positions, labels):
        assert tokens[pos] == label  # label is the original token
    assert len(out) == len(tokens)


def test_masked_lm_whole_word_jieba():
    jieba = pytest.importorskip("jieba")
    from fengshen_tpu.data.data_utils.mask_utils import whole_word_spans
    chars = list("我们喜欢机器学习")
    spans = whole_word_spans(chars, zh_tokenizer=jieba.lcut)
    # jieba groups 我们/喜欢/机器/学习 (or similar multi-char words)
    assert sum(len(s) for s in spans) == len(chars)
    assert any(len(s) > 1 for s in spans)


def test_chinese_char_tokenize():
    from fengshen_tpu.utils import chinese_char_tokenize, is_chinese_char
    assert is_chinese_char(ord("中"))
    assert not is_chinese_char(ord("a"))
    assert chinese_char_tokenize("ab中c").split() == ["ab", "中", "c"]


def test_delta_roundtrip():
    from fengshen_tpu.utils.delta import make_delta, apply_delta
    base = {"w": np.ones((4,)), "b": np.zeros((2,))}
    target = {"w": np.full((4,), 3.0), "b": np.ones((2,))}
    delta = make_delta(base, target)
    back = apply_delta(base, delta)
    np.testing.assert_allclose(back["w"], target["w"])
    np.testing.assert_allclose(back["b"], target["b"])


def test_report_memory_runs(capsys):
    from fengshen_tpu.utils.utils import report_memory
    stats = report_memory("test")
    assert len(stats) >= 1
    assert "report_memory" in capsys.readouterr().out


def test_mmap_index_dataset(tmp_path):
    from fengshen_tpu.data.mmap_dataloader.mmap_index_dataset import (
        MMapIndexDataset, convert_py_to_npy)
    rows = [[1, 2, 3], [4, 5], [6]]
    convert_py_to_npy(rows, str(tmp_path), "input_ids")
    ds = MMapIndexDataset(str(tmp_path), ["input_ids"])
    assert len(ds) == 3
    np.testing.assert_array_equal(ds[0]["input_ids"], [1, 2, 3])
    np.testing.assert_array_equal(ds[2]["input_ids"], [6])


def test_conll_loader(tmp_path):
    from fengshen_tpu.data.sequence_tagging_dataloader import load_conll
    p = tmp_path / "ner.txt"
    p.write_text("北 B-LOC\n京 I-LOC\n好 O\n\n天 O\n")
    samples = load_conll(str(p))
    assert samples[0]["text"] == "北京好"
    assert samples[0]["labels"] == ["B-LOC", "I-LOC", "O"]
    assert samples[1]["text"] == "天"


def test_task_datasets(tmp_path):
    from fengshen_tpu.data.task_dataloader import (LCSTSDataset,
                                                   MedicalQADataset)
    p = tmp_path / "lcsts.jsonl"
    p.write_text('{"text": "正文", "summary": "摘要"}\n')
    ds = LCSTSDataset(str(p))
    assert ds[0] == {"text": "正文", "summary": "摘要"}
    q = tmp_path / "qa.jsonl"
    q.write_text('{"question": "问", "answer": "答"}\n')
    qa = MedicalQADataset(str(q))
    assert qa[0] == {"question": "问", "answer": "答"}
