"""Fused cross-entropy (`ops/pallas/fused_ce.py`): dispatch, interpret
parity with gradients, and the vocabulary-parallel form
(docs/kernels.md)."""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def _ce_case(rng, batch=2, seq=8, hidden_dim=128, vocab=256):
    hidden = jnp.asarray(rng.randn(batch, seq, hidden_dim) * 0.1,
                         jnp.float32)
    kernel = jnp.asarray(rng.randn(hidden_dim, vocab) * 0.1, jnp.float32)
    labels = np.asarray(rng.randint(0, vocab, (batch, seq)))
    # some ignored positions + some guaranteed-correct ones (argmax
    # labels) so n_valid AND n_correct both carry signal
    labels[0, :2] = -100
    greedy = np.asarray((hidden @ kernel).argmax(-1))
    labels[1, :3] = greedy[1, :3]
    return hidden, kernel, jnp.asarray(labels, jnp.int32)


def test_fused_ce_dispatch_is_stock_on_cpu():
    """fused_ce_loss through the seam == ops.fused_ce.fused_lm_head_ce
    bitwise (the xla lowering IS that function)."""
    from fengshen_tpu.ops.fused_ce import fused_lm_head_ce
    from fengshen_tpu.ops.pallas.fused_ce import fused_ce_loss

    hidden, kernel, labels = _ce_case(np.random.RandomState(11))
    seam = fused_ce_loss(hidden, kernel, labels, num_chunks=4)
    stock = fused_lm_head_ce(hidden, kernel, labels, num_chunks=4)
    for a, b in zip(seam, stock):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_ce_stays_on_xla_under_a_mesh(mesh8):
    """The Mosaic CE is not partitioned: under a multi-device mesh the
    seam takes the xla lowering and says why."""
    from fengshen_tpu.ops.pallas.fused_ce import (_ineligible_reason,
                                                  pallas_ce_eligible)

    hidden, kernel, _ = _ce_case(np.random.RandomState(14))
    assert "8-device mesh" in _ineligible_reason(hidden, kernel)
    from fengshen_tpu.parallel import set_mesh
    set_mesh(None)
    assert pallas_ce_eligible(hidden, kernel)


def test_pallas_fused_ce_interpret_parity_and_grads():
    """The Mosaic CE (interpret mode): loss/n_valid/n_correct and the
    custom-vjp grads against the stock chunked-scan lowering."""
    from fengshen_tpu.ops.fused_ce import fused_lm_head_ce
    from fengshen_tpu.ops.pallas.fused_ce import pallas_fused_ce

    hidden, kernel, labels = _ce_case(np.random.RandomState(12))
    loss, n_valid, n_correct = pallas_fused_ce(hidden, kernel, labels,
                                               interpret=True)
    ref_loss, ref_valid, ref_correct = fused_lm_head_ce(
        hidden, kernel, labels, num_chunks=4)
    assert int(n_valid) == int(ref_valid)
    assert int(n_correct) == int(ref_correct) and int(n_correct) >= 3
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)

    g_pallas = jax.grad(
        lambda h, w: pallas_fused_ce(h, w, labels, interpret=True)[0],
        argnums=(0, 1))(hidden, kernel)
    g_stock = jax.grad(
        lambda h, w: fused_lm_head_ce(h, w, labels, num_chunks=4)[0],
        argnums=(0, 1))(hidden, kernel)
    for gp, gs in zip(g_pallas, g_stock):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gs),
                                   rtol=1e-5, atol=1e-6)


def test_fused_vocab_parallel_ce_bitwise(mesh8):
    """The sharded-vocab fused CE against the unfused
    vocab_parallel_cross_entropy on the tier-1 mesh (tensor=2): the
    per-chunk mpu collectives are the SAME ops on the same rows, so
    the loss must be bit-equal, never just close — and the full
    [B, S, V] logits never materialize on the fused side."""
    from fengshen_tpu.parallel.cross_entropy import (
        fused_vocab_parallel_ce, vocab_parallel_cross_entropy)

    hidden, kernel, labels = _ce_case(np.random.RandomState(13),
                                      hidden_dim=16, vocab=64)
    loss, n_valid, n_correct = fused_vocab_parallel_ce(
        hidden, kernel, labels, num_chunks=4)
    ref_loss, ref_valid = vocab_parallel_cross_entropy(
        hidden @ kernel, labels)
    assert float(loss) == float(ref_loss)  # bitwise
    assert int(n_valid) == int(ref_valid)
    greedy = np.asarray((hidden @ kernel).argmax(-1))
    want_correct = int(((greedy == np.asarray(labels)) &
                        (np.asarray(labels) != -100)).sum())
    assert int(n_correct) == want_correct and want_correct >= 3

    g_fused = jax.grad(lambda h: fused_vocab_parallel_ce(
        h, kernel, labels, num_chunks=4)[0])(hidden)
    g_ref = jax.grad(lambda h: vocab_parallel_cross_entropy(
        h @ kernel, labels)[0])(hidden)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-7)


def test_trainer_routes_vocab_parallel_fused_ce(mesh8):
    """CausalLMModule under tensor parallelism with fused_ce_chunks:
    the pinned `_fused_ce_active` gate still reports False (replicated
    lever off), the NEW mode routes `vocab_parallel`, and the loss
    equals the plain unfused path."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.trainer.modules import CausalLMModule

    base = LlamaConfig(vocab_size=64, hidden_size=32,
                       intermediate_size=64, num_hidden_layers=2,
                       num_attention_heads=4,
                       max_position_embeddings=32, dtype="float32")
    args = argparse.Namespace(max_seq_length=16)
    ids = jnp.asarray(np.random.RandomState(14).randint(0, 63, (2, 16)),
                      jnp.int32)
    batch = {"input_ids": ids}
    rng = jax.random.PRNGKey(0)

    plain = CausalLMModule(args, LlamaForCausalLM(base), base)
    params = plain.init_params(rng)
    cfg_f = dataclasses.replace(base, fused_ce_chunks=4)
    fused = CausalLMModule(args, LlamaForCausalLM(cfg_f), cfg_f)

    assert plain._fused_ce_mode() == "off"
    assert not fused._fused_ce_active()  # the pinned tensor-par gate
    assert fused._fused_ce_mode() == "vocab_parallel"

    l_p, m_p = plain.training_loss(params, batch, rng)
    l_f, m_f = fused.training_loss(params, batch, rng)
    np.testing.assert_allclose(float(l_p), float(l_f), rtol=1e-6)
    np.testing.assert_allclose(float(m_p["acc"]), float(m_f["acc"]),
                               rtol=1e-6)
