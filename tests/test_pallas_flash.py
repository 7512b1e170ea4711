"""Flash prefill, block-sparse attention and `run_per_shard`: the
adopted kernels' interpret parity (docs/kernels.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_flash_orphan_interpret_parity():
    """pallas_flash_attention (GQA, causal) vs the blockwise xla
    fallback it registers next to."""
    from fengshen_tpu.ops.flash_attention import blockwise_attention
    from fengshen_tpu.ops.pallas.flash_attention import (
        pallas_flash_attention)

    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(1, 256, 2, 128) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, 1, 128) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(1, 256, 1, 128) * 0.3, jnp.float32)
    out = pallas_flash_attention(q, k, v, causal=True, blk_q=128,
                                 blk_k=128, interpret=True)
    ref = blockwise_attention(q, jnp.repeat(k, 2, 2),
                              jnp.repeat(v, 2, 2), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("seq,cap,tile", [
    (2048, 1024, 1024), (1024, 1024, 1024), (128, 1024, 128),
    (1536, 1024, 768), (384, 256, 128), (1152, 1024, 384),
    (16, 8, 8)])
def test_flash_tile_divides_every_eligible_length(seq, cap, tile):
    """Any multiple of 128 is an eligible length; the tile is the
    largest under the cap that divides it (min(cap, seq) does not
    divide 384 or 1536, and the kernel then asserted)."""
    from fengshen_tpu.ops.pallas.flash_attention import _tile
    assert _tile(seq, cap) == tile and seq % tile == 0


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 2e-2)])
def test_flash_kernel_own_tiles_with_grad_interpret_parity(dtype, tol):
    """The kernels' own tiles (no `blk_q` / `blk_k` named) at a length
    the forward's does not divide (384 = 3 x 128), GQA, causal, a
    left-padded row as segment ids, forward and backward, against the
    dense chain, in float32 and at the serving dtype."""
    from fengshen_tpu.ops.attention import dot_product_attention
    from fengshen_tpu.ops.pallas.flash_attention import (
        pallas_flash_attention)

    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(2, 384, 2, 128) * 0.3, dtype)
    k = jnp.asarray(rng.randn(2, 384, 1, 128) * 0.3, dtype)
    v = jnp.asarray(rng.randn(2, 384, 1, 128) * 0.3, dtype)
    seg = jnp.asarray(np.stack([np.r_[np.zeros(50), np.ones(334)],
                                np.ones(384)]).astype(np.int32))
    mask = (jnp.tril(jnp.ones((384, 384), bool))[None, None] &
            (seg[:, None, :, None] == seg[:, None, None, :]))

    def dense(q, k, v):
        return dot_product_attention(q, jnp.repeat(k, 2, 2),
                                     jnp.repeat(v, 2, 2), mask=mask)

    def kernel(q, k, v):
        return pallas_flash_attention(q, k, v, seg, seg, True,
                                      interpret=True)

    def loss(f):
        return lambda q, k, v: (f(q, k, v).astype(jnp.float32) ** 2).sum()

    np.testing.assert_allclose(
        np.asarray(kernel(q, k, v), np.float32),
        np.asarray(dense(q, k, v), np.float32), rtol=tol, atol=tol)
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=tol, atol=tol)


def test_block_sparse_orphan_interpret_parity():
    """block_sparse_attention vs the dense expanded-mask fallback that
    ops.attention.dot_product_attention uses for ineligible shapes."""
    from fengshen_tpu.ops.attention import dot_product_attention
    from fengshen_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention)

    rng = np.random.RandomState(10)
    blk, n = 128, 2
    q = jnp.asarray(rng.randn(1, blk * n, 2, 128) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(1, blk * n, 2, 128) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(1, blk * n, 2, 128) * 0.3, jnp.float32)
    layout = np.tril(np.ones((n, n), bool))
    out = block_sparse_attention(q, k, v, layout, blk, interpret=True)
    mask = jnp.asarray(np.kron(layout, np.ones((blk, blk), bool)))
    ref = dot_product_attention(q, k, v, mask=mask[None, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_run_per_shard_under_a_mesh(mesh8):
    """GSPMD cannot partition a Mosaic call, so under a multi-device
    mesh the attention kernels run inside a shard_map — batch over the
    batch axes, heads over `tensor`, the sequence whole — and the
    result is the unsharded one."""
    from fengshen_tpu.ops.flash_attention import blockwise_attention
    from fengshen_tpu.ops.pallas import run_per_shard

    rng = np.random.RandomState(13)
    q, k, v = (jnp.asarray(rng.randn(4, 64, 4, 32) * 0.3, jnp.float32)
               for _ in range(3))
    seg = jnp.asarray(rng.randint(1, 3, (4, 64)), jnp.int32)
    seen = []

    def kernel(q, k, v, seg):
        seen.append((q.shape, seg.shape))
        return blockwise_attention(q, k, v, causal=True,
                                   q_segment_ids=seg, kv_segment_ids=seg)

    out = jax.jit(lambda *a: run_per_shard(kernel, *a))(q, k, v, seg)
    # data x fsdp = 4 ways over the batch, tensor = 2 ways over heads
    assert seen == [((1, 64, 2, 32), (1, 64))]
    from fengshen_tpu.parallel import set_mesh
    set_mesh(None)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(kernel(q, k, v, seg)),
                               rtol=1e-6, atol=1e-6)
    set_mesh(mesh8)
    # a batch the axes do not divide (the init pass) stays replicated
    seen.clear()
    jax.jit(lambda *a: run_per_shard(kernel, *a))(
        q[:1], k[:1], v[:1], seg[:1])
    assert seen == [((1, 64, 2, 32), (1, 64))]
