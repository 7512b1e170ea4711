"""The folded entry of the decode-attention seam: rows that hold a
token's few, wide KV heads (docs/kernels.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops.pallas.decode_attention import (
    _folded_ineligible_reason, _layer_of_stack,
    folded_decode_attention, pallas_folded_decode_attention)


#: per lane of a `_folded_case`, the query's position on a table row of
#: four 128-token blocks (None: a released lane, its row parked on the
#: null block, cursor 0): the first key alone, one key short of a block
#: boundary, on it, past it, the row's last key
_FOLDED_LANES = (0, 127, 128, 129, 511, None, 300)


def _folded_case(rng, groups, stacked, dtype, n_heads=4, dim=128):
    """q, pools, table, t, layer for the seam's folded entry, and the
    lanes' keys and values in order for a plain reference. Every lane's
    four blocks lie scattered in a pool whose block 0 is the null
    block; `stacked` hands the pools as a `[3, ...]` stack read at
    layer 1."""
    lanes, per, block = len(_FOLDED_LANES), 4, 128
    width = groups * dim
    rows_k = rng.randn(lanes, per * block, width).astype(np.float32)
    rows_v = rng.randn(lanes, per * block, width).astype(np.float32)
    order = rng.permutation(lanes * per) + 1
    table = order.reshape(lanes, per).astype(np.int32)
    layers = 3 if stacked else 1
    pools = []
    for rows in (rows_k, rows_v):
        pool = rng.randn(layers, lanes * per + 1, block, 1,
                         width).astype(np.float32)
        pool[layers // 2, order] = rows.reshape(-1, block, 1, width)
        pools.append(jnp.asarray(pool if stacked else pool[0], dtype))
    t = np.array([0 if at is None else at for at in _FOLDED_LANES],
                 np.int32)
    for b, at in enumerate(_FOLDED_LANES):
        if at is None:
            table[b] = 0
    q = jnp.asarray(rng.randn(lanes, 1, n_heads, dim), dtype)
    layer = jnp.int32(1) if stacked else None
    return (q, pools[0], pools[1], jnp.asarray(table), jnp.asarray(t),
            layer, rows_k, rows_v)


def _grouped_softmax(q, rows_k, rows_v, t, groups):
    """Plain grouped-query attention of one query a lane over keys
    `0 .. t` of the lane's rows, float32."""
    lanes, _, n_heads, dim = q.shape
    k = rows_k.reshape(lanes, -1, groups, dim).repeat(n_heads // groups, 2)
    v = rows_v.reshape(lanes, -1, groups, dim).repeat(n_heads // groups, 2)
    s = np.einsum("bhd,bthd->bht", np.asarray(q, np.float32)[:, 0],
                  k) * dim ** -0.5
    s = np.where(np.arange(k.shape[1])[None, None] <= t[:, None, None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bht,bthd->bhd", p, v)[:, None]


def _folded_kernel(q, k, v, table, t, layer, per):
    """The kernel in interpret mode: through the seam, or (`per`) with
    that many blocks a step, as the seam calls it."""
    scale = q.shape[-1] ** -0.5
    if per is None:
        return folded_decode_attention(q, k, v, table, t, scale=scale,
                                       layer=layer, impl="pallas",
                                       interpret=True)
    if layer is not None:
        k, v, table = _layer_of_stack(k, v, table, layer)
    return pallas_folded_decode_attention(q, k, v, table, t, scale=scale,
                                          blocks_per_step=per,
                                          interpret=True)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("stacked", [False, True], ids=["pool", "stack"])
@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("per", [None, 1, 3], ids=["row", "1", "3"])
def test_folded_decode_kernel_interpret_parity(per, groups, stacked, dtype):
    """The folded kernel (interpret mode) against `folded_decode_walk`,
    the seam's xla lowering, and against plain grouped softmax
    attention: lanes of different lengths in one call
    (`_FOLDED_LANES`), a released lane on the null block, one and two
    KV heads a row, the pools as they are and as a stack read in place
    through `layer`, blocks scattered; the whole 4-block row a step (the
    seam's 8 blocks, cut to the row), a block a step, and 3, which
    leaves a lane's last step one live block of three. In float32 the
    two differ by the online softmax's partition; in bfloat16 they
    round the same operands the same way."""
    rng = np.random.RandomState(330 + 4 * groups + 2 * stacked +
                                (dtype == "float32"))
    q, k, v, table, t, layer, rows_k, rows_v = _folded_case(
        rng, groups, stacked, jnp.dtype(dtype))
    assert _folded_ineligible_reason(q, k) is None
    got = _folded_kernel(q, k, v, table, t, layer, per)
    walk = folded_decode_attention(q, k, v, table, t,
                                   scale=q.shape[-1] ** -0.5, layer=layer,
                                   impl="xla")
    assert got.shape == q.shape and got.dtype == q.dtype
    tol = 2e-5 if dtype == "float32" else 2e-2
    held = np.array([at is not None for at in _FOLDED_LANES])
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(walk, np.float32),
                               rtol=tol, atol=tol)
    if dtype == "bfloat16":
        rows_k, rows_v = (np.asarray(jnp.asarray(x, jnp.bfloat16),
                                     np.float32) for x in (rows_k, rows_v))
    want = _grouped_softmax(q, rows_k, rows_v, np.asarray(t), groups)
    np.testing.assert_allclose(np.asarray(got, np.float32)[held],
                               want[held], rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("groups", [2, 4])
@pytest.mark.parametrize("window", [1, 4, 8])
def test_folded_window_shares_one_extent(window, groups, dtype):
    """`S` queries a lane that all read keys `<= t[lane]` (a generation
    block's: `t` is the block's last position): the kernel in interpret
    mode and the walk against the DENSE lowering, plain grouped softmax
    attention of every query over the lane's rows in order; 8 query
    heads over 2 and over 4 KV heads (SDAR's row: four heads of 128),
    the pools as a stack read through `layer`."""
    rng = np.random.RandomState(350 + window + groups)
    q1, k, v, table, t, layer, rows_k, rows_v = _folded_case(
        rng, groups, True, jnp.dtype(dtype), n_heads=8)
    q = jnp.asarray(rng.randn(q1.shape[0], window, 8, 128), q1.dtype)
    assert _folded_ineligible_reason(q, k) is None
    scale = q.shape[-1] ** -0.5
    got = folded_decode_attention(q, k, v, table, t, scale=scale,
                                  layer=layer, impl="pallas", interpret=True)
    walk = folded_decode_attention(q, k, v, table, t, scale=scale,
                                   layer=layer, impl="xla")
    assert got.shape == walk.shape == q.shape and got.dtype == q.dtype
    if dtype == "bfloat16":
        rows_k, rows_v = (np.asarray(jnp.asarray(x, jnp.bfloat16),
                                     np.float32) for x in (rows_k, rows_v))
    want = np.concatenate([
        _grouped_softmax(q[:, i:i + 1], rows_k, rows_v, np.asarray(t),
                         groups) for i in range(window)], axis=1)
    tol = 2e-5 if dtype == "float32" else 2e-2
    held = np.array([at is not None for at in _FOLDED_LANES])
    for out in (got, walk):
        np.testing.assert_allclose(np.asarray(out, np.float32)[held],
                                   want[held], rtol=tol, atol=tol)


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("per", [None, 1, 3], ids=["row", "1", "3"])
def test_folded_decode_kernel_never_reads_past_a_lanes_cursor(per, groups):
    """That the walk ends at each lane's OWN last block, inside a step
    too: with NaN in the null block and in every block past a lane's
    cursor, the lanes that hold a request return what they return over
    clean pools, bit for bit (the xla lowering walks every lane to the
    longest lane's cursor and multiplies a zero probability by that
    NaN)."""
    rng = np.random.RandomState(340 + groups)
    q, k, v, table, t, _, _, _ = _folded_case(rng, groups, False,
                                              jnp.float32)
    clean = _folded_kernel(q, k, v, table, t, None, per)
    held = np.array([at is not None for at in _FOLDED_LANES])
    reached = {int(block) for b, row in enumerate(np.asarray(table))
               if held[b] for block in row[:int(t[b]) // 128 + 1]}
    dead = [i for i in range(k.shape[0]) if i not in reached]
    assert 0 in dead and len(dead) > len(_FOLDED_LANES)
    k, v = (x.at[jnp.asarray(dead)].set(jnp.nan) for x in (k, v))
    out = np.asarray(_folded_kernel(q, k, v, table, t, None, per))
    assert np.isfinite(out[held]).all()
    np.testing.assert_array_equal(out[held], np.asarray(clean)[held])
    lost = np.asarray(folded_decode_attention(
        q, k, v, table, t, scale=q.shape[-1] ** -0.5, impl="xla"))
    assert np.isnan(lost[held]).any()


@pytest.mark.parametrize("q_shape, kv_shape, why", [
    ((2, 1, 4, 16), (9, 8, 1, 32), "head_dim 16 % 128"),
    ((2, 1, 4, 128), (9, 8, 1, 256), "block_size 8 % 128"),
    ((2, 9, 4, 128), (9, 128, 1, 256), "query window 9 > 8"),
    ((2, 4, 4, 128), (9, 128, 1, 256), None),
    ((2, 1, 4, 128), (9, 128, 2, 128), "do not fold"),
    ((2, 1, 4, 128), (9, 128, 1, 320), "do not fold"),
    ((2, 1, 4, 128), (9, 128, 1, 384), "3 kv heads do not divide 4"),
    ((2, 1, 16, 256), (3, 9, 128, 1, 512), None),
    ((2, 1, 4, 128), (9, 256, 1, 128), None),
], ids=["narrow_head", "tiny_block", "long_window", "block_window",
        "unfolded_heads", "ragged_width", "heads_not_grouped", "cell_stack",
        "one_kv_head"])
def test_folded_dispatch_follows_the_rows_shape(fresh_probe, monkeypatch,
                                                q_shape, kv_shape, why):
    """The folded entry chooses its path from the rows' shape through
    `resolve_dispatch`: on a backend that runs Mosaic an eligible shape
    takes the kernel, any other the xla lowering with the reason on
    record; the tiny shapes of the CPU tests (8-token blocks, 16-wide
    heads) are among those."""
    import fengshen_tpu.ops.pallas as kernels
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "test"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16)
    layer = jnp.int32(1) if len(kv_shape) == 5 else None
    reason = _folded_ineligible_reason(q, k)
    assert (reason is None) if why is None else (why in reason), reason
    if why == "do not fold":
        return          # not rows the walk can read either: no call
    out = jax.eval_shape(
        lambda q, k, table, t: folded_decode_attention(
            q, k, k, table, t, scale=1.0, layer=layer),
        q, k, jax.ShapeDtypeStruct((2, 4), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32))
    assert out.shape == q_shape
    took, = kernels.traced_dispatch()
    assert took["op"] == "folded_decode_attention"
    assert took["impl"] == ("pallas" if why is None else "xla")
    assert (why is None) or (why in took["detail"])
