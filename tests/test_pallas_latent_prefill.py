"""Latent attention's FULL form: the seam
`ops.latent_attention.latent_prefill_attention`, its Mosaic kernel in
interpret mode and its `jax.numpy` walk (docs/kernels.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops import pallas as kernels
from fengshen_tpu.ops.latent_attention import (latent_prefill_attention,
                                               latent_prefill_walk)
from fengshen_tpu.ops.pallas.latent_attention import (
    _ineligible_reason, pallas_latent_prefill_attention)

#: heads, dn, dr, dv, rank and the row's width: the serving rows' layout
#: `[c | k_shared | zeros]` at a quarter of their rank
H, DN, DR, DV, RANK, WIDTH = 2, 128, 64, 128, 128, 256
SCALE = 0.07


def _case(rng, seq, total, dtype, pad=None):
    """q_nope, q_shared, rows, w_kvb and the key validity of a prompt
    left-padded by `pad` rows (None: no validity at all)."""
    q_nope = jnp.asarray(rng.randn(1, seq, H, DN), dtype)
    q_shared = jnp.asarray(rng.randn(1, seq, H, DR), dtype)
    rows = rng.randn(1, total, WIDTH).astype(np.float32)
    rows[..., RANK + DR:] = 0.
    w_kvb = jnp.asarray(rng.randn(RANK, H, DN + DV) * RANK ** -0.5, dtype)
    valid = None
    if pad is not None:
        valid = np.ones((1, total), bool)
        valid[:, :pad] = False
    return q_nope, q_shared, rows, w_kvb, valid


def _dense(q_nope, q_shared, rows, w_kvb, start, valid):
    """Plain latent attention in float32 numpy: every row expanded,
    whole scores, a whole softmax. Rows past the last query are cut
    away first (they may hold NaN)."""
    seq = q_nope.shape[1]
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    rows = f32(rows)[:, :start + seq]
    kv = np.einsum("btc,chd->bthd", rows[..., :RANK], f32(w_kvb))
    s = (np.einsum("bshd,bthd->bhst", f32(q_nope), kv[..., :DN]) +
         np.einsum("bshr,btr->bhst", f32(q_shared),
                   rows[..., RANK:RANK + DR])) * SCALE
    ok = np.arange(start + seq)[None, :] <= \
        (start + np.arange(seq))[:, None]
    if valid is not None:
        ok = ok & np.asarray(valid)[0, None, :start + seq]
    s = np.where(ok[None, None], s, -np.inf)
    with np.errstate(invalid="ignore"):     # a pad query: no key
        p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhst,bthd->bshd", p, kv[..., DN:])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq, total, start, pad, tiles", [
    (128, 512, 0, None, (128, 128)),
    (256, 1024, 256, None, (128, 256)),
    (256, 1024, 384, None, (128, 256)),
    (256, 1024, 100, None, (128, 256)),
    (384, 2048, 128, None, (128, 128)),
    (256, 2048, 0, None, (256, 512)),
    (256, 512, 0, 37, (128, 256)),
    (256, 512, 0, 200, (128, 128)),
    (128, 1024, 300, 150, (128, 256)),
], ids=["start0", "start_on_a_block", "start_on_half_a_block",
        "start_off_every_tile", "three_tiles", "lane_far_longer",
        "left_padded", "a_whole_block_of_padding", "padded_and_carried"])
def test_latent_prefill_kernel_interpret_parity(seq, total, start, pad,
                                                tiles, dtype):
    """The kernel (interpret mode) against `latent_prefill_walk` and
    against plain latent attention in float32: a window at 0, at a
    multiple of the key block, at half a block and off every tile's
    edge (the diagonal then crosses two blocks a tile); one query tile
    and several; a lane far longer than `start + S`, NaN in every row
    from the first block no query reaches on (what the kernel never
    fetches; the rest of the frontier's own block is finite, as a
    serving cache's is: it weighs nothing); a left-padded prompt, its
    padding inside one block and as wide as a whole one, at 0 and onto
    a carried cache: a pad query has no valid key, its row comes out
    finite and is compared with nothing. In float32 the three differ by
    the online softmax's partition; in bfloat16 kernel and walk round
    the same operands the same way."""
    rng = np.random.RandomState(470 + seq + start)
    dt = jnp.dtype(dtype)
    q_nope, q_shared, rows, w_kvb, valid = _case(rng, seq, total, dt, pad)
    q_tile, key_block = tiles
    # the walk reads in blocks of its own: NaN from the first row that
    # neither reaches
    reach = -(-(start + seq) // key_block) * key_block
    rows[:, reach:] = np.nan
    rows = jnp.asarray(rows, dt)
    key_valid = None if valid is None else jnp.asarray(valid)
    got = pallas_latent_prefill_attention(
        q_nope, q_shared, rows, w_kvb, jnp.int32(start),
        key_valid=key_valid, scale=SCALE, q_tile=q_tile,
        key_block=key_block, interpret=True)
    assert got.shape == (1, seq, H, DV) and got.dtype == dt
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    want = np.asarray(latent_prefill_walk(
        q_nope, q_shared, rows, w_kvb, jnp.int32(start),
        key_valid=key_valid, scale=SCALE, q_tile=128,
        key_block=key_block), np.float32)
    # the queries that have a key: all, or those past the padding
    real = slice(max((pad or 0) - start, 0), None)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got[:, real], want[:, real],
                               rtol=tol, atol=tol)
    plain = _dense(q_nope, q_shared, rows, w_kvb, start, valid)
    np.testing.assert_allclose(got[:, real], plain[:, real],
                               rtol=tol, atol=tol)


def test_latent_prefill_kernel_takes_a_traced_start():
    """One compiled program, three offsets: `start` is an operand (the
    serving window program is compiled once a bucket and walks a prompt
    of sixteen windows), and the trip count follows it — with NaN past
    each offset's own frontier the results stay finite."""
    rng = np.random.RandomState(480)
    q_nope, q_shared, rows, w_kvb, _ = _case(rng, 128, 1024, jnp.float32)
    call = jax.jit(lambda rows, start: pallas_latent_prefill_attention(
        q_nope, q_shared, rows, w_kvb, start, scale=SCALE, q_tile=128,
        key_block=128, interpret=True))
    for start in (0, 128, 640):
        dirty = np.array(rows)
        dirty[:, start + 128:] = np.nan
        got = np.asarray(call(jnp.asarray(dirty), jnp.int32(start)))
        plain = _dense(q_nope, q_shared, rows, w_kvb, start, None)
        np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-5)
    assert call._cache_size() == 1


def test_the_walk_masks_a_left_padded_prompt_as_keys():
    """`key_valid` in the walk alone (the CPU's lowering of JoyAI's
    whole-prompt prefill), at sizes no tile divides: 13 queries onto 29
    rows, five of them padding."""
    rng = np.random.RandomState(481)
    q_nope, q_shared, rows, w_kvb, valid = _case(rng, 13, 29, jnp.float32,
                                                 pad=5)
    got = np.asarray(latent_prefill_walk(
        q_nope, q_shared, jnp.asarray(rows), w_kvb, jnp.int32(3),
        key_valid=jnp.asarray(valid), scale=SCALE))
    assert np.isfinite(got).all()
    plain = _dense(q_nope, q_shared, rows, w_kvb, 3, valid)
    np.testing.assert_allclose(got[:, 2:], plain[:, 2:], rtol=2e-5,
                               atol=2e-5)


@pytest.fixture()
def mosaic_backend(monkeypatch):
    """The seam as a chip would answer it (the probe says Mosaic; what
    is checked is the DECISION and its record, nothing runs)."""
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "test"))
    monkeypatch.setattr(kernels, "_TRACED", {})


def _shapes(seq=256, total=1024, dtype=jnp.bfloat16, rank=RANK, dr=DR,
            width=WIDTH):
    s = jax.ShapeDtypeStruct
    return (s((1, seq, H, DN), dtype), s((1, seq, H, dr), dtype),
            s((1, total, width), dtype), s((rank, H, DN + DV), dtype))


@pytest.mark.parametrize("kwargs, why", [
    (dict(seq=200), "window 200 % 128 != 0"),
    (dict(total=1000), "lane 1000 % 128 != 0"),
    (dict(rank=96, width=224), "rank 96 % 128 != 0"),
    (dict(dtype=jnp.float32), "q is float32, not bfloat16"),
    (dict(width=192), "row width 192 holds no whole lanes of the 64-value "
                      "shared key after rank 128"),
], ids=["window", "lane", "rank", "dtype", "row_width"])
def test_an_ineligible_shape_takes_the_walk_and_says_why(
        mosaic_backend, kwargs, why):
    """A shape the kernel cannot tile is routed to the xla lowering at
    trace time, and `traced_dispatch()` records `mla_prefill_attention`
    with the reason (on a chip the same line goes to stderr)."""
    shapes = _shapes(**kwargs)
    assert _ineligible_reason(*shapes) == why
    jax.eval_shape(
        lambda *a: latent_prefill_attention(*a, jnp.int32(0), scale=SCALE),
        *shapes)
    took = [d for d in kernels.traced_dispatch()
            if d["op"] == "mla_prefill_attention"]
    assert len(took) == 1 and took[0]["impl"] == "xla"
    assert took[0]["detail"].endswith(f"({why})")
    assert f"q={shapes[0].shape}+{shapes[1].shape[-1]}" in took[0]["detail"]


def test_the_seam_records_which_lowering_a_call_site_took(mosaic_backend):
    """With a Mosaic backend a tiling shape takes the kernel (recorded
    with the shapes and whether keys are masked); on the CPU as it is
    the same shape takes the walk, `backend cannot run Mosaic` on
    record, and returns the walk's result."""
    shapes = _shapes()
    assert _ineligible_reason(*shapes) is None
    valid = jax.ShapeDtypeStruct((1, 1024), jnp.bool_)
    jax.eval_shape(
        lambda *a: latent_prefill_attention(*a[:4], jnp.int32(256),
                                            key_valid=a[4], scale=SCALE),
        *shapes, valid)
    assert kernels.traced_dispatch() == [{
        "op": "mla_prefill_attention", "impl": "pallas",
        "detail": "q=(1, 256, 2, 128)+64:bfloat16 "
                  "rows=(1, 1024, 256):bfloat16 key_valid"}]
    assert kernels.dispatch_table()["mla_prefill_attention"] == "pallas"


def test_on_the_cpu_the_seam_is_the_walk(monkeypatch):
    monkeypatch.setattr(kernels, "_TRACED", {})
    rng = np.random.RandomState(482)
    q_nope, q_shared, rows, w_kvb, valid = _case(rng, 128, 256,
                                                 jnp.bfloat16, pad=9)
    args = (q_nope, q_shared, jnp.asarray(rows, jnp.bfloat16), w_kvb,
            jnp.int32(128))
    got = latent_prefill_attention(*args, key_valid=jnp.asarray(valid),
                                   scale=SCALE)
    want = latent_prefill_walk(*args, key_valid=jnp.asarray(valid),
                               scale=SCALE)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    took = kernels.traced_dispatch()
    assert [(d["op"], d["impl"]) for d in took] == [
        ("mla_prefill_attention", "xla")]
    assert took[0]["detail"].endswith("(backend cannot run Mosaic)")
    assert kernels.get_kernel("mla_prefill_attention") is latent_prefill_walk
