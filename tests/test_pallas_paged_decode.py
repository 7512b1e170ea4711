"""The decode-attention seam (`ops/pallas/decode_attention.py`): the xla
lowering is the stock math, and the Mosaic kernel, interpreted, walks
a slot cache and a paged pool to the same answer (docs/kernels.md)."""

import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops.pallas.decode_attention import (
    decode_attention, pallas_decode_attention,
    pallas_decode_eligible, xla_decode_attention)


def _stock_decode(q, k, v, valid, k_scale=None, v_scale=None,
                  block_table=None, dt=jnp.float32):
    """The pre-seam model path, inlined from what
    `_update_paged_cache`/`_update_cache` + the attention call used to
    do: take-gather, dequantize, GQA repeat, dense attention."""
    from fengshen_tpu.ops.attention import dot_product_attention
    from fengshen_tpu.ops.int8_matmul import dequantize_kv

    if block_table is not None:
        nb, bs = k.shape[:2]
        batch = q.shape[0]
        idx = ((block_table * bs)[:, :, None] +
               jnp.arange(bs)[None, None, :]).reshape(batch, -1)
        k = jnp.take(k.reshape(nb * bs, *k.shape[2:]), idx, axis=0)
        v = jnp.take(v.reshape(nb * bs, *v.shape[2:]), idx, axis=0)
        if k_scale is not None:
            ks = jnp.take(k_scale.reshape(nb * bs, -1), idx, axis=0)
            vs = jnp.take(v_scale.reshape(nb * bs, -1), idx, axis=0)
            k, v = dequantize_kv(k, ks, dt), dequantize_kv(v, vs, dt)
    elif k_scale is not None:
        k = dequantize_kv(k, k_scale, dt)
        v = dequantize_kv(v, v_scale, dt)
    rep = q.shape[2] // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return dot_product_attention(q, k, v, mask=valid[:, None])


def _decode_case(layout, quant, s, rng, batch=2, n_heads=16, kv_heads=8,
                 head_dim=128, block_size=128, blocks_per_lane=2):
    """One (layout, dtype, spec_mode) decode combo's operands (8 KV
    heads: the Mosaic kernel's fold needs a multiple of 8)."""
    virt = block_size * blocks_per_lane
    q = jnp.asarray(rng.randn(batch, s, n_heads, head_dim) * 0.3,
                    jnp.float32)
    ctx = virt - 37  # ragged fill: the last block is partial
    valid = jnp.asarray(
        np.broadcast_to(np.arange(virt) < ctx, (batch, s, virt)).copy())
    kw = {}
    if layout == "paged":
        nb = batch * blocks_per_lane
        shape = (nb, block_size, kv_heads, head_dim)
        kw["block_table"] = jnp.asarray(
            rng.permutation(nb).reshape(batch, blocks_per_lane),
            jnp.int32)
    else:
        shape = (batch, virt, kv_heads, head_dim)
    if quant:
        k = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        kw["k_scale"] = jnp.asarray(rng.rand(*shape[:-1]) * 0.02 + 0.001,
                                    jnp.float32)
        kw["v_scale"] = jnp.asarray(rng.rand(*shape[:-1]) * 0.02 + 0.001,
                                    jnp.float32)
    else:
        k = jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
        v = jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
    return q, k, v, valid, kw


@pytest.mark.parametrize("layout", ["slot", "paged"])
@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("s", [1, 4])  # decode tick / spec-verify window
def test_xla_decode_is_the_stock_math(layout, quant, s):
    """The dispatcher's xla lowering must be BITWISE the pre-seam
    model sequence on every (layout, dtype, spec_mode) combo — this is
    what makes greedy decode through the seam token-identical."""
    rng = np.random.RandomState(hash((layout, quant, s)) % 2**31)
    q, k, v, valid, kw = _decode_case(layout, quant, s, rng)
    seam = decode_attention(q, k, v, valid, **kw)
    stock = _stock_decode(q, k, v, valid,
                          k_scale=kw.get("k_scale"),
                          v_scale=kw.get("v_scale"),
                          block_table=kw.get("block_table"))
    assert seam.shape == q.shape
    np.testing.assert_array_equal(np.asarray(seam), np.asarray(stock))


#: how the lanes of a case fill their table rows, as (cursor, left
#: padding) a lane: the tick's query sits at `cursor` (a verify window's
#: queries at `cursor + t`) and sees `padding <= position <= cursor`.
#: Four 128-token blocks a lane; a paged lane is allotted the blocks up
#: to its cursor's and one more (the engine allots the answer's at
#: admission), the rest of its row stays on the null block
_WALKS = {
    # a released lane: cursor 0, its whole row parked on the null block
    "dead_lane": [(300, 0), None, (170, 0)],
    # rows whose tail is unallocated: one, two and one blocks held
    "open_tail": [(130, 0), (200, 0), (5, 0)],
    # the cursor on a block's last token ...
    "last_token": [(127, 0), (255, 0), (383, 0)],
    # ... and on the next block's first
    "first_token": [(128, 0), (256, 0), (384, 0)],
    # a four-query window whose queries straddle a block boundary
    "window_crosses": [(126, 0), (253, 0), (381, 0)],
    # left-padded prompts: holes at the FRONT, one wider than a block
    "left_padded": [(300, 40), (290, 130), (100, 0)],
}


def _walk_case(walk, layout, quant, s, rng):
    """`_decode_case`'s operands with the lanes of `_WALKS[walk]`.
    Returns them with `live` (the lanes that hold a request) and
    `reach` (per lane, the leading blocks of its row a query sees a key
    in: every row entry from there on is dead)."""
    lanes = _WALKS[walk]
    q, k, v, _, kw = _decode_case(layout, quant, s, rng, batch=len(lanes),
                                  blocks_per_lane=4)
    virt, at = 4 * 128, np.arange(4 * 128)
    valid = np.zeros((len(lanes), s, virt), bool)
    live, reach = [], []
    if layout == "paged":
        # block 0 is the null block; every lane's blocks are its own
        table = np.zeros((len(lanes), 4), np.int32)

        def grow(x):
            return jnp.concatenate([x, x, x[:1]])
        k, v = grow(k), grow(v)
        kw = {name: grow(x) for name, x in kw.items()
              if name != "block_table"}
    for b, lane in enumerate(lanes):
        cursor, pad = lane or (0, 0)
        for t in range(s):
            valid[b, t] = (at >= pad) & (at <= cursor + t)
        live.append(lane is not None)
        reach.append((cursor + s - 1) // 128 + 1)
        if layout == "paged" and lane is not None:
            held = min(4, reach[-1] + 1)
            table[b, :held] = 1 + 4 * b + np.arange(held)
    if layout == "paged":
        kw["block_table"] = jnp.asarray(table)
    return q, k, v, jnp.asarray(valid), kw, np.asarray(live), reach


@pytest.fixture()
def walk_everything(monkeypatch):
    """Once called, the kernel walks every block of every table row
    for the rest of the test, as it did before its trip count followed
    `valid`."""
    import importlib
    # (the package binds the seam FUNCTION under the submodule's name)
    module = importlib.import_module(
        "fengshen_tpu.ops.pallas.decode_attention")
    return lambda: monkeypatch.setattr(
        module, "_live_blocks", lambda valid, block_size: jnp.full(
            (valid.shape[0],), valid.shape[-1] // block_size, jnp.int32))


@pytest.mark.parametrize("layout", ["slot", "paged"])
@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("s", [1, 4])
@pytest.mark.parametrize("walk", ["ragged"] + sorted(_WALKS))
def test_pallas_decode_interpret_parity(layout, quant, s, walk,
                                        walk_everything):
    """The Mosaic kernel (interpret mode — same numerics the TPU
    compiles, run on CPU) against the stock lowering: fp32 tight, int8
    margin-aware (both paths round through the same dequant dtype, so
    the tolerance covers only the online-softmax reassociation). The
    kernel walks a lane's row only as far as `valid` reaches
    (`_WALKS`): what it returns is, for every lane, what it returns
    when made to walk every block — the skipped terms are exact
    zeros."""
    rng = np.random.RandomState(
        100 + hash((layout, quant, s, walk)) % 2**31)
    if walk == "ragged":
        q, k, v, valid, kw = _decode_case(layout, quant, s, rng)
    else:
        q, k, v, valid, kw, _, _ = _walk_case(walk, layout, quant, s, rng)
    assert pallas_decode_eligible(q, k, v,
                                  block_table=kw.get("block_table"))
    ref = xla_decode_attention(q, k, v, valid, **kw)
    out = pallas_decode_attention(q, k, v, valid, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    walk_everything()
    whole = pallas_decode_attention(q, k, v, valid, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(whole))


@pytest.mark.parametrize("layout", ["slot", "paged"])
@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("s", [1, 4])
@pytest.mark.parametrize("walk", ["dead_lane", "open_tail"])
def test_pallas_decode_never_reads_past_a_lanes_cursor(layout, quant, s,
                                                       walk,
                                                       walk_everything):
    """That the skip ENGAGES, not only that it is harmless: with NaN in
    the null block and in every block past a lane's cursor (an int8
    pool's NaN sits in its scales), the lanes that hold a request
    return what they return over clean pools, bit for bit. Made to walk
    every block the kernel multiplies a zero probability by that NaN
    and the lane is lost."""
    rng = np.random.RandomState(hash((layout, quant, s, walk)) % 2**31)
    q, k, v, valid, kw, live, reach = _walk_case(walk, layout, quant, s,
                                                 rng)
    clean = pallas_decode_attention(q, k, v, valid, interpret=True, **kw)

    def poison(x):
        x = np.array(x)
        if layout == "paged":
            table = np.asarray(kw["block_table"])
            reached = {int(block) for b, row in enumerate(table)
                       if live[b] for block in row[:reach[b]]}
            x[[i for i in range(len(x)) if i not in reached]] = np.nan
        else:
            for b, n in enumerate(reach):
                x[b, n * 128:] = np.nan
                if not live[b]:
                    x[b] = np.nan
        return jnp.asarray(x)

    if quant:
        kw = dict(kw, k_scale=poison(kw["k_scale"]),
                  v_scale=poison(kw["v_scale"]))
    else:
        k, v = poison(k), poison(v)
    out = np.asarray(pallas_decode_attention(q, k, v, valid,
                                             interpret=True, **kw))
    assert np.isfinite(out[live]).all()
    np.testing.assert_array_equal(out[live], np.asarray(clean)[live])
    walk_everything()
    lost = np.asarray(pallas_decode_attention(q, k, v, valid,
                                              interpret=True, **kw))
    assert np.isnan(lost[live]).any()


def test_live_blocks_follow_the_last_valid_column():
    """`_live_blocks` on hand-made masks: the block of the last valid
    column over all query positions, front holes inside the walk, and
    one block for a lane with no valid column at all."""
    from fengshen_tpu.ops.pallas.decode_attention import _live_blocks
    at = np.arange(512)
    rows = [at <= 0, at <= 127, at <= 128, (at >= 130) & (at <= 300),
            at < 0, at <= 511]
    valid = jnp.asarray(np.stack(rows)[:, None, :])
    assert _live_blocks(valid, 128).tolist() == [1, 1, 2, 3, 1, 4]
    # a window's last query reaches furthest
    window = jnp.asarray(np.stack(
        [np.stack([at <= 126 + t for t in range(4)])]))
    assert _live_blocks(window, 128).tolist() == [2]
    assert _live_blocks(window[:, :2], 128).tolist() == [1]


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("s", [1, 4])
def test_pallas_decode_reads_a_layer_of_the_stack(quant, s):
    """Under scan_layers the model never slices a layer's pool out of
    the `[L, num_blocks, ...]` stack: it hands the seam the stacks and
    `layer`, and the read views them as ONE pool of `L * num_blocks`
    blocks behind `block_table + layer * num_blocks`. The kernel over
    that view must be the kernel over layer `layer`'s own pool, bit
    for bit, and the xla lowering its twin."""
    rng = np.random.RandomState(7 + s + 2 * quant)
    q, _, _, valid, kw = _decode_case("paged", quant, s, rng)
    table = kw["block_table"]
    layers = [_decode_case("paged", quant, s, rng) for _ in range(3)]
    k_stack = jnp.stack([case[1] for case in layers])
    v_stack = jnp.stack([case[2] for case in layers])
    scales = {name: jnp.stack([case[4][name] for case in layers])
              for name in (("k_scale", "v_scale") if quant else ())}
    assert pallas_decode_eligible(q, k_stack, block_table=table)
    for layer, (_, k, v, _, own) in enumerate(layers):
        own = {**own, "block_table": table}
        want = pallas_decode_attention(q, k, v, valid, interpret=True,
                                       **own)
        got = pallas_decode_attention(
            q, k_stack, v_stack, valid, interpret=True, block_table=table,
            layer=jnp.int32(layer), **scales)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(xla_decode_attention(
                q, k_stack, v_stack, valid, block_table=table,
                layer=jnp.int32(layer), **scales)),
            np.asarray(xla_decode_attention(q, k, v, valid, **own)))


def test_decode_dispatcher_eligibility():
    """Ineligible shapes (tiny pages, odd head_dim, prefill-length
    windows, KV heads the fold cannot tile) stay on the xla lowering
    instead of erroring, and the seam records which way each went."""
    from fengshen_tpu.ops.pallas import traced_dispatch

    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 1, 8, 64), jnp.float32)  # D=64
    k = jnp.asarray(rng.randn(2, 256, 8, 64), jnp.float32)
    assert not pallas_decode_eligible(q, k, k)
    q2 = jnp.asarray(rng.randn(2, 16, 8, 128), jnp.float32)  # S=16
    k2 = jnp.asarray(rng.randn(2, 256, 8, 128), jnp.float32)
    assert not pallas_decode_eligible(q2, k2, k2)
    q4 = jnp.asarray(rng.randn(2, 1, 4, 128), jnp.float32)  # KVH=2
    k4 = jnp.asarray(rng.randn(2, 256, 2, 128), jnp.float32)
    assert not pallas_decode_eligible(q4, k4, k4)
    decode_attention(q4, k4, k4, jnp.ones((2, 1, 256), bool))
    assert {"op": "decode_attention", "impl": "xla",
            "detail": "q=(2, 1, 4, 128) kv=(2, 256, 2, 128):float32 "
                      "slot (backend cannot run Mosaic)"} \
        in traced_dispatch()
    # eligible shape, impl override pins each path explicitly
    q3, k3, v3, valid, kw = _decode_case("slot", False, 1,
                                         np.random.RandomState(8))
    a = decode_attention(q3, k3, v3, valid, impl="xla", **kw)
    b = decode_attention(q3, k3, v3, valid, impl="pallas",
                         interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
