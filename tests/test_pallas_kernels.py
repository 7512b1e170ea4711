"""Kernel layer (fengshen_tpu.ops.pallas): registry/probe mechanics,
XLA-fallback parity for every dispatch seam, and the bench row
contract.

Parity doctrine (docs/kernels.md): every Pallas kernel registers next
to the stock XLA lowering it replaces, the xla lowering is op-for-op
the pre-seam model code (so CPU tier-1 pins bit-identical decode), and
the Mosaic path is checked against it in interpret mode — the same
numerics the TPU kernel runs, executed on the CPU backend.
"""

import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops.pallas import (FORCE_ENV, dispatch_table,
                                     get_kernel, kernel_choice,
                                     log_dispatch, probe)
from fengshen_tpu.ops.pallas.decode_attention import (
    _folded_ineligible_reason, _layer_of_stack, _mla_ineligible_reason,
    decode_attention, folded_decode_attention, mla_decode_attention,
    pallas_decode_attention, pallas_decode_eligible,
    pallas_folded_decode_attention, pallas_mla_decode_attention,
    xla_decode_attention, xla_mla_decode_attention)


@pytest.fixture
def fresh_probe(monkeypatch):
    """Each force-env scenario re-probes; the cache key includes the
    env var so leaving it unset afterwards restores the real answer."""
    monkeypatch.delenv(FORCE_ENV, raising=False)
    yield monkeypatch
    probe(refresh=True)


# -- registry + probe ---------------------------------------------------


def test_probe_cached_and_forceable(fresh_probe):
    info = probe(refresh=True)
    assert info.backend == "cpu"
    assert not info.pallas_tpu
    assert "cpu" in info.reason
    # cached: the second call answers from the dict, same object
    assert probe() is info

    fresh_probe.setenv(FORCE_ENV, "pallas")
    forced = probe()
    assert forced.pallas_tpu and forced.forced == "pallas"
    fresh_probe.setenv(FORCE_ENV, "xla")
    assert not probe().pallas_tpu


def test_dispatch_table_follows_the_probe(fresh_probe):
    table = dispatch_table()
    for op in ("decode_attention", "folded_decode_attention",
               "mla_decode_attention", "fused_ce",
               "flash_attention", "block_sparse_attention",
               "gated_delta_prefill", "grouped_matmul"):
        assert table[op] == "xla"  # CPU backend: stock lowerings

    fresh_probe.setenv(FORCE_ENV, "pallas")
    assert dispatch_table()["decode_attention"] == "pallas"


def test_get_kernel_resolution(fresh_probe):
    assert get_kernel("decode_attention") is xla_decode_attention
    assert get_kernel("decode_attention",
                      "pallas") is pallas_decode_attention
    from fengshen_tpu.ops.gated_attention import folded_decode_walk
    assert get_kernel("folded_decode_attention") is folded_decode_walk
    assert get_kernel("folded_decode_attention",
                      "pallas") is pallas_folded_decode_attention
    with pytest.raises(KeyError):
        get_kernel("nonexistent_op")
    with pytest.raises(KeyError):
        # block-sparse's fallback lives in ops.attention, not here
        get_kernel("block_sparse_attention", "xla")


def test_log_dispatch_event_and_gauge(fresh_probe):
    from fengshen_tpu.observability.registry import MetricsRegistry

    events = []
    reg = MetricsRegistry()
    table = log_dispatch(events.append, registry=reg)
    assert table == dispatch_table()
    (event,) = events
    assert event["event"] == "kernel_dispatch"
    assert event["table"]["decode_attention"] == "xla"
    assert event["backend"] == "cpu" and event["reason"]
    gauge = reg.gauge("fstpu_kernel_dispatch", "",
                      labelnames=("op", "impl"))
    assert gauge.labels("decode_attention", "xla").value == 1.0
    assert gauge.labels("decode_attention", "pallas").value == 0.0


# -- decode attention: the stock-math pin -------------------------------


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


# -- the folded entry: rows that hold a token's few, wide KV heads ------

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
    ((2, 2, 4, 128), (9, 128, 1, 256), "query window 2"),
    ((2, 1, 4, 128), (9, 128, 2, 128), "do not fold"),
    ((2, 1, 4, 128), (9, 128, 1, 320), "do not fold"),
    ((2, 1, 4, 128), (9, 128, 1, 384), "3 kv heads do not divide 4"),
    ((2, 1, 16, 256), (3, 9, 128, 1, 512), None),
    ((2, 1, 4, 128), (9, 256, 1, 128), None),
], ids=["narrow_head", "tiny_block", "window", "unfolded_heads",
        "ragged_width", "heads_not_grouped", "cell_stack", "one_kv_head"])
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
    assert out.shape == (2, 1) + q_shape[2:]    # one query a lane
    took, = kernels.traced_dispatch()
    assert took["op"] == "folded_decode_attention"
    assert took["impl"] == ("pallas" if why is None else "xla")
    assert (why is None) or (why in took["detail"])


# -- the latent entry: one shared row a token, key and value at once ----

#: per lane of a `_mla_case`, the (first, last) valid key of the FIRST
#: query on a table row of four 128-token blocks (None: a released
#: lane, no valid key, its row parked on the null block): one key, a
#: cursor on a block's last key and on the next block's first, a
#: left-padded lane, one whose whole first block is padding, the row's
#: last key
_MLA_LANES = ((0, 0), (0, 127), (0, 128), (5, 129), (130, 300), None,
              (0, 510), (256, 383))


def _mla_case(rng, stacked, dtype, window=1, n_heads=8, rank=128, rope=64,
              width=256):
    """q_latent, q_rope, pool, valid, table, layer for the seam's
    latent entry, and the lanes' rows in order for a plain reference.
    Every lane's four blocks lie scattered in a pool whose block 0 is
    the null block; `stacked` hands the pool as a `[3, ...]` stack read
    at layer 1; query `s` of a `window` sees one key more than query
    `s - 1`."""
    lanes, per, block = len(_MLA_LANES), 4, 128
    rows = rng.randn(lanes, per * block, width).astype(np.float32)
    rows[..., rank + rope:] = 0.
    order = rng.permutation(lanes * per) + 1
    table = order.reshape(lanes, per).astype(np.int32)
    layers = 3 if stacked else 1
    pool = rng.randn(layers, lanes * per + 1, block, 1,
                     width).astype(np.float32)
    pool[layers // 2, order] = rows.reshape(-1, block, 1, width)
    valid = np.zeros((lanes, window, per * block), bool)
    for b, span in enumerate(_MLA_LANES):
        if span is None:
            table[b] = 0
            continue
        for s in range(window):
            valid[b, s, span[0]:span[1] + 1 + s] = True
    q_latent = jnp.asarray(rng.randn(lanes, window, n_heads, rank), dtype)
    q_rope = jnp.asarray(rng.randn(lanes, window, n_heads, rope), dtype)
    return (q_latent, q_rope,
            jnp.asarray(pool if stacked else pool[0], dtype),
            jnp.asarray(valid), jnp.asarray(table),
            jnp.int32(1) if stacked else None, rows)


def _latent_softmax(q_latent, q_rope, rows, valid, scale):
    """Plain absorbed latent attention over each lane's rows, float32:
    every head scores `[c_kv | k_rope]` and weighs `c_kv`."""
    rank = q_latent.shape[-1]
    q = np.concatenate([np.asarray(q_latent, np.float32),
                        np.asarray(q_rope, np.float32)], -1)
    s = np.einsum("bshd,btd->bsht", q, rows[..., :q.shape[-1]]) * scale
    s = np.where(valid[:, :, None], s, -np.inf)
    with np.errstate(invalid="ignore"):     # a released lane: no key
        p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bsht,btc->bshc", p, rows[..., :rank])


def _mla_kernel(q_latent, q_rope, pool, valid, table, layer, per):
    """The kernel in interpret mode: through the seam, or (`per`) with
    that many blocks a step."""
    if per is None:
        return mla_decode_attention(q_latent, q_rope, pool, valid,
                                    scale=0.1, block_table=table,
                                    layer=layer, impl="pallas",
                                    interpret=True)
    return pallas_mla_decode_attention(q_latent, q_rope, pool, valid,
                                       scale=0.1, block_table=table,
                                       layer=layer, blocks_per_step=per,
                                       interpret=True)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("stacked", [False, True], ids=["pool", "stack"])
@pytest.mark.parametrize("per, window", [
    (None, 1), (1, 1), (2, 1), (3, 1), (None, 3), (3, 3)],
    ids=["row", "1", "2", "3", "row_window", "3_window"])
def test_mla_decode_kernel_interpret_parity(per, window, stacked, dtype):
    """The latent kernel (interpret mode) against
    `xla_mla_decode_attention` and against plain latent attention:
    ragged cursors in one call (`_MLA_LANES`), a cursor on a block's
    edge, left-padded lanes (holes at the front of `valid`, a whole
    block of them), a released lane on the null block, the pool as it
    is and as a stack read in place through a TRACED `layer`, blocks
    scattered; the whole 4-block row a step (the seam's 8 blocks, cut
    to the row), a block a step, two, and 3, which leaves a lane's last
    step one live block of three; one query a lane and a verify window
    of three. In float32 the two differ by the online softmax's
    partition; in bfloat16 they round the same operands the same
    way."""
    rng = np.random.RandomState(430 + 2 * stacked + (dtype == "float32"))
    q_latent, q_rope, pool, valid, table, layer, rows = _mla_case(
        rng, stacked, jnp.dtype(dtype), window)
    assert _mla_ineligible_reason(q_latent, pool, table) is None
    got = jax.jit(_mla_kernel, static_argnums=6)(
        q_latent, q_rope, pool, valid, table, layer, per)
    want = xla_mla_decode_attention(q_latent, q_rope, pool, valid,
                                    scale=0.1, block_table=table,
                                    layer=layer)
    assert got.shape == q_latent.shape and got.dtype == q_latent.dtype
    tol = 2e-5 if dtype == "float32" else 2e-2
    held = np.array([span is not None for span in _MLA_LANES])
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[held], np.asarray(want, np.float32)[held],
                               rtol=tol, atol=tol)
    if dtype == "bfloat16":
        rows = np.asarray(jnp.asarray(rows, jnp.bfloat16), np.float32)
    plain = _latent_softmax(q_latent, q_rope, rows, np.asarray(valid), 0.1)
    np.testing.assert_allclose(got[held], plain[held], rtol=tol, atol=tol)


@pytest.mark.parametrize("per", [None, 1, 3], ids=["row", "1", "3"])
def test_mla_decode_kernel_never_reads_past_a_lanes_cursor(per):
    """That the walk ends at each lane's OWN last block, inside a step
    too: with NaN in the null block and in every block past a lane's
    cursor, the lanes that hold a request return what they return over
    a clean pool, bit for bit (the xla lowering gathers every entry of
    every row and multiplies a zero probability by that NaN)."""
    rng = np.random.RandomState(440)
    q_latent, q_rope, pool, valid, table, _, _ = _mla_case(
        rng, False, jnp.float32)
    clean = _mla_kernel(q_latent, q_rope, pool, valid, table, None, per)
    held = np.array([span is not None for span in _MLA_LANES])
    reached = {int(block) for row, span in zip(np.asarray(table), _MLA_LANES)
               if span is not None for block in row[:span[1] // 128 + 1]}
    dead = [i for i in range(pool.shape[0]) if i not in reached]
    assert 0 in dead and len(dead) > len(_MLA_LANES)
    pool = pool.at[jnp.asarray(dead)].set(jnp.nan)
    out = np.asarray(_mla_kernel(q_latent, q_rope, pool, valid, table,
                                 None, per))
    assert np.isfinite(out[held]).all()
    np.testing.assert_array_equal(out[held], np.asarray(clean)[held])
    lost = np.asarray(mla_decode_attention(
        q_latent, q_rope, pool, valid, scale=0.1, block_table=table,
        impl="xla"))
    assert np.isnan(lost[held]).any()


@pytest.mark.parametrize("q_shape, kv_shape, paged, why", [
    ((2, 1, 32, 512), (2, 512, 1, 640), False, "slot cache"),
    ((2, 1, 32, 512), (3, 2, 512, 1, 640), False, "slot cache"),
    ((2, 1, 32, 512), (9, 128, 1, 576), True, "row width 576 % 128"),
    ((2, 9, 32, 512), (9, 128, 1, 640), True, "query window 9 > 8"),
    ((2, 1, 4, 32), (9, 128, 1, 128), True, "rank 32 % 128"),
    ((2, 1, 32, 512), (9, 16, 1, 640), True, "block_size 16 % 128"),
    ((2, 1, 4, 128), (9, 128, 1, 256), True, None),
    ((64, 1, 32, 512), (5, 1537, 128, 1, 640), True, None),
    ((2, 8, 32, 512), (9, 128, 1, 640), True, None),
], ids=["slot", "slot_stack", "published_row", "long_window", "tiny_rank",
        "tiny_block", "four_heads", "cell_stack", "verify_window"])
def test_mla_dispatch_follows_the_caches_shape(fresh_probe, monkeypatch,
                                               q_shape, kv_shape, paged,
                                               why):
    """The latent entry chooses its path from the cache's shape through
    `resolve_dispatch`: on a backend that runs Mosaic the cell's paged
    stack of 640-wide rows takes the kernel (a verify window up to 8
    too); a slot cache, the published 576-wide row, a window of 9 and
    the tiny shapes of the CPU tests take the xla lowering, each with
    its reason on record by name."""
    import fengshen_tpu.ops.pallas as kernels
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "test"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    lanes, window = q_shape[:2]
    stacked = len(kv_shape) == 5
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    q_rope = jax.ShapeDtypeStruct(q_shape[:3] + (64,), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16)
    table = jax.ShapeDtypeStruct((lanes, 4), jnp.int32) if paged else None
    lane_len = 4 * kv_shape[-3] if paged else kv_shape[-3]
    reason = _mla_ineligible_reason(q, kv, table)
    assert (reason is None) if why is None else (why in reason), reason
    out = jax.eval_shape(
        lambda q, q_rope, kv, valid, table: mla_decode_attention(
            q, q_rope, kv, valid, scale=1.0, block_table=table,
            layer=jnp.int32(1) if stacked else None),
        q, q_rope, kv,
        jax.ShapeDtypeStruct((lanes, window, lane_len), jnp.bool_), table)
    assert out.shape == q_shape and out.dtype == jnp.bfloat16
    took, = kernels.traced_dispatch()
    assert took["op"] == "mla_decode_attention"
    assert took["impl"] == ("pallas" if why is None else "xla")
    assert ("paged" if paged else "slot") in took["detail"]
    assert (why is None) or (why in took["detail"])


def test_the_latent_kernel_refuses_a_slot_cache_by_name():
    q = jnp.zeros((2, 1, 8, 128))
    with pytest.raises(ValueError, match="walks a block table"):
        mla_decode_attention(q, q[..., :64], jnp.zeros((2, 128, 1, 256)),
                             jnp.ones((2, 1, 128), bool), scale=1.0,
                             impl="pallas", interpret=True)


# -- the chunked gated delta rule ---------------------------------------


def _delta_window(seq, *, rep=2, dtype="float32", pad="none", seed=0,
                  batch=2, key_heads=1, dim=128, zero_state=False):
    """q, k, v, g, beta, state, mask of one window for the delta rule's
    seam: `key_heads` key heads of `dim` under `rep` value heads each,
    l2-normalised q and k as the model hands them, `pad` tokens masked
    off on the left, the right or nowhere (with NaN-free junk under the
    mask: padding computes on real embeddings)."""
    from fengshen_tpu.ops.gated_delta import l2norm
    heads = key_heads * rep
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (batch, seq, key_heads, dim)))
    k = l2norm(jax.random.normal(ks[1], (batch, seq, key_heads, dim)))
    v = jax.random.normal(ks[2], (batch, seq, heads, dim))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (batch, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    state = jnp.zeros((batch, heads, dim, dim)) if zero_state else \
        jax.random.normal(ks[5], (batch, heads, dim, dim))
    n_pad = seq // 3
    real = {"none": slice(0, seq), "right": slice(0, seq - n_pad),
            "left": slice(n_pad, seq)}[pad]
    mask = None if pad == "none" else \
        jnp.zeros((batch, seq), bool).at[:, real].set(True)
    q, k, v = (x.astype(jnp.dtype(dtype)) for x in (q, k, v))
    return (q, k, v, g, beta, state, mask), real


@jax.jit
def _delta_kernel(*case):
    """The chunk kernel in interpret mode (jitted: windows of one shape
    share a compilation)."""
    from fengshen_tpu.ops.pallas.gated_delta import (
        pallas_gated_delta_prefill)
    return pallas_gated_delta_prefill(*case, interpret=True)


@pytest.fixture
def interpreted():
    """A test that compiles an interpreted chunk kernel drops jax's
    executables before it starts and when it ends. Each is one CPU
    executable of some 2,600 memory mappings (a process may hold
    65,530), and in a run of this whole file without this the first of
    them ended the process in `Aborted` inside the CPU compiler, twice
    of two runs; alone, or after either half of the file, they pass."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("rep", [1, 2])
@pytest.mark.parametrize("seq, pad, zero_state, dtype", [
    (128, "none", False, "float32"), (128, "none", False, "bfloat16"),
    (384, "right", False, "float32"), (384, "left", True, "bfloat16"),
    (300, "none", True, "float32"), (300, "left", False, "float32"),
    (150, "right", False, "float32"), (150, "right", False, "bfloat16"),
], ids=["one_chunk", "one_chunk_bf16", "three_right", "three_left_fresh_bf16",
        "ragged_fresh", "ragged_left", "ragged_right", "ragged_right_bf16"])
def test_delta_kernel_interpret_equals_recurrence(seq, pad, zero_state, rep,
                                                  dtype, interpreted):
    """The chunk kernel (interpret mode) against the one-token
    recurrence `_recurrence` of tests/test_qwen3_next.py and against
    the `jax.numpy` chunked form, its xla twin: one chunk, several, a
    window that is not a multiple of the chunk (the wrapper pads it);
    padding on the left, the right or nowhere; an incoming state and a
    fresh one; value heads 1x and 2x the key heads (a key head's rows
    read for both, not repeated); float32 and bfloat16 q, k, v."""
    from tests.test_qwen3_next import _recurrence

    from fengshen_tpu.ops.gated_delta import xla_gated_delta_prefill
    case, real = _delta_window(seq, rep=rep, dtype=dtype, pad=pad, batch=1,
                               zero_state=zero_state, seed=seq + 7 * rep)
    q, k, v, g, beta, state, mask = case
    got, got_state = _delta_kernel(*case)
    assert got.shape == v.shape and got.dtype == v.dtype
    assert got_state.shape == state.shape and got_state.dtype == jnp.float32
    twin, twin_state = xla_gated_delta_prefill(*case)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:, real],
        np.asarray(twin, np.float32)[:, real], rtol=tol, atol=tol)
    np.testing.assert_allclose(got_state, twin_state, rtol=2e-5, atol=2e-5)
    wide = [jnp.repeat(x[:, real].astype(jnp.float32), rep, axis=2)
            for x in (q, k)]
    want, want_state = _recurrence(
        *wide, v[:, real].astype(jnp.float32), g[:, real], beta[:, real],
        state)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:, real], want,
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(got_state, want_state, rtol=2e-5, atol=2e-5)


def test_delta_kernel_carries_the_state_over_the_windows_tiles(interpreted):
    """A window of more than one tile of the grid's last axis (1,152
    tokens: 9 chunks, 3 a tile at two value heads a key head) at the
    cell's head layout: the state the output block carries from tile to
    tile is the `jax.numpy` form's."""
    from fengshen_tpu.ops.gated_delta import xla_gated_delta_prefill
    from fengshen_tpu.ops.pallas.gated_delta import CHUNK, _chunks_a_tile
    assert _chunks_a_tile(1152 // CHUNK, 2) == 3
    case, real = _delta_window(1152, dtype="bfloat16", pad="right",
                               batch=1, seed=11)
    got, got_state = _delta_kernel(*case)
    twin, twin_state = xla_gated_delta_prefill(*case)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:, real],
        np.asarray(twin, np.float32)[:, real], rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(got_state, twin_state, rtol=2e-5, atol=2e-5)


def test_delta_kernel_padded_window_returns_the_state_bit_for_bit(
        interpreted):
    """A window with no valid token (NaN under the mask, as a kernel
    must bear it in k and v) hands the state back as it came."""
    case, _ = _delta_window(256, pad="right")
    q, k, v, g, beta, state, _ = case
    _, same = _delta_kernel(q, jnp.full_like(k, jnp.nan),
                            jnp.full_like(v, jnp.nan), g, beta, state,
                            jnp.zeros((2, 256), bool))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(state))


def test_delta_kernel_two_windows_equal_one(interpreted):
    """Two windows in a row (the second onto the first's state, its
    tail padded) equal one window of both."""
    from fengshen_tpu.ops.gated_delta import xla_gated_delta_prefill
    case, _ = _delta_window(512, seed=3, batch=1)
    q, k, v, g, beta, state, _ = case
    cut, end = 256, 400
    every = jnp.ones((1, cut), bool)
    first, mid = _delta_kernel(q[:, :cut], k[:, :cut], v[:, :cut],
                               g[:, :cut], beta[:, :cut], state, every)
    second, last = _delta_kernel(
        q[:, cut:], k[:, cut:], v[:, cut:], g[:, cut:], beta[:, cut:], mid,
        every.at[:, end - cut:].set(False))
    whole, want = xla_gated_delta_prefill(
        q[:, :end], k[:, :end], v[:, :end], g[:, :end], beta[:, :end], state)
    np.testing.assert_allclose(first, whole[:, :cut], atol=2e-5)
    np.testing.assert_allclose(second[:, :end - cut], whole[:, cut:],
                               atol=2e-5)
    np.testing.assert_allclose(last, want, atol=2e-5)
    assert np.abs(np.asarray(mid - want)).max() > 1e-3


def _chunk_inverse_interpreted(kk, a, g_row, beta_row, upto):
    """`gated_delta._chunk_inverse` as the kernel's body runs it (its
    rolls are the chip's), in interpret mode."""
    from jax.experimental import pallas as pl

    from fengshen_tpu.ops.pallas.gated_delta import _chunk_inverse

    def body(kk_ref, a_ref, g_ref, beta_ref, o_ref):
        o_ref[...] = _chunk_inverse(kk_ref[...], a_ref[...], g_ref[...],
                                    beta_ref[...], upto)
    return pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        interpret=True)(kk, a, g_row, beta_row)


@pytest.mark.parametrize("spread", [0.0, 0.05, 1.0, 100.0],
                         ids=["repeated", "near", "cosine_half", "free"])
@pytest.mark.parametrize("upto", [16, 64, 128])
def test_chunk_inverse_equals_the_triangular_solve(upto, spread,
                                                   interpreted):
    """`(I - A)^-1` as the kernel builds it (16-row blocks along their
    diagonals, then merged by products up to blocks of `upto` rows)
    against `solve_triangular` on the `[c, c]` system of the worst
    conditioning the model can produce: l2-normalised keys that repeat
    or nearly do (`|A_ij|` near its bound 1: a prompt of one repeated
    token), `beta` near 1, decays near 0 (`exp(G_i - G_j)` near 1), at
    the tolerance `test_delta_chunks_equal_recurrence` uses; and the
    same down to keys that are independent. (The inverse by squarings
    of `A`, `(I + A)(I + A^2)(I + A^4) ...`, reads 1e10 off on the
    first two of these and 7e2 on the third.)"""
    from jax.scipy.linalg import solve_triangular
    from tests.test_qwen3_next import ATOL

    from fengshen_tpu.ops.gated_delta import l2norm
    from fengshen_tpu.ops.pallas.gated_delta import CHUNK as c
    rng = np.random.RandomState(350 + upto)
    rows, cols = np.indices((c, c))
    strict = rows > cols
    eye = jnp.eye(c, dtype=jnp.float32)
    k = l2norm(jnp.asarray(rng.randn(1, 128) + spread * rng.randn(c, 128),
                           jnp.float32))
    beta = jnp.asarray(1.0 - 1e-3 * rng.rand(c), jnp.float32)
    G = jnp.cumsum(jnp.asarray(-1e-3 * rng.rand(c), jnp.float32))
    kk = jnp.matmul(k, k.T, precision="highest")
    a = jnp.where(strict, -(beta[:, None] * kk) *
                  jnp.exp(jnp.where(strict, G[:, None] - G[None, :], 0.0)),
                  0.0)
    assert float(jnp.abs(a).max()) <= 1.0 + 1e-6
    if spread <= 0.05:
        assert float(jnp.abs(a[strict]).min()) > 0.9
    blocks = jnp.where(rows // upto == cols // upto, a, 0.0)
    want = solve_triangular(eye - blocks, eye, lower=True,
                            unit_diagonal=True)
    got = _chunk_inverse_interpreted(kk, a, G[None], beta[None], upto)
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("q_shape, v_shape, why", [
    ((1, 256, 2, 16), (1, 256, 4, 16), "Dk 16 % 128"),
    ((1, 256, 2, 128), (1, 256, 4, 64), "Dv 64 % 128"),
    ((1, 100, 2, 128), (1, 100, 4, 128), "window 100 shorter than a chunk"),
    ((1, 512, 1, 1024), (1, 512, 8, 1024), "outgrow VMEM"),
    ((1, 2048, 16, 128), (1, 2048, 32, 128), None),
    ((2, 200, 4, 128), (2, 200, 4, 256), None),
], ids=["narrow_key", "narrow_value", "short_window", "wide_group", "cell",
        "ragged_one_to_one"])
def test_delta_dispatch_follows_the_windows_shape(fresh_probe, monkeypatch,
                                                  q_shape, v_shape, why):
    """`gated_delta_prefill` chooses its path from the window's shape
    through `resolve_dispatch`: on a backend that runs Mosaic an
    eligible window takes the chunk kernel, any other the `jax.numpy`
    form with the reason on record (the tiny CPU models' 16-wide heads
    among those), and the choice shows on the
    `fstpu_kernel_dispatch{op,impl}` gauge and the dispatch line."""
    import fengshen_tpu.ops.pallas as kernels
    from fengshen_tpu.observability.registry import MetricsRegistry
    from fengshen_tpu.ops.gated_delta import gated_delta_prefill
    from fengshen_tpu.ops.pallas.gated_delta import _ineligible_reason
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "test"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    q = jax.ShapeDtypeStruct(q_shape, jnp.float32)
    v = jax.ShapeDtypeStruct(v_shape, jnp.bfloat16)
    reason = _ineligible_reason(q, v)
    assert (reason is None) if why is None else (why in reason), reason
    batch, seq, heads, dv = v_shape
    per_token = jax.ShapeDtypeStruct((batch, seq, heads), jnp.float32)
    out, state = jax.eval_shape(
        gated_delta_prefill, q, q, v, per_token, per_token,
        jax.ShapeDtypeStruct((batch, heads, q_shape[-1], dv), jnp.float32))
    assert out.shape == v_shape and out.dtype == jnp.bfloat16
    assert state.shape == (batch, heads, q_shape[-1], dv)
    took, = kernels.traced_dispatch()
    assert took["op"] == "gated_delta_prefill"
    assert took["impl"] == ("pallas" if why is None else "xla")
    assert (why is None) or (why in took["detail"])
    events = []
    reg = MetricsRegistry()
    table = log_dispatch(events.append, registry=reg)
    assert table["gated_delta_prefill"] == "pallas"
    assert took in events[0]["call_sites"]
    gauge = reg.gauge("fstpu_kernel_dispatch", "",
                      labelnames=("op", "impl"))
    assert gauge.labels("gated_delta_prefill", "pallas").value == 1.0
    assert gauge.labels("gated_delta_prefill", "xla").value == 0.0


def test_delta_seam_stays_on_xla_under_a_mesh(mesh8, fresh_probe,
                                              monkeypatch):
    """GSPMD cannot partition a Mosaic call: under a multi-device mesh
    the seam takes the `jax.numpy` form and says why."""
    import fengshen_tpu.ops.pallas as kernels
    from fengshen_tpu.ops.gated_delta import gated_delta_prefill
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "test"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    q = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.float32)
    per_token = jax.ShapeDtypeStruct((1, 256, 2), jnp.float32)
    jax.eval_shape(gated_delta_prefill, q, q, q, per_token, per_token,
                   jax.ShapeDtypeStruct((1, 2, 128, 128), jnp.float32))
    took, = kernels.traced_dispatch()
    assert took["impl"] == "xla" and "8-device mesh" in took["detail"]


# -- orphan adoption: flash + block-sparse fallback parity --------------


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


# -- fused CE -----------------------------------------------------------


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


# -- bench rows + benchdiff identity ------------------------------------


def test_kernel_bench_rows_smoke(monkeypatch):
    """The decode + fused-CE rungs run in-process on CPU and emit
    BENCH-schema rows carrying the kernel dispatch decision."""
    from fengshen_tpu.ops.pallas.bench import (bench_fused_ce,
                                               bench_paged_decode)

    monkeypatch.setenv("KERNEL_BENCH_ITERS", "2")
    monkeypatch.setenv("KERNEL_BENCH_BATCH", "2")
    monkeypatch.setenv("KERNEL_BENCH_SEQ", "64")
    monkeypatch.setenv("KERNEL_BENCH_HIDDEN", "64")
    monkeypatch.setenv("KERNEL_BENCH_VOCAB", "256")
    for row in (bench_paged_decode(), bench_fused_ce()):
        for key in ("metric", "value", "unit", "vs_baseline", "kernel",
                    "backend"):
            assert key in row, (row["metric"], key)
        assert row["kernel"] == "xla"  # CPU process
        assert row["value"] > 0


def test_benchdiff_kernel_rows_incomparable():
    """A Mosaic round and a stock-lowering round measure different
    programs: benchdiff must diff them as incomparable, never as a
    regression (same contract as offload placement / fleet replicas)."""
    from fengshen_tpu.observability.benchdiff import diff_rounds

    rounds = [
        (1, "BENCH_r01.json", {"rc": 0, "parsed": [
            {"metric": "kernel_paged_decode_tokens_per_sec",
             "value": 100.0, "unit": "tokens/s", "vs_baseline": 1.0,
             "kernel": "xla"}]}),
        (2, "BENCH_r02.json", {"rc": 0, "parsed": [
            {"metric": "kernel_paged_decode_tokens_per_sec",
             "value": 5000.0, "unit": "tokens/s", "vs_baseline": 3.0,
             "kernel": "pallas"}]}),
        (3, "BENCH_r03.json", {"rc": 0, "parsed": [
            {"metric": "kernel_paged_decode_tokens_per_sec",
             "value": 4000.0, "unit": "tokens/s", "vs_baseline": 2.4,
             "kernel": "pallas"}]}),
    ]
    report = diff_rounds(rounds)
    statuses = {(c["round"], c["status"])
                for c in report["comparisons"]}
    assert (2, "incomparable") in statuses  # xla -> pallas: new program
    assert (3, "regression") in statuses    # pallas -> pallas: honest
    assert report["verdict"] == "REGRESSED"
