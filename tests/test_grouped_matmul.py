"""`ops/pallas/grouped_matmul.py`: the routed experts' products, a
prefill window's many rows an expert and a decode tick's row or two.

The Mosaic kernel in interpret mode (the arithmetic; the compile for
the chip is `tests/test_compile_for_v5e.py`'s) against
`jax.lax.ragged_dot` over group layouts that each break a different
part of the visit list, the visit list itself, the three products
through `ops.moe.grouped_swiglu`'s seam forced to the kernel against
the xla lowering (values and gradients), and the seam's choice by
shape.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_routed_experts import JOYAI, _init, _layer

import fengshen_tpu.ops.pallas as kernels
from fengshen_tpu.ops.moe import grouped_swiglu, xla_grouped_swiglu
from fengshen_tpu.ops.pallas import grouped_matmul as gm

TILE = gm.TILE


def _tick(lanes, top_k, experts, held, skew, seed):
    """(rows, rows a held group) of one decode tick: `lanes` tokens pick
    `top_k` distinct experts of `experts` (Gumbel top-k over weights
    `exp(skew z)`), the first `held` of them have tables here."""
    rng = np.random.RandomState(seed)
    keys = skew * rng.randn(experts) + rng.gumbel(size=(lanes, experts))
    picks = np.argsort(-keys, axis=1)[:, :top_k].reshape(-1)
    return lanes * top_k, np.bincount(picks[picks < held],
                                      minlength=held).tolist()


#: name -> (rows of the call, rows a group): each in whole tiles
LAYOUTS = {
    "balanced": (4 * TILE, [TILE] * 4),
    "one_expert_has_every_row": (3 * TILE, [0, 3 * TILE, 0, 0]),
    "empty_at_start_middle_end": (3 * TILE, [0, 0, 150, 0, 0, 234, 0]),
    # tile 1 holds the end of group 0, all of 1 and 2, the start of 3
    "a_tile_straddles_three_groups": (3 * TILE,
                                      [TILE + 20, 30, 40, TILE + 38, 0]),
    # a share's experts not held: the last two tiles and a half
    "rows_past_the_last_group": (4 * TILE, [70, 0, 90, 32]),
    "sizes_sum_to_less_than_a_tile": (2 * TILE, [5, 0, 7]),
    "no_row_held": (2 * TILE, [0, 0, 0]),
    # the three cells' decode ticks, a row or two an expert: 512 rows
    # over 256 groups of which ~40 are empty;
    "joyai_tick": _tick(64, 8, 256, 256, 0.38, 1),
    # 128 rows, ONE tile, over 128 groups of which ~50 are empty;
    "keye_tick": _tick(16, 8, 128, 128, 0.3, 2),
    # half of the 640 rows past the last of the 256 groups held
    "qwen3next_tick": _tick(64, 10, 512, 256, 0.0, 3),
    # groups of ONE row on both sides of a tile's edge, then a gap of
    # empty groups, one more row, and a tile that one group fills
    "one_row_at_a_tiles_edge": (4 * TILE, [TILE - 1, 1, 1, TILE - 2, 0, 0,
                                           1, TILE] + [0] * 8),
}


def _case(layout, dtype, n_tables, width_in=128, width_out=256):
    total, sizes = LAYOUTS[layout]
    rng = np.random.RandomState(len(layout) + 7 * n_tables)
    rows = jnp.asarray(rng.randn(total, width_in), dtype)
    tables = tuple(jnp.asarray(0.1 * rng.randn(len(sizes), width_in,
                                               width_out), dtype)
                   for _ in range(n_tables))
    return rows, tables, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_tables", [1, 2], ids=["product", "swiglu"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_interpret_equals_ragged_dot(layout, n_tables, dtype):
    """One product, and gate and up with the SwiGLU epilogue, in
    interpret mode against `ragged_dot` on the rows the groups hold;
    the rows past the last group are exact zeros whatever the tables
    (ragged_dot's own are not compared: on the chip they are
    undefined)."""
    rows, tables, sizes = _case(layout, jnp.dtype(dtype), n_tables)
    got = jax.jit(functools.partial(gm.grouped_matmul, name="t",
                                    interpret=True))(rows, tables, sizes)
    assert got.shape == (rows.shape[0], 256) and got.dtype == rows.dtype
    want = [jax.lax.ragged_dot(rows, t, sizes,
                               preferred_element_type=jnp.float32)
            for t in tables]
    want = want[0] if n_tables == 1 else jax.nn.silu(want[0]) * want[1]
    held = int(sizes.sum())
    got = np.asarray(got, np.float32)
    assert not got[held:].any()
    tol = 1e-5 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(got[:held], np.asarray(want)[:held],
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_visits_touch_each_held_table_once_and_every_tile(layout):
    """The visit list: every (tile, group) pair that shares a row is
    visited once, in group order, so a touched group's visits are
    consecutive (its table is fetched once) and a group with no row is
    never visited; then one visit a tile wholly past the last group."""
    total, sizes = LAYOUTS[layout]
    tiles = total // TILE
    steps, (offsets, group, tile, slot, nxt) = jax.jit(
        gm._visits, static_argnums=1)(jnp.asarray(sizes, jnp.int32), tiles)
    steps, group, tile = int(steps), np.asarray(group), np.asarray(tile)
    ends = np.cumsum(sizes)
    want = [(t, g) for g, (a, b) in enumerate(zip(ends - sizes, ends))
            for t in range(tiles) if max(a, t * TILE) < min(b, (t + 1) * TILE)]
    made = len(want)
    assert list(zip(tile[:made], group[:made])) == want
    held_tiles = -(-int(ends[-1]) // TILE)
    assert steps == made + tiles - held_tiles <= len(group)
    assert list(tile[made:steps]) == list(range(held_tiles, tiles))
    touched = [g for g, n in enumerate(sizes) if n]
    # a fill visit keeps the last touched table: no fetch
    assert set(group[made:steps]) <= {touched[-1] if touched else 0}
    assert np.asarray(offsets).tolist() == [0] + ends.tolist()
    # the slots go round over the touched groups, each names the next
    assert [int(slot[g]) for g in touched] == [i % gm._SLOTS for i in
                                               range(len(touched))]
    assert [int(nxt[g]) for g in touched] == (touched + [-1])[1:]


@pytest.fixture
def forced(monkeypatch):
    """The seam as on a TPU, the kernel interpreted."""
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "test"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    monkeypatch.setattr(gm, "pallas_grouped_swiglu", functools.partial(
        gm.pallas_grouped_swiglu, interpret=True))


@pytest.mark.parametrize("setting", [
    dict(top_k=8, **JOYAI), dict(scoring="softmax", top_k=3),
    dict(top_k=2, experts_held=(2, 4), **JOYAI)],
    ids=["joyai-all8", "softmax-top3", "held-2-to-5"])
def test_layer_through_the_kernel_equals_the_xla_path(setting, forced,
                                                      monkeypatch):
    """`RoutedExperts` with its seam forced to the kernel against the
    same layer on `ragged_dot`: the output, and the gradients of every
    parameter and of the input (the kernel's `custom_vjp` is the xla
    lowering's backward at the same operands). With a held share half
    the assignments sort past the last group."""
    layer = _layer(hidden_size=128, intermediate_size=128,
                   initializer_range=0.1, **setting)
    x, params = _init(layer, shape=(2, 64, 128))
    first, count = setting.get("experts_held", (0, 8))
    if count != 8:
        params = dict(params, **{k: params[k][:count] for k in (
            "experts_gate", "experts_up", "experts_down")})

    def loss(params, x):
        out = layer.apply({"params": params}, x)
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))
                ).sum(), out

    (_, got), got_grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        params, x)
    took, = kernels.traced_dispatch()
    assert took["op"] == "grouped_matmul" and took["impl"] == "pallas", took
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("cpu", False, None, "test"))
    (_, want), want_grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        params, x)
    assert kernels.traced_dispatch()[-1]["impl"] == "xla"

    def close(got, want, what):
        got, want = np.asarray(got), np.asarray(want)
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max(), what

    assert np.abs(np.asarray(want)).max() > 0.01
    close(got, want, "output")
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) >= 5
    for (path, g), w in zip(flat_got, flat_want):
        # the selection bias moves picks, never a weight: no gradient
        assert np.abs(np.asarray(w)).max() > 0 or "bias" in str(path), path
        close(g, w, path)


def test_products_through_the_kernel_in_bfloat16(forced):
    """`grouped_swiglu` at the serving dtype: bf16 rows and tables, the
    kernel's one rounding of `silu(gate) * up` against the xla
    lowering's three."""
    rng = np.random.RandomState(5)
    tokens, top_k, count, hidden, width = 128, 4, 8, 128, 256
    x = jnp.asarray(rng.randn(tokens, hidden), jnp.bfloat16)
    index = jnp.asarray(np.stack([rng.permutation(count)[:top_k]
                                  for _ in range(tokens)]), jnp.int32)
    weight = jnp.asarray(rng.rand(tokens, top_k), jnp.float32)
    tables = [jnp.asarray(0.1 * rng.randn(*s), jnp.bfloat16) for s in (
        (count, hidden, width), (count, hidden, width),
        (count, width, hidden))]
    got = grouped_swiglu(x, index, weight, *tables)
    assert kernels.traced_dispatch()[-1]["impl"] == "pallas"
    order = jnp.argsort(index.reshape(-1), stable=True)
    sizes = jnp.bincount(index.reshape(-1), length=count).astype(jnp.int32)
    rows = xla_grouped_swiglu(x[order // top_k], *tables, sizes)
    want = (rows.astype(jnp.float32) * weight.reshape(-1)[order][:, None])[
        jnp.argsort(order)].reshape(tokens, top_k, -1).sum(1)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=3e-2)


def _decide(tokens, top_k, count, hidden=2048, width=768,
            dtype=jnp.bfloat16, table_dtype=None):
    sd = jax.ShapeDtypeStruct
    table_dtype = table_dtype or dtype
    # a fresh function: a cached trace would record no decision
    out = jax.eval_shape(
        lambda *a: grouped_swiglu(*a), sd((tokens, hidden), dtype),
        sd((tokens, top_k), jnp.int32), sd((tokens, top_k), jnp.float32),
        sd((count, hidden, width), table_dtype),
        sd((count, hidden, width), table_dtype),
        sd((count, width, hidden), table_dtype))
    assert out.shape == (tokens, hidden) and out.dtype == jnp.float32
    return kernels.traced_dispatch()[-1]


@pytest.mark.parametrize("tokens,top_k,count,width,why", [
    (2048, 8, 128, 768, None),              # Keye's window, 16384 / 128
    (2048, 10, 256, 512, None),             # Qwen3-Next's, 20480 / 256
    (2048, 8, 256, 768, None),              # JoyAI's largest bucket
    (256, 8, 256, 768, None),               # its smallest
    (16, 8, 128, 768, None),                # Keye's tick, 1 row an expert
    (64, 10, 256, 512, None),               # Qwen3-Next's, 2.5 (1.25 held)
    (64, 8, 256, 768, None),                # JoyAI's, 2
    (16, 4, 32, 768, "64 rows % 128"),      # a tick of Trinity's lanes
    (100, 3, 8, 768, "300 rows % 128"),
    (128, 8, 8, 704, "width 704"),
    (256, 8, 8, 8192, "outgrow VMEM"),
], ids=["keye_window", "qwen3next_window", "joyai_2048", "joyai_256",
        "keye_tick", "qwen3next_tick", "joyai_tick", "few_lanes",
        "ragged_rows", "narrow_lanes", "wide_tables"])
def test_seam_follows_the_calls_shape(forced, tokens, top_k, count, width,
                                      why):
    """`grouped_swiglu` chooses its path from the call's shape (static
    at trace time) through `resolve_dispatch`: the prefill windows AND
    the decode ticks of the three routed models take the kernel (at a
    row or two an expert it reads the touched tables 1.2-2.9x faster
    than `ragged_dot`: PERF.md, PR 42), rows that are not whole tiles,
    narrow lanes and tables that outgrow VMEM `ragged_dot` with the
    reason on record, and the choice shows on the
    `fstpu_kernel_dispatch{op,impl}` gauge and the dispatch line."""
    from fengshen_tpu.observability.registry import MetricsRegistry
    took = _decide(tokens, top_k, count, width=width)
    assert took["op"] == "grouped_matmul"
    assert took["impl"] == ("pallas" if why is None else "xla"), took
    assert f"rows=({tokens * top_k}, 2048):bfloat16" in took["detail"]
    assert f"tables=({count}, 2048, {width})" in took["detail"]
    assert why is None or why in took["detail"], took
    events, reg = [], MetricsRegistry()
    table = kernels.log_dispatch(events.append, registry=reg)
    assert table["grouped_matmul"] == "pallas"
    assert took in events[0]["call_sites"]
    gauge = reg.gauge("fstpu_kernel_dispatch", "",
                      labelnames=("op", "impl"))
    assert gauge.labels("grouped_matmul", "pallas").value == 1.0


def test_seam_wants_rows_and_tables_of_one_dtype(forced):
    took = _decide(256, 8, 8, table_dtype=jnp.float32)
    assert took["impl"] == "xla"
    assert "rows bfloat16 against tables float32" in took["detail"]


def test_seam_stays_on_ragged_dot_under_an_expert_mesh(forced):
    """GSPMD cannot partition a Mosaic call: with the tables sharded
    over `expert` the seam takes the xla lowering and says why."""
    from fengshen_tpu.parallel import MeshConfig, make_mesh, set_mesh
    set_mesh(make_mesh(MeshConfig(data=1, fsdp=1, expert=2, sequence=1,
                                  tensor=1), devices=jax.devices()[:2]))
    try:
        took = _decide(2048, 8, 128)
    finally:
        set_mesh(None)
    assert took["impl"] == "xla" and "2-device mesh" in took["detail"]


def test_off_the_tpu_every_call_takes_ragged_dot(monkeypatch):
    monkeypatch.setattr(kernels, "_TRACED", {})
    took = _decide(2048, 8, 128)
    assert took["impl"] == "xla"
    assert "backend cannot run Mosaic" in took["detail"]
