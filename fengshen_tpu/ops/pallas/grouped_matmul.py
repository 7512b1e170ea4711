"""The routed experts' products as a Mosaic grouped matmul that reads
each touched expert's table once, whatever the rows an expert: a
prefill window's hundred and a decode tick's one or two.

`ops/moe.py:grouped_swiglu` sorts a call's `tokens x top_k` assignments
by expert and has the group sizes; its xla lowering
(:func:`fengshen_tpu.ops.moe.xla_grouped_swiglu`, three
`jax.lax.ragged_dot`) is this kernel's twin and its backward. XLA:TPU's
ragged dot runs a prefill window's products at a third to a fifth of
the rate at which the touched tables can be read (PERF.md, PR 38) and a
tick's at 31-73 % of it where this kernel stands at 89-92 % (PERF.md,
PR 42): its cost is the touched tables' bytes at any row count.
Here, for one product `out[r] = rows[r] @ tables[group of r]`:

- the grid is a list of VISITS, one a (row tile, group) pair that
  share rows, in group order, built from `sizes` outside the kernel
  (:func:`_visits`) and scalar-prefetched. A tile of `TILE` rows that
  straddles groups is visited once a group under a row mask, so a call
  makes at most `tiles + groups - 1` visits; a group with no row is
  never visited. The tiles past the last group (the rows of experts a
  share does not hold) get one visit each that writes zeros, so every
  row of the output is defined and the grid is exactly as long as the
  call's work (a dynamic grid bound);
- a visit's weights are the WHOLE `[in, out]` table of its group, in
  one of `_SLOTS` VMEM slots that go round by the group's rank among
  the touched. The tables stay in HBM (`pl.ANY`); the first visit of a
  group waits for its table and starts the copy of the touched group
  TWO after it into the slot of the group before, so two copies are in
  flight: a visit is as long as its table's copy (7.7 us for 6.3 MB
  against ~4 us of MXU, at 128 rows as at 2), and with one copy in
  flight each copy's issue showed (6-9 % a call). Every touched table
  crosses HBM once a call;
- the row and output tiles ride the ordinary block pipeline: a tile
  visited by consecutive groups is fetched once and written back once;
- float32 accumulation, one rounding to the output's dtype.

With two tables a visit (`gate` and `up`) the rows are read once and
the epilogue is `silu(gate) * up` on the float32 accumulators, rounded
once: the `[rows, width]` products never reach HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fengshen_tpu.ops.moe import EXPERTS_SCOPE, xla_grouped_swiglu

#: rows a visit: an MXU pass's worth. A smaller tile makes more visits
#: that each push a whole table through the MXU, a larger one streams
#: more rows a straddling visit throws away (PERF.md, PR 38). With a row
#: or two an expert a visit is its table's copy and tiles of 8 to 128
#: rows time alike (PERF.md, PR 42)
TILE = 128

#: VMEM slots a table: the visited group's and the next two in flight
#: (two slots: 6-9 % slower in windows and ticks alike; four: no faster)
_SLOTS = 3

#: scoped-VMEM ceiling asked of Mosaic (the default is 16 MiB; a v5e
#: core has 128 MiB), and what of it the slots of a visit's tables may
#: take (Keye's gate and up: 18.9 MB), beside the row and output tiles
_VMEM_LIMIT_BYTES = 64 * 2 ** 20
_TABLE_BYTES = 32 * 2 ** 20


def _ineligible_reason(rows, w_gate) -> Optional[str]:
    """Why the seam routes this call to `ragged_dot`, or None when the
    kernel can take it. `rows`: the `[assignments, hidden]` sorted rows
    (shape and dtype only); `w_gate`: `[count, hidden, width]`."""
    from fengshen_tpu.parallel.mesh import get_mesh
    assignments, hidden = rows.shape
    count, _, width = w_gate.shape
    mesh = get_mesh()
    if mesh is not None and mesh.size > 1:
        return f"{mesh.size}-device mesh: GSPMD cannot partition a " \
               "Mosaic call"
    if assignments % TILE:
        return f"{assignments} rows % {TILE} != 0"
    if hidden % 128 or width % 128:
        return f"hidden {hidden} or width {width} % 128 != 0"
    if rows.dtype != w_gate.dtype or \
            rows.dtype not in (jnp.bfloat16, jnp.float32):
        return f"rows {rows.dtype.name} against tables " \
               f"{w_gate.dtype.name}"
    tables = _SLOTS * 2 * hidden * width * w_gate.dtype.itemsize
    if tables > _TABLE_BYTES:
        return f"{_SLOTS} slots of gate and up ({tables} B) outgrow VMEM"
    return None


def _visits(sizes, tiles: int):
    """The visit list of a call from its group `sizes` (`[count]`
    int32) over `tiles` row tiles. Returns (`steps`, the scalar
    operands): `steps` visits are made, those of the touched groups in
    group order and then one a tile that lies wholly past the last
    group; `offsets` `[count + 1]` (group g's rows are `offsets[g] ...
    offsets[g + 1]`), `group` and `tile` `[tiles + count - 1]` a visit,
    and a group `slot` (its rank among the touched groups modulo
    `_SLOTS`) and `nxt` (the next touched group, -1 after the last)
    `[count]`."""
    count = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    touched = sizes > 0
    ids = jnp.arange(count, dtype=jnp.int32)
    first_tile = starts // TILE
    n = jnp.where(touched, (ends - 1) // TILE - first_tile + 1, 0)
    visit_end = jnp.cumsum(n)
    made = visit_end[-1]
    held_tiles = (ends[-1] + TILE - 1) // TILE
    v = jnp.arange(tiles + count - 1, dtype=jnp.int32)
    fill = v >= made
    # a fill visit keeps the last touched table: nothing is fetched
    group = jnp.where(fill, jnp.max(jnp.where(touched, ids, 0)),
                      jnp.searchsorted(visit_end, v, side="right")
                      ).astype(jnp.int32)
    tile = jnp.where(fill, held_tiles + v - made,
                     first_tile[group] + v - (visit_end - n)[group])
    # the entries past `steps` are never visited
    tile = jnp.minimum(tile, tiles - 1).astype(jnp.int32)
    after = jax.lax.cummin(jnp.where(touched, ids, count), reverse=True)
    nxt = jnp.concatenate([after[1:], jnp.full((1,), count, jnp.int32)])
    nxt = jnp.where(nxt == count, -1, nxt).astype(jnp.int32)
    slot = ((jnp.cumsum(touched) - 1) % _SLOTS).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               ends.astype(jnp.int32)])
    steps = (made + tiles - held_tiles).astype(jnp.int32)
    return steps, (offsets, group, tile, slot, nxt)


def _visit_kernel(offsets, group, tile, slot, nxt, rows_ref, *refs,
                  n_tables: int):
    """One visit: the rows of `tile[v]` that belong to `group[v]`
    through that group's `n_tables` tables (1: the product; 2:
    `silu(a) * b`), written under the row mask."""
    tables = refs[:n_tables]
    out_ref, bufs, sems = refs[n_tables], refs[n_tables + 1:-1], refs[-1]
    v = pl.program_id(0)
    g, before = group[v], jnp.maximum(v - 1, 0)
    s = slot[g]

    def copies(which, into):
        return [pltpu.make_async_copy(t.at[which], b.at[into],
                                      sems.at[i, into])
                for i, (t, b) in enumerate(zip(tables, bufs))]

    def start(hops):
        """Start the copy of the touched group `hops` after `g`, if
        there is one, into its slot."""
        which = g
        for _ in range(hops):
            which = jnp.where(which >= 0, nxt[jnp.maximum(which, 0)], -1)

        @pl.when(which >= 0)
        def _():
            for c in copies(which, (s + hops) % _SLOTS):
                c.start()

    @pl.when(v == 0)
    def _():
        for hops in range(_SLOTS - 1):
            start(hops)

    @pl.when((v == 0) | (g != group[before]))
    def _():
        for c in copies(g, s):
            c.wait()
        # into the slot of the group before, whose visits are over
        start(_SLOTS - 1)

    @pl.when((v == 0) | (tile[v] != tile[before]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    low = tile[v] * TILE
    start, end = offsets[g], offsets[g + 1]

    @pl.when(jnp.minimum(end, low + TILE) > jnp.maximum(start, low))
    def _():
        x = rows_ref[...]
        acc = [jnp.dot(x, b[s], preferred_element_type=jnp.float32)
               for b in bufs]
        res = acc[0] if n_tables == 1 else jax.nn.silu(acc[0]) * acc[1]
        row = low + jax.lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)
        out_ref[...] = jnp.where((row >= start) & (row < end),
                                 res.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def grouped_matmul(rows, tables, sizes, *, name: str,
                   interpret: bool = False):
    """`rows[r] @ t[g]` for the rows of each group g, `sizes[g]` of them
    in group order, for the one or two `[count, in, out]` arrays in
    `tables`; with two, `silu(first) * second`. `[rows, out]` in the
    rows' dtype; rows past the last group give zeros. `rows.shape[0]`
    is whole tiles of `TILE`. Jitted, so that the layers of an unrolled
    model share ONE lowering of the kernel a program: a Mosaic call's
    lowering is ~0.3 s of Python, paid at every start, cache or not."""
    total, width_in = rows.shape
    count, _, width_out = tables[0].shape
    tiles = total // TILE
    steps, scalars = _visits(sizes.astype(jnp.int32), tiles)
    itemsize = rows.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_visit_kernel, n_tables=len(tables)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(steps,),
            in_specs=[pl.BlockSpec((TILE, width_in),
                                   lambda v, o, g, tile, *_: (tile[v], 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(tables),
            out_specs=pl.BlockSpec((TILE, width_out),
                                   lambda v, o, g, tile, *_: (tile[v], 0)),
            scratch_shapes=[pltpu.VMEM((_SLOTS, width_in, width_out), t.dtype)
                            for t in tables]
            + [pltpu.SemaphoreType.DMA((len(tables), _SLOTS))]),
        out_shape=jax.ShapeDtypeStruct((total, width_out), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * total * width_in * width_out * len(tables),
            transcendentals=total * width_out * (len(tables) - 1),
            bytes_accessed=itemsize * (
                total * (width_in + width_out) +
                len(tables) * count * width_in * width_out)),
        interpret=interpret, name=name,
    )(*scalars, rows, *tables)


def _products(rows, w_gate, w_up, w_down, sizes, interpret):
    with jax.named_scope(EXPERTS_SCOPE):
        h = grouped_matmul(rows, (w_gate, w_up), sizes, interpret=interpret,
                           name=EXPERTS_SCOPE + "_gate_up")
        return grouped_matmul(h, (w_down,), sizes, interpret=interpret,
                              name=EXPERTS_SCOPE + "_down")


_swiglu = jax.custom_vjp(_products, nondiff_argnums=(5,))


def _swiglu_fwd(rows, w_gate, w_up, w_down, sizes, interpret):
    return _products(rows, w_gate, w_up, w_down, sizes, interpret), \
        (rows, w_gate, w_up, w_down, sizes)


def _swiglu_bwd(interpret, res, ct):
    # no cell trains experts: the backward is the xla lowering's
    *operands, sizes = res
    _, vjp = jax.vjp(lambda *a: xla_grouped_swiglu(*a, sizes), *operands)
    return (*vjp(ct), None)


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def pallas_grouped_swiglu(rows, w_gate, w_up, w_down, sizes, *,
                          interpret: bool = False):
    """`ops.moe.xla_grouped_swiglu` as two Mosaic calls (gate and up
    with the SwiGLU epilogue, then down), the same arguments and
    result: `rows` `[assignments, hidden]` sorted by expert, the three
    `[count, ...]` tables, `sizes` `[count]` int32. Differentiable
    through the xla lowering. Named and scoped `EXPERTS_SCOPE`, so a
    trace finds the experts by that text whichever path ran."""
    return _swiglu(rows, w_gate, w_up, w_down, sizes, interpret)
