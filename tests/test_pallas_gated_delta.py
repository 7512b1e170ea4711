"""The chunked gated delta rule (`ops/pallas/gated_delta.py`): the
interpreted kernel against the recurrence, and its dispatch
(docs/kernels.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops.pallas import log_dispatch


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
