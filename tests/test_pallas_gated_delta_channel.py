"""The chunk kernel's body for a gate per key CHANNEL
(`ops/pallas/gated_delta.py`, Kimi Delta Attention), interpreted,
against the recurrence itself, the `jax.numpy` chunked form (its xla
twin), the scalar body where the gate is constant over a head's
channels, and `solve_triangular` for the chunk's inverse. Two heads of
128, 256-384 tokens (docs/kernels.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops.gated_delta import l2norm, xla_gated_delta_prefill

#: float32 on both sides, the same mathematics in another order
ATOL = 2e-5


def _window(seq, *, seed=0, pad="none", dtype="float32", lo=-2.0, hi=-0.01,
            heads=2, dim=128, zero_state=False):
    """q, k, v, g `[1, S, H, D]`, beta, state, mask; each channel its
    own gate, uniform over `[lo, hi]`; `pad`: a third of the window
    masked off on the left, the right or nowhere."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (1, seq, heads, dim)))
    k = l2norm(jax.random.normal(ks[1], (1, seq, heads, dim)))
    v = jax.random.normal(ks[2], (1, seq, heads, dim))
    g = jax.random.uniform(ks[3], (1, seq, heads, dim), minval=lo,
                           maxval=hi)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, heads)))
    state = jnp.zeros((1, heads, dim, dim)) if zero_state else \
        jax.random.normal(ks[5], (1, heads, dim, dim))
    n_pad = seq // 3
    real = {"none": slice(0, seq), "right": slice(0, seq - n_pad),
            "left": slice(n_pad, seq)}[pad]
    mask = jnp.zeros((1, seq), bool).at[:, real].set(True)
    q, k, v = (x.astype(jnp.dtype(dtype)) for x in (q, k, v))
    return (q, k, v, g, beta, state, mask), real


@jax.jit
def _recurrence(q, k, v, g, beta, state):
    """The rule itself, a token a step: `S' = Diag(exp(g)) S`, `d =
    beta (v - k S')`, `S = S' + k^T d`, `o = q S`."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        decayed = jnp.exp(g_t)[..., None] * s
        d = b_t[..., None] * (v_t - jnp.einsum(
            "bhk,bhkv->bhv", k_t, decayed, precision="highest"))
        s = decayed + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision="highest")
    state, out = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(x.astype(jnp.float32), 1, 0)
        for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


@jax.jit
def _kernel(*case):
    """The kernel in interpret mode (jitted: windows of one shape share
    a compilation)."""
    from fengshen_tpu.ops.pallas.gated_delta import (
        pallas_gated_delta_prefill)
    return pallas_gated_delta_prefill(*case, interpret=True)


_twin = jax.jit(xla_gated_delta_prefill)


@pytest.fixture(scope="module")
def interpreted():
    """As tests/test_pallas_gated_delta.py: an interpreted chunk kernel
    is one CPU executable of thousands of memory mappings (a process
    may hold 65,530). This file compiles five; it drops jax's
    executables before its first and after its last, and its tests
    share them in between (four window shapes, one compilation each)."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("seq, pad, zero_state, dtype, heads", [
    (256, "none", False, "float32", 2), (256, "none", True, "bfloat16", 2),
    (256, "right", False, "float32", 2), (256, "left", False, "bfloat16", 2),
    (300, "right", True, "float32", 2), (300, "left", False, "float32", 2),
    (256, "right", False, "float32", 3),
    # the cell's own four heads a step: 8 problems a step, 25 s to compile
    pytest.param(256, "right", False, "float32", 4, marks=pytest.mark.slow),
], ids=["two_chunks", "two_chunks_fresh_bf16", "two_right",
        "two_left_bf16", "ragged_right_fresh", "ragged_left", "three_heads",
        "four_heads"])
def test_channel_kernel_interpret_equals_recurrence(seq, pad, zero_state,
                                                    dtype, heads,
                                                    interpreted):
    """Whole chunks and a window the wrapper pads to them; padding on
    the left, the right or nowhere; an incoming state and a fresh one;
    float32 and bfloat16 q, k, v; two heads a grid step, one where the
    heads are odd, and (slow) the cell's four: the recurrence's numbers
    over the real tokens, and the twin's."""
    case, real = _window(seq, seed=seq, pad=pad, dtype=dtype, heads=heads,
                         zero_state=zero_state)
    q, k, v, g, beta, state, mask = case
    got, got_state = _kernel(*case)
    assert got.shape == v.shape and got.dtype == v.dtype
    assert got_state.shape == state.shape and got_state.dtype == jnp.float32
    tol = ATOL if dtype == "float32" else 2e-2
    want, want_state = _recurrence(q[:, real], k[:, real], v[:, real],
                                   g[:, real], beta[:, real], state)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:, real], want,
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(got_state, want_state, rtol=ATOL, atol=ATOL)
    twin, twin_state = _twin(*case)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:, real],
        np.asarray(twin, np.float32)[:, real], rtol=tol, atol=tol)
    np.testing.assert_allclose(got_state, twin_state, rtol=ATOL, atol=ATOL)


def test_channel_kernel_two_windows_equal_one(interpreted):
    """Two windows in a row (the second onto the first's state, its
    tail padded) equal one window of both."""
    (q, k, v, g, beta, state, _), _ = _window(512, seed=3)
    cut, end = 256, 400
    every = jnp.ones((1, cut), bool)
    first, mid = _kernel(q[:, :cut], k[:, :cut], v[:, :cut], g[:, :cut],
                         beta[:, :cut], state, every)
    second, last = _kernel(
        q[:, cut:], k[:, cut:], v[:, cut:], g[:, cut:], beta[:, cut:], mid,
        every.at[:, end - cut:].set(False))
    whole, want = _recurrence(q[:, :end], k[:, :end], v[:, :end],
                              g[:, :end], beta[:, :end], state)
    np.testing.assert_allclose(first, whole[:, :cut], atol=ATOL)
    np.testing.assert_allclose(second[:, :end - cut], whole[:, cut:],
                               atol=ATOL)
    np.testing.assert_allclose(last, want, atol=ATOL)
    assert np.abs(np.asarray(mid - want)).max() > 1e-3


def test_channel_kernel_padded_window_returns_the_state_bit_for_bit(
        interpreted):
    """A window with no valid token (NaN under the mask in k, v and the
    gate) hands the state back as it came."""
    (q, k, v, g, beta, state, _), _ = _window(256, seed=1)
    nan = lambda x: jnp.full_like(x, jnp.nan)  # noqa: E731
    _, same = _kernel(q, nan(k), nan(v), nan(g), beta, state,
                      jnp.zeros((1, 256), bool))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(state))


@pytest.mark.parametrize("gate", ["spread", "alternating"])
def test_channel_kernel_hard_decays_stay_finite_and_equal_the_loop(
        gate, interpreted):
    """Gates down to -30 a token: inside a chunk of 128 a channel decays
    by up to e^-3840 and `exp(-G_j)` alone overflows float32 at e^88.
    No exponent of the kernel is positive where it is read: finite, and
    the recurrence's numbers. `alternating`: a head's even channels
    forget within a token while their neighbours nearly keep."""
    (q, k, v, g, beta, state, mask), _ = _window(256, seed=4, lo=-30.0,
                                                 hi=-0.01)
    if gate == "alternating":
        g = jnp.where(jnp.arange(128) % 2 == 0, -30.0, -0.01) * \
            jnp.ones_like(g)
    want, want_state = _recurrence(q, k, v, g, beta, state)
    got, got_state = _kernel(q, k, v, g, beta, state, mask)
    assert np.isfinite(got).all() and np.isfinite(got_state).all()
    np.testing.assert_allclose(got, want, atol=ATOL)
    np.testing.assert_allclose(got_state, want_state, atol=ATOL)


def test_a_gate_constant_over_channels_equals_the_scalar_kernel(
        interpreted):
    """`g[..., d] = g` for every channel of a head: the per-channel body
    gives what the scalar body gives on the scalar gate (float32, the
    same mathematics in another order)."""
    (q, k, v, g, beta, state, mask), real = _window(256, seed=5,
                                                    pad="right")
    scalar = g[..., 0]
    wide = jnp.broadcast_to(scalar[..., None], g.shape)
    a, sa = _kernel(q, k, v, wide, beta, state, mask)
    b, sb = _kernel(q, k, v, scalar, beta, state, mask)
    np.testing.assert_allclose(a[:, real], b[:, real], atol=ATOL)
    np.testing.assert_allclose(sa, sb, atol=ATOL)


def _channel_inverse_interpreted(k, g, beta, upto):
    """`(I - A)^-1` of one chunk as the kernel's body builds it
    (`_channel_chunk_matrices`: its rolls are the chip's), in interpret
    mode."""
    from jax.experimental import pallas as pl

    from fengshen_tpu.ops.pallas.gated_delta import _channel_chunk_matrices

    def body(k_ref, g_ref, G_ref, row_ref, col_ref, o_ref):
        problem = dict(q=k_ref[...], k=k_ref[...], g=g_ref[...],
                       G=G_ref[...], beta_row=row_ref[...],
                       beta_col=col_ref[...])
        _channel_chunk_matrices([problem], upto)
        o_ref[...] = problem["inverse"]
    c = k.shape[0]
    return pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((c, c), jnp.float32),
        interpret=True)(k, g, jnp.cumsum(g, axis=0), beta[None],
                        beta[:, None])


_channel_inverse_jitted = jax.jit(_channel_inverse_interpreted,
                                  static_argnames=("upto",))


@pytest.mark.parametrize("spread", [0.0, 0.05, 100.0],
                         ids=["repeated", "near", "free"])
@pytest.mark.parametrize("upto", [16, 64, 128])
def test_channel_chunk_inverse_equals_the_triangular_solve(upto, spread,
                                                           interpreted):
    """`(I - A)^-1` by the kernel's steps (the diagonal sub-blocks of
    `A` from explicit differences along their diagonals, what lies
    between them anchored along the merge tree, merged by products up
    to blocks of `upto` rows) against `solve_triangular` on `A` built
    in float64 from a per-channel gate: keys that repeat or nearly do
    (`|A_ij|` near 1 where the channels have decayed little), `beta`
    near 1, half the channels' decays near 0 and half down to -2 a
    token; and the same for independent keys."""
    from jax.scipy.linalg import solve_triangular

    from fengshen_tpu.ops.pallas.gated_delta import CHUNK as c
    rng = np.random.RandomState(350 + upto)
    rows, cols = np.indices((c, c))
    k = np.asarray(l2norm(jnp.asarray(
        rng.randn(1, 128) + spread * rng.randn(c, 128), jnp.float32)))
    beta = (1.0 - 1e-3 * rng.rand(c)).astype(np.float32)
    g = (-np.where(np.arange(128) % 2 == 0, 1e-3, 2.0) *
         rng.rand(c, 128)).astype(np.float32)
    G64, k64 = np.cumsum(g.astype(np.float64), axis=0), k.astype(np.float64)
    a = np.zeros((c, c))
    for i in range(c):
        for j in range(i):
            a[i, j] = -beta[i] * np.sum(
                k64[i] * k64[j] * np.exp(G64[i] - G64[j]))
    if spread <= 0.05:
        assert np.abs(a[rows == cols + 1]).min() > 0.4
    blocks = np.where(rows // upto == cols // upto, a, 0.0)
    want = solve_triangular(jnp.eye(c) - jnp.asarray(blocks, jnp.float32),
                            jnp.eye(c), lower=True, unit_diagonal=True)
    got = _channel_inverse_jitted(jnp.asarray(k), jnp.asarray(g),
                                  jnp.asarray(beta), upto=upto)
    np.testing.assert_allclose(got, want, atol=ATOL)
