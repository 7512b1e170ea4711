"""The delta rule gated per key CHANNEL (`ops/gated_delta.py`, Kimi
Delta Attention): the one-token step and the chunked form against a
token-by-token loop, the anchored exponents at decays that would
overflow a naive `exp(-G)`, the scalar gate as the special case it is,
and what the seam puts on the dispatch record (the Mosaic body for this
gate has tests/test_pallas_gated_delta_channel.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops.gated_delta import (_channel_decayed_products,
                                          gated_delta_decode,
                                          gated_delta_prefill, l2norm,
                                          xla_gated_delta_prefill)

#: float32 on both sides, the same mathematics in another order
ATOL = 2e-5


def _case(seq, seed=0, batch=2, heads=3, dim=16, lo=-2.0, hi=-0.01):
    """q, k, v, g `[B, S, H, Dk]`, beta, state; the gate uniform over
    `[lo, hi]`, each channel its own."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (batch, seq, heads, dim)))
    k = l2norm(jax.random.normal(ks[1], (batch, seq, heads, dim)))
    v = jax.random.normal(ks[2], (batch, seq, heads, dim))
    g = jax.random.uniform(ks[3], (batch, seq, heads, dim), minval=lo,
                           maxval=hi)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    state = jax.random.normal(ks[5], (batch, heads, dim, dim))
    return q, k, v, g, beta, state


def _loop(q, k, v, g, beta, state, mask=None):
    """The recurrence itself, written out here: `S' = Diag(exp(g)) S`,
    `d = beta (v - k S')`, `S = S' + k^T d`, `o = q S`."""
    outs = []
    for t in range(q.shape[1]):
        decayed = jnp.exp(g[:, t])[..., None] * state
        d = beta[:, t, :, None] * (v[:, t] - jnp.einsum(
            "bhk,bhkv->bhv", k[:, t], decayed, precision="highest"))
        new = decayed + k[:, t, :, :, None] * d[:, :, None, :]
        if mask is not None:
            new = jnp.where(mask[:, t, None, None, None], new, state)
        state = new
        outs.append(jnp.einsum("bhk,bhkv->bhv", q[:, t], state,
                               precision="highest"))
    return jnp.stack(outs, 1), state


def test_the_step_decays_each_row_of_the_state_by_its_own_gate():
    """One head of two channels: row 0 halves, row 1 keeps a tenth."""
    state = jnp.asarray([[1.0, 2.0], [10.0, 20.0]])[None, None]
    g = jnp.log(jnp.asarray([0.5, 0.1]))[None, None]              # [1,1,2]
    zero = jnp.zeros((1, 1, 2))
    out, new = gated_delta_decode(jnp.ones((1, 1, 2)), zero, zero, g,
                                  jnp.ones((1, 1)), state)
    np.testing.assert_allclose(new[0, 0], [[0.5, 1.0], [1.0, 2.0]],
                               rtol=1e-6)
    np.testing.assert_allclose(out[0, 0], [1.5, 3.0], rtol=1e-6)


def test_the_step_equals_the_loop_and_keeps_a_dead_lanes_state():
    q, k, v, g, beta, state = _case(6, seed=3)
    want, want_state = _loop(q, k, v, g, beta, state)
    s = state
    for t in range(6):
        o, s = gated_delta_decode(q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t], s)
        np.testing.assert_allclose(o, want[:, t], atol=ATOL)
    np.testing.assert_allclose(s, want_state, atol=ATOL)
    _, kept = gated_delta_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], state,
                                 jnp.asarray([False, True]))
    np.testing.assert_array_equal(kept[0], state[0])
    assert not np.array_equal(kept[1], state[1])


@pytest.mark.parametrize("chunk,seq", [(1, 12), (8, 24), (16, 32), (64, 64),
                                       (16, 45), (64, 70), (24, 50)])
def test_channel_gated_chunks_equal_the_loop(chunk, seq):
    """Over chunk sizes (sub-blocks of 16, of 8 where the chunk is 24 or
    8, of 1), whole and ragged windows."""
    case = _case(seq)
    want, want_state = _loop(*case)
    got, state = gated_delta_prefill(*case, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=ATOL)
    np.testing.assert_allclose(state, want_state, atol=ATOL)


@pytest.mark.parametrize("side", ["right", "left"])
def test_channel_gated_padding_enters_no_state(side):
    q, k, v, g, beta, state = _case(20, seed=1)
    real = slice(0, 13) if side == "right" else slice(7, 20)
    mask = jnp.zeros((2, 20), bool).at[:, real].set(True)
    got, got_state = gated_delta_prefill(q, k, v, g, beta, state, mask,
                                         chunk=8)
    want, want_state = _loop(q[:, real], k[:, real], v[:, real],
                             g[:, real], beta[:, real], state)
    np.testing.assert_allclose(got[:, real], want, atol=ATOL)
    np.testing.assert_allclose(got_state, want_state, atol=ATOL)


def test_two_windows_carry_the_state_over_their_boundary():
    """A prompt in two windows (the second padded on the right) leaves
    the state and the outputs of the prompt in one."""
    q, k, v, g, beta, state = _case(44, seed=2)
    want, want_state = _loop(q, k, v, g, beta, state)
    first, mid = gated_delta_prefill(q[:, :32], k[:, :32], v[:, :32],
                                     g[:, :32], beta[:, :32], state,
                                     chunk=16)
    pad = lambda x: jnp.pad(  # noqa: E731
        x[:, 32:], ((0, 0), (0, 20)) + ((0, 0),) * (x.ndim - 2),
        constant_values=0.3)
    mask = jnp.zeros((2, 32), bool).at[:, :12].set(True)
    second, end = gated_delta_prefill(pad(q), pad(k), pad(v), -pad(-g),
                                      pad(beta), mid, mask, chunk=16)
    np.testing.assert_allclose(first, want[:, :32], atol=ATOL)
    np.testing.assert_allclose(second[:, :12], want[:, 32:], atol=ATOL)
    np.testing.assert_allclose(end, want_state, atol=ATOL)


@pytest.mark.parametrize("chunk", [16, 64])
def test_hard_decays_stay_finite_and_equal_the_loop(chunk):
    """Gates spread over [-20, -0.01] across channels: inside a chunk of
    64 a channel decays by up to e^-1280, and `exp(-G_j)` alone would
    overflow float32 at e^88. The anchored form forms no positive
    exponent: finite, and the loop's numbers."""
    case = _case(130, seed=4, lo=-20.0, hi=-0.01)
    want, want_state = _loop(*case)
    got, state = gated_delta_prefill(*case, chunk=chunk)
    assert np.isfinite(got).all() and np.isfinite(state).all()
    np.testing.assert_allclose(got, want, atol=ATOL)
    np.testing.assert_allclose(state, want_state, atol=ATOL)
    # some channels nearly keep, their neighbours forget within a token
    q, k, v, g, beta, s0 = case
    g = jnp.where(jnp.arange(16) % 2 == 0, -20.0, -0.01) * jnp.ones_like(g)
    want, want_state = _loop(q, k, v, g, beta, s0)
    got, state = gated_delta_prefill(q, k, v, g, beta, s0, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=ATOL)
    np.testing.assert_allclose(state, want_state, atol=ATOL)


def test_the_pairwise_products_by_hand_and_without_a_positive_exponent():
    """`M[i, j] = sum_d r_i[d] k_j[d] exp(G_i[d] - G_j[d])`, `i >= j`,
    against the plain double loop in float64, at cumulative decays down
    to -1,900 (a naive `exp(-G)` is inf from -88 on)."""
    rng = np.random.default_rng(0)
    c, dk = 48, 8
    g = -rng.uniform(0.01, 40.0, size=(c, dk))
    G = np.cumsum(g, axis=0)
    assert G.min() < -900
    r, k = rng.normal(size=(2, c, dk))
    want = np.zeros((c, c))
    for i in range(c):
        for j in range(i + 1):
            want[i, j] = np.sum(r[i] * k[j] * np.exp(G[i] - G[j]))
    got, = _channel_decayed_products(
        (jnp.asarray(r, jnp.float32),), jnp.asarray(k, jnp.float32),
        jnp.asarray(G, jnp.float32))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_gate_constant_over_channels_is_the_scalar_rule(chunk):
    """`g[..., d] = g` for every channel: the per-channel forms give what
    the scalar ones give, the step bit for bit (the same multiply, the
    gate broadcast along the state's rows either way) and the chunked
    form to float32 rounding (another order of operations)."""
    q, k, v, g, beta, state = _case(70, seed=5)
    scalar = g[..., 0]
    wide = jnp.broadcast_to(scalar[..., None], g.shape)
    a, sa = gated_delta_decode(q[:, 0], k[:, 0], v[:, 0], scalar[:, 0],
                               beta[:, 0], state)
    b, sb = gated_delta_decode(q[:, 0], k[:, 0], v[:, 0], wide[:, 0],
                               beta[:, 0], state)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(sa, sb)
    a, sa = gated_delta_prefill(q, k, v, scalar, beta, state, chunk=chunk)
    b, sb = gated_delta_prefill(q, k, v, wide, beta, state, chunk=chunk)
    np.testing.assert_allclose(a, b, atol=ATOL)
    np.testing.assert_allclose(sa, sb, atol=ATOL)


def test_a_gate_averaged_over_a_heads_channels_is_another_rule():
    """What a program that gates a head by the MEAN of its channels'
    gates computes is far from the rule (hundreds of times the
    comparison's tolerance): the tests above would not pass it."""
    q, k, v, g, beta, state = _case(64, seed=6)
    want, _ = _loop(q, k, v, g, beta, state)
    got, _ = gated_delta_prefill(q, k, v, g.mean(-1), beta, state, chunk=16)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() > 500 * ATOL


def _channel_window(seq, heads, dk):
    sds = jax.ShapeDtypeStruct
    q = sds((1, seq, heads, dk), jnp.float32)
    return (q, q, sds((1, seq, heads, 128), jnp.bfloat16), q,
            sds((1, seq, heads), jnp.float32),
            sds((1, heads, dk, 128), jnp.float32))


def test_the_seam_takes_the_kernel_at_the_cells_shape_and_says_why_elsewhere(
        fresh_probe, monkeypatch):
    """On a backend that runs Mosaic a window of the cell's shape takes
    the chunk kernel under either gate, the per-channel one with the
    gate's shape on the dispatch record; a shape that does not tile (a
    key head of 64, a window shorter than a chunk) runs the `jax.numpy`
    form and says why."""
    import fengshen_tpu.ops.pallas as kernels
    from fengshen_tpu.ops.pallas.gated_delta import _ineligible_reason
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "test"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    q, _, v, g, beta, state = cell = _channel_window(2048, 32, 128)
    assert _ineligible_reason(q, v, beta) is None
    assert _ineligible_reason(q, v, g) is None
    out, new = jax.eval_shape(gated_delta_prefill, *cell)
    assert out.shape == v.shape and out.dtype == v.dtype
    assert new.shape == state.shape
    took, = kernels.traced_dispatch()
    assert took == {"op": "gated_delta_prefill", "impl": "pallas",
                    "detail": "q=(1, 2048, 32, 128):float32 v=(1, 2048, 32, "
                              "128):bfloat16 g=(1, 2048, 32, 128)"}
    for case, why in [(_channel_window(2048, 32, 64), "Dk 64 % 128"),
                      (_channel_window(100, 32, 128),
                       "window 100 shorter than a chunk")]:
        assert why in _ineligible_reason(case[0], case[2], case[3])
        jax.eval_shape(gated_delta_prefill, *case)
        last = kernels.traced_dispatch()[-1]
        assert last["impl"] == "xla" and why in last["detail"]
        assert f"g={case[3].shape}" in last["detail"]
    assert not any("gate per channel" in t["detail"]
                   for t in kernels.traced_dispatch())
    # several value heads a key head under this gate: no model has them
    narrow = jax.ShapeDtypeStruct((1, 2048, 16, 128), jnp.float32)
    assert "value heads a key head" in _ineligible_reason(narrow, v, g)


def test_the_seam_stays_on_xla_under_a_mesh_with_this_gate(
        mesh8, fresh_probe, monkeypatch):
    """GSPMD cannot partition a Mosaic call: under a multi-device mesh
    the cell's shape runs the `jax.numpy` form and says why."""
    import fengshen_tpu.ops.pallas as kernels
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "test"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    # a function of its own: the test above has traced this shape
    jax.eval_shape(lambda *window: gated_delta_prefill(*window),
                   *_channel_window(2048, 32, 128))
    took, = kernels.traced_dispatch()
    assert took["impl"] == "xla" and "8-device mesh" in took["detail"]
    assert "g=(1, 2048, 32, 128)" in took["detail"]


def test_both_gates_run_under_the_scopes_a_trace_reads():
    """No model has both gates, so both forms carry the scalar ones'
    scopes: a reader finds the delta rule by one text."""
    q, k, v, g, beta, state = _case(32)
    text = jax.jit(xla_gated_delta_prefill).lower(
        q, k, v, g, beta, state).as_text(debug_info=True)
    assert "fstpu_gated_delta_prefill" in text
    text = jax.jit(gated_delta_decode).lower(
        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
        state).as_text(debug_info=True)
    assert "fstpu_gated_delta_decode" in text
