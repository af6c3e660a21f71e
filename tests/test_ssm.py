"""``ops/ssm.py`` against the recurrence written out token by token
(CPU, float32): the chunked scan inside a chunk and across chunk
boundaries with the state carried, right-padded rows, the one-token step,
and the short convolution's carried inputs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from serverless_learn_tpu.ops import ssm

B_, H, P, G, N, K = 2, 4, 8, 2, 16, 4
C_ = H * P + 2 * G * N


def _inputs(T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B_, T, H, P))
    # Decays spread over (0, 1): a state that forgets at once or never
    # would hide a dropped carry.
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B_, T, H)) - 1.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    Bm = jax.random.normal(ks[3], (B_, T, G, N))
    Cm = jax.random.normal(ks[4], (B_, T, G, N))
    h0 = jax.random.normal(ks[5], (B_, H, P, N))
    return x, dt, A, Bm, Cm, h0


def _token_by_token(x, dt, A, Bm, Cm, h0):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t; y_t = h_t C_t."""
    x, dt, A, Bm, Cm, h = (np.asarray(a, np.float64)
                           for a in (x, dt, A, Bm, Cm, h0))
    per_head = lambda a: np.repeat(a, H // G, axis=1)
    ys = []
    for t in range(x.shape[1]):
        decay = np.exp(dt[:, t] * A)
        h = (h * decay[:, :, None, None]
             + (dt[:, t, :, None] * x[:, t])[..., None]
             * per_head(Bm[:, t])[:, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", h, per_head(Cm[:, t])))
    return np.stack(ys, axis=1), h


@pytest.mark.parametrize("T,chunk", [(8, 8), (8, 256), (24, 8), (21, 8),
                                     (5, 4), (1, 4)])
def test_the_chunked_scan_is_the_recurrence(T, chunk):
    """One chunk, several whole chunks, and lengths no chunk divides."""
    args = _inputs(T)
    y, h = ssm.ssd_scan(*args, chunk=chunk)
    y_ref, h_ref = _token_by_token(*args)
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h, h_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cuts", [(8, 16), (5, 6, 13), (1, 2, 23)])
def test_a_sequence_fed_in_pieces_carries_its_state(cuts):
    """Prefill chunk after prefill chunk: the state one call returns is
    what the next starts from, and the pieces' outputs are the whole's."""
    x, dt, A, Bm, Cm, h0 = _inputs(24, seed=1)
    y_ref, h_ref = _token_by_token(x, dt, A, Bm, Cm, h0)
    h, ys, lo = h0, [], 0
    for hi in (*cuts, 24):
        y, h = ssm.ssd_scan(x[:, lo:hi], dt[:, lo:hi], A, Bm[:, lo:hi],
                            Cm[:, lo:hi], h, chunk=8)
        ys.append(y)
        lo = hi
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_ref,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h, h_ref, rtol=2e-4, atol=2e-4)
    # Dropping the carry between two pieces is far outside the tolerance.
    y_cold, _ = ssm.ssd_scan(x[:, cuts[0]:], dt[:, cuts[0]:], A,
                             Bm[:, cuts[0]:], Cm[:, cuts[0]:],
                             jnp.zeros_like(h0), chunk=8)
    assert np.abs(np.asarray(y_cold) - y_ref[:, cuts[0]:]).max() > 0.1


def test_a_right_padded_row_stops_at_its_real_tokens():
    """``dt`` = 0 on the padding: the state is EXACTLY that of the real
    tokens (row 0 has 5 of 12, row 1 has none), whatever the padding
    holds."""
    x, dt, A, Bm, Cm, h0 = _inputs(12, seed=2)
    lens = jnp.array([5, 0])
    real = jnp.arange(12)[None, :] < lens[:, None]
    y, h = ssm.ssd_scan(x, jnp.where(real[..., None], dt, 0.0), A, Bm, Cm,
                        h0, chunk=4)
    y5, h5 = _token_by_token(x[:1, :5], dt[:1, :5], A, Bm[:1, :5],
                             Cm[:1, :5], h0[:1])
    np.testing.assert_allclose(y[:1, :5], y5, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h[:1], h5, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(h[1], h0[1])


def test_the_one_token_step_is_a_scan_of_length_one():
    x, dt, A, Bm, Cm, h0 = _inputs(1, seed=3)
    y1, h1 = ssm.ssm_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], h0)
    y, h = ssm.ssd_scan(x, dt, A, Bm, Cm, h0, chunk=4)
    np.testing.assert_allclose(y1, y[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h1, h, rtol=1e-5, atol=1e-5)
    y_ref, h_ref = _token_by_token(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(y1, y_ref[:, 0], rtol=1e-5, atol=1e-5)


def _conv_whole(x, w, b):
    """The convolution over a whole sequence, zeros before its start."""
    T = x.shape[1]
    full = np.concatenate([np.zeros((x.shape[0], K - 1, x.shape[2])),
                           np.asarray(x, np.float64)], axis=1)
    return sum(full[:, j:j + T] * np.asarray(w[j], np.float64)
               for j in range(K)) + np.asarray(b, np.float64)


@pytest.mark.parametrize("cuts", [(), (4,), (1, 2, 3), (7, 8)])
def test_the_convolution_carries_its_last_inputs(cuts):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (B_, 10, C_))
    w = jax.random.normal(ks[1], (K, C_))
    b = jax.random.normal(ks[2], (C_,))
    carried, ys, lo = jnp.zeros((B_, K - 1, C_)), [], 0
    for hi in (*cuts, 10):
        y, carried = ssm.causal_conv(x[:, lo:hi], carried, w, b)
        ys.append(y)
        lo = hi
    np.testing.assert_allclose(jnp.concatenate(ys, 1), _conv_whole(x, w, b),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(carried, x[:, -(K - 1):])


def test_a_padded_rows_convolution_keeps_its_last_three_real_inputs():
    """Row 0 has 5 real tokens of 8, row 1 has 2 (fewer than K - 1: the
    oldest carried input stays), row 2 none (nothing moves)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (3, 8, C_))
    before = jax.random.normal(ks[1], (3, K - 1, C_))
    w, b = jnp.ones((K, C_)), jnp.zeros((C_,))
    _, after = ssm.causal_conv(x, before, w, b, lens=jnp.array([5, 2, 0]))
    np.testing.assert_array_equal(after[0], x[0, 2:5])
    np.testing.assert_array_equal(
        after[1], jnp.concatenate([before[1, -1:], x[1, :2]]))
    np.testing.assert_array_equal(after[2], before[2])
