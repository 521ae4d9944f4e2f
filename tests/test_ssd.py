"""Mamba-2's state-space recurrence (``ops.linear_attention.chunk_ssd``,
``ssd_step``) against the token-by-token definition, and the short
convolution with a carried tail against the whole row's."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.linear_attention import chunk_ssd, short_conv, ssd_step

HEADS, GROUPS, P, N = 4, 2, 8, 16


def naive(x, dt, A, B, C, D, S0=None):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t,
    in float64 numpy, head i reading group i // (heads / groups)."""
    x, dt, A, B, C, D = (np.asarray(t, np.float64) for t in (x, dt, A, B, C, D))
    b, l, h, p = x.shape
    rep = h // B.shape[2]
    B, C = np.repeat(B, rep, axis=2), np.repeat(C, rep, axis=2)
    S = np.zeros((b, h, p, B.shape[-1])) if S0 is None else np.asarray(
        S0, np.float64).copy()
    ys = []
    for t in range(l):
        S = (np.exp(dt[:, t] * A)[..., None, None] * S
             + (dt[:, t, :, None] * x[:, t])[..., None] * B[:, t, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", S, C[:, t]) + D[:, None] * x[:, t])
    return np.stack(ys, axis=1), S


def inputs(l, b=2, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    dt = np.log1p(np.exp(f(b, l, HEADS) * 2.0)).astype(np.float32)
    A = -np.exp(f(HEADS) * 0.5)
    return (f(b, l, HEADS, P), dt, A, f(b, l, GROUPS, N), f(b, l, GROUPS, N),
            f(HEADS))


@pytest.mark.parametrize("l", [1, 5, 8, 16, 19, 40])
def test_chunk_ssd_matches_the_recurrence(l):
    args = inputs(l)
    want_y, want_S = naive(*args)
    y, S = chunk_ssd(*map(jnp.asarray, args), chunk=8)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(S), want_S, atol=2e-4, rtol=2e-4)


def test_a_state_that_comes_in_is_carried():
    args = inputs(21, seed=1)
    S0 = np.random.default_rng(2).standard_normal(
        (2, HEADS, P, N)).astype(np.float32)
    want_y, want_S = naive(*args, S0=S0)
    y, S = chunk_ssd(*map(jnp.asarray, args), chunk=8,
                     initial_state=jnp.asarray(S0))
    np.testing.assert_allclose(np.asarray(y), want_y, atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(S), want_S, atol=3e-4, rtol=3e-4)


def test_a_masked_tail_leaves_the_state_alone():
    """dt = 0 decays nothing and writes nothing: the rule for positions
    that are not there."""
    x, dt, A, B, C, D = inputs(24, seed=3)
    real = np.array([13, 24])
    masked = dt * (np.arange(24)[None, :, None] < real[:, None, None])
    y, S = chunk_ssd(*map(jnp.asarray, (x, masked, A, B, C, D)), chunk=8)
    for row, n in enumerate(real):
        want_y, want_S = naive(x[row:row + 1, :n], dt[row:row + 1, :n], A,
                               B[row:row + 1, :n], C[row:row + 1, :n], D)
        np.testing.assert_allclose(np.asarray(y)[row, :n], want_y[0],
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(S)[row], want_S[0], atol=2e-4,
                                   rtol=2e-4)


def test_two_chunks_in_turn_equal_one_call():
    args = [jnp.asarray(t) for t in inputs(27, seed=4)]
    x, dt, A, B, C, D = args
    whole_y, whole_S = chunk_ssd(*args, chunk=8)
    y1, S1 = chunk_ssd(x[:, :11], dt[:, :11], A, B[:, :11], C[:, :11], D,
                       chunk=8)
    y2, S2 = chunk_ssd(x[:, 11:], dt[:, 11:], A, B[:, 11:], C[:, 11:], D,
                       chunk=8, initial_state=S1)
    np.testing.assert_allclose(np.concatenate([y1, y2], axis=1), whole_y,
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(S2, whole_S, atol=2e-4, rtol=2e-4)


def test_ssd_step_is_a_scan_of_length_one():
    x, dt, A, B, C, D = (jnp.asarray(t) for t in inputs(1, seed=5))
    S0 = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, HEADS, P, N)).astype(np.float32))
    want_y, want_S = chunk_ssd(x, dt, A, B, C, D, chunk=8, initial_state=S0)
    y, S = ssd_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D, S0)
    np.testing.assert_allclose(y, want_y[:, 0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S, want_S, atol=1e-5, rtol=1e-5)
    naive_y, naive_S = naive(x, dt, A, B, C, D, S0=S0)
    np.testing.assert_allclose(y, naive_y[:, 0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S, naive_S, atol=1e-5, rtol=1e-5)


def test_the_state_stays_float32_under_bfloat16_inputs():
    x, dt, A, B, C, D = inputs(12, seed=7)
    y, S = chunk_ssd(jnp.asarray(x, jnp.bfloat16), jnp.asarray(dt),
                     jnp.asarray(A), jnp.asarray(B, jnp.bfloat16),
                     jnp.asarray(C, jnp.bfloat16), jnp.asarray(D), chunk=8)
    assert y.dtype == jnp.bfloat16 and S.dtype == jnp.float32


@pytest.mark.parametrize("cut", [1, 3, 7])
def test_short_conv_with_a_carried_tail_equals_the_whole_rows(cut):
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((2, 12, 6)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((6, 4)).astype(np.float32))
    whole = short_conv(x, w)
    y1, tail = short_conv(x[:, :cut], w, jnp.zeros((2, 3, 6), jnp.float32))
    y2, tail2 = short_conv(x[:, cut:], w, tail)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], axis=1), whole,
                               atol=1e-6)
    np.testing.assert_allclose(tail2, x[:, -3:], atol=0)


def test_short_conv_leaves_padding_out_of_the_tail():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, 8, 5)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((5, 4)).astype(np.float32))
    before = jnp.asarray(rng.standard_normal((2, 3, 5)).astype(np.float32))
    valid = jnp.asarray([5, 0], jnp.int32)
    _, tail = short_conv(x, w, before, valid)
    np.testing.assert_allclose(tail[0], x[0, 2:5], atol=0)
    np.testing.assert_allclose(tail[1], before[1], atol=0)  # nothing real
