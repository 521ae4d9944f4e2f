"""``ops.linear_attention``: the chunked gated delta rule with a
per-channel decay against the token-by-token recurrence of the plain
reference (``benchmark/reference/kimi_linear.py``), outputs and all five
gradients, and the short causal convolution.

Tolerances, in float32 on the CPU: both sides compute the same sums in
another order, so they part by a few roundings of float32 (measured
1e-7 to 4e-7 of the largest value, outputs and gradients alike). 2e-5 is
fifty times that, and a hundred times under what a state carried in
bfloat16 does to the output (``test_a_bfloat16_state_would_fail``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear as reference
from paddle_tpu.ops.linear_attention import chunk_kda, short_conv
from paddle_tpu.profiler import get_telemetry

TOL = 2e-5
F32 = jnp.float32


def inputs(seed, b, l, h, d, fastest=2.0):
    """q, k as the layer hands them (unit norm, q scaled), decays a
    channel from 0.999 a token down to exp(-fastest * softplus)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, l, h, d), F32)) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (b, l, h, d), F32))
    v = jax.random.normal(keys[2], (b, l, h, d), F32)
    rate = jnp.exp(jax.random.uniform(keys[3], (h, d), F32, np.log(1e-3),
                                      np.log(fastest)))
    g = -rate * jax.nn.softplus(jax.random.normal(keys[4], (b, l, h, d), F32))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (b, l, h), F32))
    return q, k, v, g, beta


def worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# (length, chunk): one chunk, several, a length that is no multiple of the
# chunk, a chunk of one sub-block, and one shorter than a chunk
CASES = [(64, 64), (200, 64), (96, 32), (50, 16), (40, 64)]


@pytest.mark.parametrize("length,chunk", CASES)
def test_chunked_matches_the_recurrence(length, chunk):
    args = inputs(length, 2, length, 2, 16)
    want = jax.jit(reference.delta_rule)(*args)
    got = jax.jit(lambda *a: chunk_kda(*a, chunk=chunk))(*args)
    assert got.shape == want.shape and got.dtype == F32
    assert worst(got, want) < TOL


@pytest.mark.parametrize("length,chunk", CASES)
def test_all_five_gradients_match_the_recurrence(length, chunk):
    args = inputs(100 + length, 2, length, 2, 16)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape, F32)
    grads = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 1, 2, 3, 4)))(*args)
    want = grads(reference.delta_rule)
    got = grads(lambda *a: chunk_kda(*a, chunk=chunk))
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert worst(a, b) < TOL, name


@pytest.mark.parametrize("checkpoint", [True, False])
def test_checkpoint_changes_no_number(checkpoint):
    args = inputs(3, 1, 128, 2, 16)
    f = lambda *a: jnp.sum(chunk_kda(*a, checkpoint=checkpoint) ** 2)  # noqa: E731
    want = lambda *a: jnp.sum(reference.delta_rule(*a) ** 2)  # noqa: E731
    for a, b in zip(jax.jit(jax.grad(f, argnums=(0, 3)))(*args),
                    jax.jit(jax.grad(want, argnums=(0, 3)))(*args)):
        assert worst(a, b) < TOL


@pytest.mark.parametrize("per_token", [5.0, 40.0, 200.0])
def test_fast_decays_do_not_overflow(per_token):
    """Channels that lose exp(-200) a token: exp(-cumsum g) of a chunk
    would be inf in any float; the sub-blocks never form it."""
    q, k, v, g, beta = inputs(7, 1, 128, 2, 16)
    g = g.at[..., ::2].set(-per_token)     # every other channel very fast
    g = g.at[:, 40:44].set(0.0)            # and a few tokens that keep all
    want = jax.jit(reference.delta_rule)(q, k, v, g, beta)
    got, grad = jax.jit(jax.value_and_grad(
        lambda g: chunk_kda(q, k, v, g, beta).sum(), has_aux=False))(g)
    assert bool(jnp.isfinite(grad).all()) and np.isfinite(float(got))
    assert worst(jax.jit(chunk_kda)(q, k, v, g, beta), want) < TOL


def test_a_bfloat16_state_would_fail():
    """The same recurrence with S rounded to bfloat16 after every token
    parts from the float32 one by a hundred times the tolerance."""
    q, k, v, g, beta = inputs(11, 1, 256, 2, 16, fastest=0.05)

    def token(S, x):
        q_t, k_t, v_t, g_t, beta_t = x
        S = S * jnp.exp(g_t)[..., None]
        u = v_t - jnp.sum(S * k_t[..., None], axis=-2)
        S = S + (beta_t[..., None] * k_t)[..., None] * u[..., None, :]
        S = S.astype(jnp.bfloat16).astype(F32)
        return S, jnp.sum(S * q_t[..., None], axis=-2)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((1, 2, 16, 16), F32), xs)
    want = jax.jit(reference.delta_rule)(q, k, v, g, beta)
    assert worst(jnp.moveaxis(o, 0, 1), want) > 100 * TOL
    assert worst(jax.jit(chunk_kda)(q, k, v, g, beta), want) < TOL


def test_bfloat16_operands_keep_a_float32_state():
    """bf16 inputs: the output is bf16 and within bf16's rounding of the
    float32 recurrence on the same (rounded) inputs: nothing compounds
    over 8 chunks."""
    args = inputs(5, 1, 512, 2, 16, fastest=0.05)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    got = jax.jit(chunk_kda)(q, k, v, *args[3:])
    want = jax.jit(reference.delta_rule)(
        q.astype(F32), k.astype(F32), v.astype(F32), *args[3:])
    assert got.dtype == jnp.bfloat16
    assert worst(got.astype(F32), want) < 3e-2


def test_chunk_must_be_sub_block_times_a_power_of_two():
    args = inputs(1, 1, 96, 1, 16)
    with pytest.raises(ValueError, match="power"):
        chunk_kda(*args, chunk=48)


def test_short_conv_is_causal_and_depthwise():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6), F32)
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4), F32)
    got = short_conv(x, w)
    want = np.zeros((2, 10, 6), np.float32)
    xn, wn = np.asarray(x), np.asarray(w)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += wn[:, j] * xn[:, t - 3 + j]
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(reference.short_conv(x, w), want, atol=1e-5)
    # nothing of a later token reaches an earlier one
    later = short_conv(x.at[:, 7:].set(0.0), w)
    np.testing.assert_allclose(later[:, :7], got[:, :7], atol=0)


def test_counters_are_set_when_traced():
    tel = get_telemetry()
    tel.reset()
    args = inputs(2, 1, 64, 1, 16)
    jax.jit(lambda *a: chunk_kda(*a, chunk=32)).lower(*args)
    assert tel.counter_value("kda/calls") == 1
    assert tel.scalars()["gauge/kda/chunk"] == 32
    tel.reset()
