"""``ops.linear_attention``: the chunked gated delta rule with a
per-channel decay against the token-by-token recurrence of the plain
reference (``benchmark/reference/kimi_linear.py``) on q and k normalised
as the reference's layer normalises them, outputs and all five gradients
(those of q and k as they were before the norm), and the short causal
convolution.

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
from paddle_tpu.ops import kda_tpu, linear_attention
from paddle_tpu.ops.linear_attention import chunk_kda, short_conv
from paddle_tpu.profiler import get_telemetry

TOL = 2e-5
F32 = jnp.float32
EPS = 1e-6


@pytest.fixture
def impl(request, monkeypatch):
    """The lowering under test: the XLA form as the CPU takes it by rule,
    or the kernel pair, for which the test stands in for the TPU and runs
    Pallas in interpret mode, two heads a grid step so that a state is
    handed over between chunks and kept apart between head groups."""
    if request.param == "pallas":
        monkeypatch.setattr(linear_attention, "_on_tpu", lambda: True)
        monkeypatch.setattr(linear_attention, "_INTERPRET", True)
        monkeypatch.setattr(kda_tpu, "heads_per_step", lambda h: 2)
    return request.param


both = pytest.mark.parametrize("impl", ["xla", "pallas"], indirect=True)


def inputs(seed, b, l, h, d, fastest=2.0):
    """q, k as the layer hands them (before their norm, rows of lengths
    from a tenth of sqrt(d) to ten times it), decays a channel from 0.999
    a token down to exp(-fastest * softplus)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    length = lambda key: jnp.exp(jax.random.uniform(  # noqa: E731
        key, (b, l, h, 1), F32, np.log(0.1), np.log(10.0)))
    q = jax.random.normal(keys[0], (b, l, h, d), F32) * length(keys[6])
    k = jax.random.normal(keys[1], (b, l, h, d), F32) * length(keys[7])
    v = jax.random.normal(keys[2], (b, l, h, d), F32)
    rate = jnp.exp(jax.random.uniform(keys[3], (h, d), F32, np.log(1e-3),
                                      np.log(fastest)))
    g = -rate * jax.nn.softplus(jax.random.normal(keys[4], (b, l, h, d), F32))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (b, l, h), F32))
    return q, k, v, g, beta


def normalised(q, k, eps=EPS):
    """What the reference's layer does to q and k before the rule."""
    unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(t), -1, keepdims=True) + eps)
    return unit(q) * q.shape[-1] ** -0.5, unit(k)


def recurrence(q, k, v, g, beta):
    return reference.delta_rule(*normalised(q, k), v, g, beta)


def worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# (length, chunk, heads, width): one chunk, several, a length that is no
# multiple of the chunk, a chunk of one sub-block, and one shorter than a
# chunk; then at a width the kernels take (two groups of two heads): one
# chunk, several, and a length that is no multiple of the chunk
CASES = [(64, 64, 2, 16), (200, 64, 2, 16), (96, 32, 2, 16), (50, 16, 2, 16),
         (40, 64, 2, 16), (64, 64, 4, 128), (256, 64, 4, 128),
         (150, 64, 4, 128)]


def taken(impl, width):
    """The lowering a call of this width takes under ``impl``."""
    return impl if width % 128 == 0 else "xla"


@both
@pytest.mark.parametrize("length,chunk,heads,width", CASES)
def test_chunked_matches_the_recurrence(length, chunk, heads, width, impl):
    args = inputs(length, 2 if width < 128 else 1, length, heads, width)
    want = jax.jit(recurrence)(*args)
    get_telemetry().reset()
    got = jax.jit(lambda *a: chunk_kda(*a, chunk=chunk))(*args)
    assert f"gauge/kda/tier.{taken(impl, width)}" in get_telemetry().scalars()
    assert got.shape == want.shape and got.dtype == F32
    assert worst(got, want) < TOL


@both
@pytest.mark.parametrize("length,chunk,heads,width", CASES)
def test_all_five_gradients_match_the_recurrence(length, chunk, heads, width,
                                                 impl):
    args = inputs(100 + length, 2 if width < 128 else 1, length, heads, width)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape, F32)
    grads = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 1, 2, 3, 4)))(*args)
    want = grads(recurrence)
    got = grads(lambda *a: chunk_kda(*a, chunk=chunk))
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert worst(a, b) < TOL, name


@pytest.mark.parametrize("checkpoint", [True, False])
def test_checkpoint_changes_no_number(checkpoint):
    args = inputs(3, 1, 128, 2, 16)
    f = lambda *a: jnp.sum(chunk_kda(*a, checkpoint=checkpoint) ** 2)  # noqa: E731
    want = lambda *a: jnp.sum(recurrence(*a) ** 2)  # noqa: E731
    for a, b in zip(jax.jit(jax.grad(f, argnums=(0, 3)))(*args),
                    jax.jit(jax.grad(want, argnums=(0, 3)))(*args)):
        assert worst(a, b) < TOL


@both
@pytest.mark.parametrize("width", [16, 128])
@pytest.mark.parametrize("per_token", [5.0, 40.0, 200.0])
def test_fast_decays_do_not_overflow(per_token, width, impl):
    """Channels that lose exp(-200) a token: exp(-cumsum g) of a chunk
    would be inf in any float; the sub-blocks never form it."""
    q, k, v, g, beta = inputs(7, 1, 128, 2, width)
    g = g.at[..., ::2].set(-per_token)     # every other channel very fast
    g = g.at[:, 40:44].set(0.0)            # and a few tokens that keep all
    want = jax.jit(recurrence)(q, k, v, g, beta)
    got, grad = jax.jit(jax.value_and_grad(
        lambda g: chunk_kda(q, k, v, g, beta).sum(), has_aux=False))(g)
    assert bool(jnp.isfinite(grad).all()) and np.isfinite(float(got))
    assert worst(jax.jit(chunk_kda)(q, k, v, g, beta), want) < TOL


def test_a_bfloat16_state_would_fail():
    """The same recurrence with S rounded to bfloat16 after every token
    parts from the float32 one by a hundred times the tolerance."""
    q, k, v, g, beta = inputs(11, 1, 256, 2, 16, fastest=0.05)
    q_n, k_n = normalised(q, k)

    def token(S, x):
        q_t, k_t, v_t, g_t, beta_t = x
        S = S * jnp.exp(g_t)[..., None]
        u = v_t - jnp.sum(S * k_t[..., None], axis=-2)
        S = S + (beta_t[..., None] * k_t)[..., None] * u[..., None, :]
        S = S.astype(jnp.bfloat16).astype(F32)
        return S, jnp.sum(S * q_t[..., None], axis=-2)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q_n, k_n, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((1, 2, 16, 16), F32), xs)
    want = jax.jit(recurrence)(q, k, v, g, beta)
    assert worst(jnp.moveaxis(o, 0, 1), want) > 100 * TOL
    assert worst(jax.jit(chunk_kda)(q, k, v, g, beta), want) < TOL


@both
@pytest.mark.parametrize("length,width", [(512, 16), (256, 128)])
def test_bfloat16_operands_keep_a_float32_state(length, width, impl):
    """bf16 inputs: the output is bf16 and within bf16's rounding of the
    float32 recurrence on the same (rounded) inputs: nothing compounds
    over the chunks."""
    args = inputs(5, 1, length, 2, width, fastest=0.05)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    got = jax.jit(chunk_kda)(q, k, v, *args[3:])
    want = jax.jit(recurrence)(
        q.astype(F32), k.astype(F32), v.astype(F32), *args[3:])
    assert got.dtype == jnp.bfloat16
    assert worst(got.astype(F32), want) < 3e-2


@pytest.mark.parametrize("impl", ["pallas"], indirect=True)
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16])
def test_the_backward_kernel_is_the_vjp_of_the_xla_form(dtype, impl):
    """The hand-written backward (under the norm on the flat form and its
    autodiff) against autodiff of the norm by heads and ``_chunk_kda``
    after it on the same inputs, all five cotangents; in bf16 both sides
    round the same operands and part by bf16's rounding of the results."""
    args = inputs(21, 1, 192, 4, 128)
    args = tuple(t.astype(dtype) for t in args[:3]) + args[3:]
    ct = jax.random.normal(jax.random.PRNGKey(4), args[2].shape, F32)
    ct = ct.astype(dtype)
    xla = norm_by_heads_then_the_rule
    want = jax.jit(lambda a: jax.vjp(xla, *a)[1](ct))(args)
    got = jax.jit(lambda a: jax.vjp(
        lambda *x: chunk_kda(*x, checkpoint=False), *a)[1](ct))(args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert worst(a.astype(F32), b.astype(F32)) < (
            TOL if dtype == F32 else 2e-2), name


def norm_by_heads_then_the_rule(q, k, v, g, beta, chunk=64):
    """What the layer computed before the norm became the operation's:
    ``unit(...)`` on [b, l, h, d] in XLA, rounded to v's dtype, then the
    chunked rule on what it hands over. The XLA form does this still."""
    unit = lambda t: linear_attention._unit(t, EPS)  # noqa: E731
    return linear_attention._chunk_kda(
        *linear_attention._handed(unit(q), unit(k), k.shape[-1], v.dtype),
        v, g, beta, chunk=chunk, sub=16)


# one chunk, several, a ragged length; four heads are two groups of two
RAW_CASES = [64, 256, 150]


# (q and k's dtype, v's): all float32, all bfloat16, and the layer's own
# call, q and k float32 as the convolutions computed them beside a
# bfloat16 v
DTYPES = [(F32, F32), (jnp.bfloat16, jnp.bfloat16), (F32, jnp.bfloat16)]


@both
@pytest.mark.parametrize("raw,dtype", DTYPES)
@pytest.mark.parametrize("length", RAW_CASES)
def test_the_norm_on_the_flat_form_is_the_norm_by_heads(length, raw, dtype,
                                                        impl):
    """``chunk_kda`` on q and k as they come, with the epsilon, against the
    norm by heads in XLA followed by the rule, output and all five
    gradients (q's and k's in the dtype they came in). The kernels' tier
    takes the norm on [b, l, h d], its sums as products: the same numbers
    to float32's rounding, and after the rounding to v's dtype the same
    operands for the same kernels."""
    args = inputs(300 + length, 1, length, 4, 128)
    args = (args[0].astype(raw), args[1].astype(raw),
            args[2].astype(dtype)) + args[3:]
    ct = jax.random.normal(jax.random.PRNGKey(6), args[2].shape, F32)
    both_of = lambda f: jax.jit(lambda a: jax.vjp(f, *a))(args)  # noqa: E731
    want, want_vjp = both_of(norm_by_heads_then_the_rule)
    got, got_vjp = both_of(lambda *a: chunk_kda(*a, EPS))
    assert got.dtype == want.dtype == dtype
    tol_out, tol_grad = (TOL, TOL) if dtype == F32 else (3e-2, 2e-2)
    assert worst(got.astype(F32), want.astype(F32)) < tol_out
    for name, a, b in zip("q k v g beta".split(),
                          got_vjp(ct.astype(dtype)), want_vjp(ct.astype(dtype))):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert worst(a.astype(F32), b.astype(F32)) < tol_grad, name


def test_head_sums_are_products_with_a_0_1_matrix():
    """``head_sums`` and ``over_heads`` against the reshape they stand in
    for, values and transposes."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 4 * 16), F32)
    sums, back = jax.vjp(lambda t: linear_attention.head_sums(t, 4), x)
    np.testing.assert_allclose(sums, x.reshape(2, 5, 4, 16).sum(-1),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        back(sums)[0], linear_attention.over_heads(sums, 16))
    np.testing.assert_array_equal(
        linear_attention.over_heads(sums, 16),
        jnp.repeat(sums, 16, axis=-1))


@both
@pytest.mark.parametrize("width", [16, 128])
def test_rows_of_zeros_and_of_large_values_stay_finite(width, impl):
    """A row of zeros has the norm sqrt(eps): it is asked nothing and
    writes nothing, and its gradient is the cotangent over sqrt(eps). A
    row of values near 1e4 is normalised like any other."""
    q, k, v, g, beta = inputs(17, 1, 128, 2, width)
    q = q.at[:, 3].set(0.0).at[:, 70].mul(3e3)
    k = k.at[:, 5].set(0.0).at[:, 70].mul(3e3).at[:, 100, 1].set(0.0)
    ct = jax.random.normal(jax.random.PRNGKey(8), v.shape, F32)
    f = lambda f: jax.jit(lambda *a: jax.vjp(f, *a))(q, k, v, g, beta)  # noqa: E731
    (want, want_vjp), (got, got_vjp) = f(recurrence), f(chunk_kda)
    assert bool(jnp.isfinite(got).all()) and worst(got, want) < TOL
    got_grads = got_vjp(ct)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_vjp(ct)):
        assert bool(jnp.isfinite(a).all()), name
        assert worst(a, b) < TOL, name
    # a zero row of q gets the whole cotangent over sqrt(eps): not zero
    assert float(jnp.abs(got_grads[0][:, 3]).max()) > 0


def lowered_primitives(fn, *args):
    """The names of every primitive in ``fn``'s jaxpr, kernels' bodies
    left out."""
    names = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            names.add(eqn.primitive.name)
            if eqn.primitive.name == "pallas_call":
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


@pytest.mark.parametrize("impl", ["pallas"], indirect=True)
def test_the_rule_chooses_by_backend_and_shape(impl, monkeypatch):
    """On a TPU a call at d = 128 and chunk 64 is the kernel pair, forward
    and backward, with no scan over chunks and nothing of XLA's form; at
    d = 32, at a chunk the kernels do not take, or off the TPU it is the
    XLA form. The gauge says which."""
    tel = get_telemetry()
    grad = lambda **kw: jax.grad(  # noqa: E731
        lambda *a: chunk_kda(*a, **kw).sum(), argnums=(0, 1, 2, 3, 4))

    def tier_of(args, **kw):
        tel.reset()
        names = lowered_primitives(grad(**kw), *args)
        tiers = sorted(k for k in tel.scalars() if k.startswith(
            "gauge/kda/tier."))
        assert len(tiers) == 1 and tel.counter_value("kda/calls") == 1
        tier = tiers[0].rsplit(".", 1)[1]
        # the norm's layout follows the tier
        assert [k for k in tel.scalars() if k.startswith(
            "gauge/kda/qk_norm.")] == ["gauge/kda/qk_norm." + (
                "flat" if tier == "pallas" else "heads")]
        return tier, names

    wide, narrow = inputs(1, 1, 128, 2, 128), inputs(1, 1, 128, 2, 32)
    tier, names = tier_of(wide)
    assert tier == "pallas" and "pallas_call" in names
    # (the products left are the norm's head sums)
    assert not names & {"scan", "while", "cumsum", "exp"}
    assert tier_of(wide, checkpoint=False)[0] == "pallas"
    tier, names = tier_of(narrow)
    assert tier == "xla" and "scan" in names and "pallas_call" not in names
    assert tier_of(inputs(1, 1, 128, 2, 128), chunk=256)[0] == "xla"
    # q and k may come in another float dtype than v's: the rule runs in v's
    mixed = (wide[0].astype(jnp.bfloat16),) + wide[1:]
    assert tier_of(mixed)[0] == "pallas"
    half = wide[:2] + (wide[2].astype(jnp.float16),) + wide[3:]
    assert tier_of(half)[0] == "xla"
    monkeypatch.setattr(linear_attention, "_on_tpu", lambda: False)
    tier, names = tier_of(wide)
    assert tier == "xla" and "pallas_call" not in names
    tel.reset()


def test_the_cpu_takes_the_xla_form():
    """Unsteered: this process has no TPU, so every call is XLA's."""
    assert linear_attention._tier(*inputs(1, 1, 64, 2, 128)[1:3], 64) == "xla"
    assert not linear_attention._INTERPRET


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
    assert tel.scalars()["gauge/kda/tier.xla"] == 0
    assert tel.scalars()["gauge/kda/qk_norm.heads"] == 0
    assert "gauge/kda/qk_norm.flat" not in tel.scalars()
    tel.reset()
