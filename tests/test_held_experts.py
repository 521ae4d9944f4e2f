"""The two forms of ``incubate.moe.held_experts_part`` on the CPU.

``_batched_part`` (every held expert over every row, one batched product a
projection) is what a step of at most ``_RIDGE_ROWS`` rows takes, a served
decode step; ``_grouped_part`` (the pairs sorted by expert through
``jax.lax.ragged_dot``) what a longer one takes, a prefill chunk or a
training step. Both are held to each other on one routing: rows routed
nowhere (-1), an expert that every row chose, experts without a pair, and
no pair here at all. Both round alike (the products in x's type, the
weighing and the sum in f32), so they part by the order of their f32 sums
alone: the shares and loads in ``stats`` are counts, and equal.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate import moe
from paddle_tpu.incubate.moe import (DroplessMoE, held_experts_part,
                                     route_top_k)
from paddle_tpu.jit.functionalize import functionalize, get_params
from paddle_tpu.profiler import get_telemetry

H, F, ROUTED, HELD, FIRST, TOP_K = 32, 16, 16, 4, 4, 4
# worst gap over the largest output: f32 by the order of sums alone (a
# few 1e-7 measured); bf16 by one bf16 rounding at most (none measured)
TOL = {"float32": 1e-5, "bfloat16": 2.0 ** -8}


def normal(seed, *shape, std=1.0):
    return std * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                   jnp.float32)


def stacks(dtype, held=HELD):
    return tuple(normal(s, held, *shape, std=0.3).astype(dtype)
                 for s, shape in ((5, (H, F)), (6, (H, F)), (7, (F, H))))


def routing(case, t):
    """(chosen [t, k], weights [t, k]) from sigmoid scores, then bent to
    ``case``."""
    scores = jax.nn.sigmoid(normal(1, t, ROUTED))
    bias = jnp.zeros(ROUTED)
    if case == "one_expert_hit_by_every_row":
        bias = bias.at[FIRST + 2].set(10.0)
    elif case == "experts_without_a_pair":
        bias = bias.at[FIRST + 1].set(-10.0).at[FIRST + 3].set(-10.0)
    elif case == "no_pair_here":
        bias = bias.at[FIRST:FIRST + HELD].set(-10.0)
    chosen, weights = route_top_k(scores, bias, TOP_K, 2.446)
    if case == "rows_routed_nowhere":
        nowhere = (jnp.arange(t) % 3 == 0)[:, None]
        chosen = jnp.where(nowhere, -1, chosen)
    return chosen, weights


CASES = ["rows_routed_nowhere", "one_expert_hit_by_every_row",
         "experts_without_a_pair", "no_pair_here"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_batched_form_gives_what_the_grouped_form_gives(case, dtype):
    t = 40
    x = normal(0, t, H).astype(dtype)
    chosen, weights = routing(case, t)
    local = np.asarray(chosen) - FIRST
    here = (local >= 0) & (local < HELD)
    load = [int(np.sum(local == e)) for e in range(HELD)]
    if case == "rows_routed_nowhere":
        assert np.all(np.asarray(chosen)[::3] == -1) and here.any()
    elif case == "one_expert_hit_by_every_row":
        assert load[2] == t
    elif case == "experts_without_a_pair":
        assert load[1] == load[3] == 0 and load[0] and load[2]
    else:
        assert not here.any()
    w = stacks(dtype)
    got, got_stats = jax.jit(moe._batched_part, static_argnums=6)(
        x, chosen, weights, *w, FIRST)
    want, want_stats = jax.jit(moe._grouped_part, static_argnums=6)(
        x, chosen, weights, *w, FIRST)
    assert got.dtype == want.dtype == jnp.float32
    scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
    assert float(jnp.max(jnp.abs(got - want))) / scale <= TOL[dtype]
    if not here.any():
        assert float(jnp.max(jnp.abs(got))) == 0.0
    # the share here, the load's max over its mean and the dropped pairs
    # are counts: equal, not close
    np.testing.assert_array_equal(np.asarray(got_stats),
                                  np.asarray(want_stats))
    assert float(got_stats[0]) == pytest.approx(here.sum() / (t * TOP_K))
    assert float(got_stats[2]) == 0.0


def test_a_row_weighs_only_the_experts_it_chose():
    """One row, two held experts chosen of four, and the layer's output
    by hand: w_a E_a(x) + w_b E_b(x)."""
    x = normal(0, 1, H)
    chosen = jnp.asarray([[FIRST + 1, 0, FIRST + 3, ROUTED - 1]])
    weights = jnp.asarray([[0.5, 0.7, 0.25, 0.1]], jnp.float32)
    w_gate, w_up, w_down = stacks("float32")
    with jax.default_matmul_precision("highest"):
        got, _ = moe._batched_part(x, chosen, weights, w_gate, w_up, w_down,
                                   FIRST)
        want = sum(
            w * (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
            for e, w in ((1, 0.5), (3, 0.25)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


# -- which form a shape takes ---------------------------------------------------

def primitives(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("rows, batched", [
    (1, True), (64, True), (128, True), (moe._RIDGE_ROWS, True),
    (moe._RIDGE_ROWS + 1, False), (512, False)])
def test_the_rows_choose_the_form(rows, batched):
    x = normal(0, rows, H)
    chosen, weights = routing("experts_without_a_pair", rows)
    w = stacks("float32")
    text = primitives(lambda *a: held_experts_part(*a, FIRST), x, chosen,
                      weights, *w)
    assert ("ragged_dot" in text) is not batched, rows


def test_the_ridge_is_the_v5es():
    """197e12 bf16 operations a second over 819e9 bytes a second."""
    assert moe._RIDGE_ROWS == int(197e12 / 819e9)


def test_kimis_training_step_still_takes_the_grouped_form():
    """An 8,192-row ``DroplessMoE`` forward, the Kimi cell's tokens a step,
    at a tiny width."""
    layer = DroplessMoE(H, F, ROUTED, experts_held=range(FIRST, FIRST + HELD),
                        top_k=TOP_K, scale=2.446)
    apply = functionalize(layer)
    buffers = {n: b._value for n, b in layer.named_buffers()}
    text = primitives(lambda x: apply(get_params(layer), buffers, x)[0],
                      jnp.zeros((8192, H), jnp.float32))
    assert "ragged_dot" in text


def test_the_grouped_forms_backward_still_runs():
    """Above the ridge, at a tiny width: the gradients of the grouped form
    (with the gathers' own backward) against the batched form's, which
    autodiff makes."""
    t = moe._RIDGE_ROWS + 16
    x = normal(0, t, H)
    router = normal(1, H, ROUTED, std=0.3)
    w = stacks("float32")
    ct = normal(9, t, H)

    def loss(form):
        def f(x, router, w):
            chosen, weights = route_top_k(jax.nn.sigmoid(x @ router),
                                          jnp.zeros(ROUTED), TOP_K, 2.446)
            return jnp.sum(ct * form(x, chosen, weights, *w, FIRST)[0])
        return f

    with jax.default_matmul_precision("highest"):
        assert "ragged_dot" in primitives(
            jax.grad(loss(held_experts_part), argnums=(0, 1, 2)), x, router,
            w)
        got = jax.grad(loss(held_experts_part), argnums=(0, 1, 2))(
            x, router, w)
        want = jax.grad(loss(moe._batched_part), argnums=(0, 1, 2))(
            x, router, w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-5


def test_the_form_is_published_once_a_shape():
    tel = get_telemetry()
    tel.reset()
    w = stacks("float32")
    for rows in (64, 512, 64):
        chosen, weights = routing("rows_routed_nowhere", rows)
        jax.make_jaxpr(lambda *a: held_experts_part(*a, FIRST))(
            normal(0, rows, H), chosen, weights, *w)
    forms = {k: v for k, v in tel.snapshot()["gauges"].items()
             if k.startswith("moe/form.")}
    assert forms == {"moe/form.r64": 1, "moe/form.r512": 0}
    tel.reset()
