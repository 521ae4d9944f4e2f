"""What the plain references share: jax.numpy in float32, a plain Adam, and
the readings the comparison takes. Nothing here imports the program.

A reference module (``gpt2.py``, ``bert.py``) gives ``param_specs(config)``,
``denominators(batch)`` and ``block_loss(params, block, denoms, config,
einsum)``; this file turns those into three training steps and their
readings. Parameters are held one leaf per kind of weight, the layers of a
kind stacked on a leading axis, so that the blocks run as one ``lax.scan``
(one layer's compile) and a reading "by leaf" is a norm over all axes but
the first.

``precision`` is the one knob, and it exists for the control: ``float32``
is the reference (every matmul at ``highest``), ``float8`` computes every
matmul on operands and output gradients rounded to 8-bit floats, the step
below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
PRECISIONS = ("float32", "bfloat16", "float8")


def seed_key(seed: int):
    """A PRNG key from any whole number, also past 32 signed bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


# -- weights from the seed ---------------------------------------------------
# a spec is (shape, ("normal", std) | ("ones",) | ("zeros",), stacked)

def init_leaf(specs: dict, name: str, key):
    """One leaf, the same whether it is made alone or with the others."""
    shape, how, _ = specs[name]
    if how[0] == "normal":
        k = jax.random.fold_in(key, sorted(specs).index(name))
        return how[1] * jax.random.normal(k, shape, F32)
    return (jnp.ones if how[0] == "ones" else jnp.zeros)(shape, F32)


def init_params(specs: dict, seed: int) -> dict:
    """Every leaf on the device in one jitted call."""
    make = jax.jit(lambda key: {n: init_leaf(specs, n, key) for n in specs})
    return make(seed_key(seed))


# -- arithmetic --------------------------------------------------------------

def _fake_quant(x, dtype, top):
    scale = jnp.max(jnp.abs(x)) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _q8(x):
    return _fake_quant(x, jnp.float8_e4m3fn, 448.0)


_q8.defvjp(lambda x: (_q8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _q8_grad(x):
    return x


_q8_grad.defvjp(lambda x: (x, None),
                lambda _, g: (_fake_quant(g, jnp.float8_e5m2, 57344.0),))


def make_einsum(precision: str):
    """``einsum(spec, a, b)`` in the given precision, float32 out."""
    hi = jax.lax.Precision.HIGHEST
    if precision == "float32":
        return lambda s, a, b: jnp.einsum(s, a, b, precision=hi)
    if precision == "bfloat16":
        return lambda s, a, b: jnp.einsum(
            s, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=F32)
    if precision == "float8":
        # per-tensor scaled e4m3 operands, e5m2 output gradients: the
        # usual 8-bit training recipe, accumulated in float32
        return lambda s, a, b: _q8_grad(
            jnp.einsum(s, _q8(a), _q8(b), precision=hi))
    raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(einsum, q, k, v, bias):
    """softmax(q k^T / sqrt(d) + bias) v on [b, l, heads, d] operands."""
    s = einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(q.shape[-1]) + bias
    return einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)


def cross_entropy_sum(logits, labels):
    """Sum of -log softmax(logits)[label] over the labels >= 0."""
    valid = labels >= 0
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.sum(jnp.where(valid, lse - picked, 0.0))


# -- readings ----------------------------------------------------------------

def leaf_norms(tree: dict, specs: dict) -> dict:
    """The L2 norm of every leaf: one number a layer for a stacked kind."""
    out = {}
    for name, x in tree.items():
        x = x.astype(F32)
        axes = tuple(range(1, x.ndim)) if specs[name][2] else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes)).reshape(-1)
    return out


def change_norm(specs: dict, name: str, now, seed: int):
    """Norms of ``now - (the leaf as the seed made it)``."""
    return _change_norm(specs_key(specs), name, now, seed_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _change_norm(frozen_specs, name, now, key):
    specs = dict(frozen_specs)
    diff = now.astype(F32) - init_leaf(specs, name, key)
    return leaf_norms({name: diff}, specs)[name]


def specs_key(specs: dict):
    return tuple(sorted(specs.items()))


# -- three steps -------------------------------------------------------------

def loss_and_grads(ref, config, einsum, params, batch, rows_per_block):
    """Loss and gradients of one whole batch, taken ``rows_per_block`` rows
    at a time so that float32 activations fit beside the train state."""
    denoms = ref.denominators(batch)
    rows = jax.tree_util.tree_leaves(batch)[0].shape[0]
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((rows // rows_per_block, rows_per_block)
                            + a.shape[1:]), batch)
    part = jax.value_and_grad(
        lambda p, blk: ref.block_loss(p, blk, denoms, config, einsum))

    def body(carry, blk):
        loss, grads = part(params, blk)
        return (carry[0] + loss,
                jax.tree_util.tree_map(jnp.add, carry[1], grads)), None

    zero = (jnp.zeros((), F32), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, zero, blocks)
    return loss, grads


def adam_update(opt: dict, params, grads, m, v, t):
    """Adam as Kingma and Ba's section 2 closes it (the step size carries
    both bias corrections, epsilon is not rescaled), with AdamW's decoupled
    decay on every leaf where ``weight_decay`` is set."""
    b1, b2, lr = opt["beta1"], opt["beta2"], opt["lr"]
    lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    keep = 1.0 - lr * opt.get("weight_decay", 0.0)
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v,
                               grads)
    params = jax.tree_util.tree_map(
        lambda p, a, c: p * keep - lr_t * a / (jnp.sqrt(c) + opt["epsilon"]),
        params, m, v)
    return params, m, v


def three_steps(ref, config, opt, seed, batches, precision="float32",
                rows_per_block=1):
    """Follow the first steps of training from the seed's weights.

    Returns ``{"losses": [...], "grad_norms": {leaf: [...]},
    "change_norms": {leaf: [...]}}``: each step's loss, the norm of every
    leaf of the first gradient, and of every leaf's change over the steps.
    """
    specs = ref.param_specs(config)
    einsum = make_einsum(precision)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, batch):
        loss, grads = loss_and_grads(ref, config, einsum, params, batch,
                                     rows_per_block)
        params, m, v = adam_update(opt, params, grads, m, v, t)
        return params, m, v, loss, leaf_norms(grads, specs)

    params = init_params(specs, seed)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        batch = jax.tree_util.tree_map(jnp.asarray, batch)
        params, m, v, loss, norms = step(params, m, v,
                                         jnp.asarray(t, F32), batch)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = jax.device_get(norms)
    change = {n: jax.device_get(change_norm(specs, n, params[n], seed))
              for n in specs}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
