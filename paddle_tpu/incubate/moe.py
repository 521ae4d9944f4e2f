"""Mixture-of-Experts with expert parallelism over an 'ep' mesh axis.

Two layers. ``DroplessMoE`` is the one models use (``text/models/
kimi_linear.py``): it is told which of the routed experts it holds,
scores all of them, drops nothing, computes its own experts' part of the
result through one grouped matrix product a projection
(``jax.lax.ragged_dot`` over the token-expert pairs sorted by expert) and
adds a shared expert. The GShard dense-dispatch functions below it
(``top_k_gating``, ``moe_dispatch``, ``ExpertMLP``, ``MoELayer``:
capacity dropping, softmax gate, GELU experts, [T, E, C] one-hot tensors)
remain for ``tests/test_moe.py``; no model uses them.

The GShard layer, as first written: the reference snapshot predates its
MoE work (SURVEY.md §2: EP-precursor —
none), so this is net-new capability, designed TPU-first rather than ported:
the Mesh-TensorFlow/GShard dense-dispatch formulation — gate → top-k →
dispatch einsum → per-expert FFN on stacked weights → combine einsum — which
XLA partitions cleanly: sharding the expert axis of the stacked weights and
dispatched activations over 'ep' makes the dispatch/combine einsums lower to
all-to-alls on ICI, with no hand-written routing code.

Components:
- ``top_k_gating``      — softmax gate, top-k selection, capacity dropping,
                          load-balance aux loss (GShard eq. 4).
- ``moe_dispatch``      — build dispatch/combine tensors.
- ``ExpertMLP``         — stacked per-expert FFN ([E, ...] weights carrying
                          tp_spec ('ep', ...) so fleet engines shard them).
- ``MoELayer``          — drop-in FFN replacement (eager Layer API).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor, _is_tracer, apply_op, wrap_raw
from ..nn import initializer as I

__all__ = ["top_k_gating", "moe_dispatch", "ExpertMLP", "MoELayer",
           "DroplessMoE", "route_top_k", "held_experts_part", "held_load",
           "identity_experts_part", "publish_moe_stats"]


def top_k_gating(gate_logits, top_k: int, capacity: int):
    """Returns (combine_weights [T, E, C], dispatch_mask [T, E, C], aux_loss).

    GShard-style: softmax over experts, top-k per token, position-in-expert
    by cumulative sum, tokens beyond ``capacity`` dropped (their combine
    weight is 0 → the residual connection carries them). Pure jnp; vmappable
    and shardable.
    """
    t, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    combine = jnp.zeros((t, e, capacity), jnp.float32)
    dispatch = jnp.zeros((t, e, capacity), bool)
    # occupancy per expert accumulates across the k routing rounds
    occupancy = jnp.zeros((e,), jnp.int32)
    masked = probs
    density_frac = jnp.zeros((e,), jnp.float32)

    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)                      # [T]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)       # [T, E]
        # position of each token inside its chosen expert's buffer
        pos_in_round = jnp.cumsum(onehot, axis=0) - onehot      # [T, E]
        pos = (pos_in_round + occupancy[None, :]) * onehot
        pos_tok = jnp.sum(pos, axis=-1)                        # [T]
        keep = pos_tok < capacity
        w = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]  # [T]
        w = jnp.where(keep, w, 0.0)
        pos_clip = jnp.minimum(pos_tok, capacity - 1)
        cap_onehot = jax.nn.one_hot(pos_clip, capacity, dtype=jnp.float32)
        contrib = (onehot.astype(jnp.float32)[:, :, None]
                   * cap_onehot[:, None, :]) * w[:, None, None]
        combine = combine + contrib
        dispatch = dispatch | (contrib > 0)
        occupancy = occupancy + jnp.sum(onehot * keep[:, None].astype(jnp.int32),
                                        axis=0)
        density_frac = density_frac + jnp.mean(onehot.astype(jnp.float32),
                                               axis=0)
        masked = jnp.where(onehot.astype(bool), -jnp.inf, masked)

    # renormalize the k selected weights per token (top2 gating convention)
    denom = jnp.maximum(combine.sum(axis=(1, 2)), 1e-9)
    combine = combine / denom[:, None, None]
    dispatch = combine > 0

    # load-balance loss: E * mean_e(density * mean-gate-prob) (GShard eq. 4)
    density = density_frac / top_k
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * mean_prob)
    return combine, dispatch, aux


def moe_dispatch(x, dispatch):
    """x: [T, D], dispatch: [T, E, C] → expert inputs [E, C, D]."""
    return jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)


class ExpertMLP(nn.Layer):
    """E parallel FFNs as stacked weights [E, d, ff] / [E, ff, d] with
    tp_spec ('ep', …): fleet engines shard the expert axis, so each ep rank
    holds E/ep experts and the dispatch/combine einsums become all-to-alls."""

    def __init__(self, num_experts: int, d_model: int, d_ff: int,
                 activation: str = "gelu"):
        super().__init__()
        std = 0.02
        init = I.Normal(0.0, std)
        self.w_in = self.create_parameter(
            [num_experts, d_model, d_ff], default_initializer=init)
        self.b_in = self.create_parameter(
            [num_experts, 1, d_ff], default_initializer=I.Constant(0.0))
        self.w_out = self.create_parameter(
            [num_experts, d_ff, d_model], default_initializer=init)
        self.b_out = self.create_parameter(
            [num_experts, 1, d_model], default_initializer=I.Constant(0.0))
        for p in (self.w_in, self.b_in, self.w_out, self.b_out):
            p.tp_spec = ("ep",) + (None,) * (len(p.shape) - 1)
        self._act = activation

    def forward(self, expert_in):
        """expert_in: [E, C, D] → [E, C, D]; one batched MXU matmul pair."""

        def f(xe, wi, bi, wo, bo):
            h = jnp.einsum("ecd,edf->ecf", xe, wi) + bi
            h = jax.nn.gelu(h, approximate=True) if self._act == "gelu" else (
                jnp.maximum(h, 0))
            return jnp.einsum("ecf,efd->ecd", h, wo) + bo

        return apply_op(f, expert_in, self.w_in, self.b_in, self.w_out,
                        self.b_out, op_name="expert_mlp")


class MoELayer(nn.Layer):
    """Drop-in FFN replacement: ``y = combine(experts(dispatch(x)))``.

    Aux (load-balance) loss: in eager mode it lands on ``self.aux_loss``
    after each forward — add ``layer.aux_loss * coeff`` to the loss. Under
    jit/fleet engines a side-effect attribute cannot carry a traced value
    out (it would leak the tracer), so ``self.aux_loss`` stays None there;
    jitted training must call :meth:`forward_with_aux` and fold the returned
    aux into the loss functionally.
    """

    def __init__(self, d_model: int, d_ff: int, num_experts: int,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 activation: str = "gelu", gate_noise: float = 0.0):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.gate = nn.Linear(d_model, num_experts, bias_attr=False)
        self.experts = ExpertMLP(num_experts, d_model, d_ff, activation)
        self.aux_loss = None

    def forward(self, x):
        """x: [B, L, D] (or [T, D]) → same shape."""
        out, aux = self.forward_with_aux(x)
        # only a concrete value may live on the layer (a tracer stored here
        # would escape its trace and error on any later access)
        self.aux_loss = None if _is_tracer(aux._value) else aux
        return out

    def forward_with_aux(self, x):
        """Functional form for jitted training: returns (out, aux_loss)."""
        orig_shape = x.shape
        d = orig_shape[-1]
        t = int(np.prod(orig_shape[:-1]))
        cap = max(1, int(math.ceil(
            self.capacity_factor * self.top_k * t / self.num_experts)))
        flat = x.reshape([t, d])
        logits = self.gate(flat)

        def route(flat_raw, logits_raw):
            combine, dispatch, aux = top_k_gating(
                logits_raw, self.top_k, cap)
            expert_in = moe_dispatch(flat_raw, dispatch)
            return expert_in, combine.astype(flat_raw.dtype), aux

        expert_in, combine, aux = apply_op(route, flat, logits,
                                           multi_out=True, op_name="moe_route")
        expert_out = self.experts(expert_in)

        def unroute(eo, comb):
            return jnp.einsum("ecd,tec->td", eo, comb)

        out = apply_op(unroute, expert_out, combine, op_name="moe_combine")
        return out.reshape(list(orig_shape)), aux


# ---------------------------------------------------------------------------
# The dropless layer: held experts, sigmoid router, grouped product
# ---------------------------------------------------------------------------
def route_top_k(scores, select_bias, top_k: int, scale: float,
                renormalize: bool = True):
    """(chosen experts [T, k], their weights [T, k] in f32) from the
    router's scores s [T, E] (a sigmoid's or a softmax's of its logits,
    over every column it routes to): the ``top_k`` largest of
    s + ``select_bias`` are chosen (the bias steers the choice only: it is
    moved by a load-balancing rule outside the gradient), and a chosen
    expert weighs ``scale`` * s, over the sum of the chosen s where
    ``renormalize``."""
    scores = scores.astype(jnp.float32)
    _, chosen = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, scale * picked


@jax.custom_vjp
def _to_pairs(x, order, inverse, here):
    """Row ``order[p] // k`` of ``x`` [T, h] for every sorted pair p: the
    tokens as the experts want them. A gather; and so is its backward (the
    pairs of a token lie at ``inverse[t k : (t + 1) k]``), where autodiff
    would scatter-add. Pairs whose expert is absent bring no gradient."""
    return x[order // (order.shape[0] // x.shape[0])]


def _to_pairs_fwd(x, order, inverse, here):
    return _to_pairs(x, order, inverse, here), (inverse, here, x.shape)


def _to_pairs_bwd(res, g):
    inverse, here, (t, h) = res
    back = jnp.where(here[:, None], g[inverse], 0).reshape(t, -1, h)
    dx = back.sum(axis=1, dtype=jnp.float32).astype(g.dtype)
    return dx, None, None, None


_to_pairs.defvjp(_to_pairs_fwd, _to_pairs_bwd)


@jax.custom_vjp
def _from_pairs(ys, order, inverse):
    """The sorted pairs' rows back in token order, [T k, h]: a
    permutation, whose backward is the inverse permutation (a gather
    again)."""
    return ys[inverse]


_from_pairs.defvjp(lambda ys, order, inverse: (ys[inverse], order),
                   lambda order, g: (g[order], None, None))


def _sort_by_group(key, groups: int):
    """The stable sort of ``key`` (ints in [0, groups), P of them) without
    a sort: (order, inverse, sizes) with key[order] ascending, inverse its
    inverse permutation and sizes the count of every group. A pair's place
    is its group's offset plus its rank among its group's pairs (a running
    count a group); the pair at a place comes from one scatter of P
    integers to places that all differ. On the v5e, for 65,536 pairs:
    0.95 ms and a second of the compiler's time, against 0.4 ms and 10 to
    48 s for ``argsort`` (twice a layer), and 10 ms for a binary search in
    the running counts (PERF.md section 6 "PR 30")."""
    p = key.shape[0]
    member = (key[None, :] == jnp.arange(groups, dtype=key.dtype)[:, None])
    rank = jnp.cumsum(member.astype(jnp.int32), axis=1)    # [groups, P]
    sizes = rank[:, -1]
    offset = jnp.cumsum(sizes) - sizes
    at = jnp.arange(p, dtype=jnp.int32)
    inverse = (offset[:, None] + rank)[key, at] - 1
    order = jnp.zeros((p,), jnp.int32).at[inverse].set(
        at, unique_indices=True)
    return order, inverse, sizes


def held_experts_part(x, chosen, weights, w_gate, w_up, w_down, first: int):
    """What the experts held here add to every token: sum over the chosen
    experts e in [first, first + E_held) of w_e * down_e(silu(gate_e x) *
    up_e x). ``x`` [T, h]; ``chosen``, ``weights`` [T, k]; the stacks
    [E_held, h, f], [E_held, h, f], [E_held, f, h]. Returns (y [T, h] in
    f32, stats f32[3] = the share of all T * k pairs that is routed here,
    the fullest held expert's load over the mean load, pairs dropped).

    All T * k token-expert pairs are sorted by expert, those of absent
    experts last; the held ones go through one ``jax.lax.ragged_dot`` a
    projection, which works on the rows its groups cover and leaves the
    rest alone, and come back by the inverse permutation to be weighed
    and summed a token. The pair buffer has a row for every pair, so
    nothing is ever dropped, whatever the router does."""
    t, k = chosen.shape
    held = w_gate.shape[0]
    local = chosen.astype(jnp.int32) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(-1)        # absent: last
    order, inverse, sizes = _sort_by_group(key, held + 1)
    sizes = sizes[:held]
    xs = _to_pairs(x, order, inverse, here.reshape(-1))
    grouped = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
        a, w.astype(a.dtype), sizes, preferred_element_type=a.dtype)
    ys = grouped(jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up),
                 w_down)
    # rows of absent pairs hold nothing that was computed: never read them
    back = jnp.where(here[..., None],
                     _from_pairs(ys, order, inverse).reshape(t, k, -1), 0)
    y = jnp.sum(back.astype(jnp.float32)
                * jnp.where(here, weights, 0.0)[..., None], axis=1)
    load = sizes.astype(jnp.float32)
    pairs = jnp.sum(sizes)
    stats = jnp.stack([
        pairs.astype(jnp.float32) / (t * k),
        jnp.max(load) / jnp.maximum(jnp.mean(load), 1.0),
        (jnp.sum(here) - pairs).astype(jnp.float32)])
    return y, jax.lax.stop_gradient(stats)


def identity_experts_part(x, chosen, weights, first: int):
    """What the zero-computation experts add to every token: sum over the
    chosen experts e >= ``first`` (the router's columns past the routed
    experts) of w_e * x, an identity expert's output being its input.
    ``x`` [T, h]; ``chosen``, ``weights`` [T, k]. Returns (y [T, h] in f32,
    int32 pairs routed to them). No exchange: every token's identity
    experts are computed where the token is; ids below ``first`` (a routed
    expert, or -1 for a position that is not there) add nothing."""
    zero = chosen >= first
    w = jnp.sum(jnp.where(zero, weights.astype(jnp.float32), 0.0), axis=-1)
    return (w[:, None] * x.astype(jnp.float32),
            jnp.sum(zero).astype(jnp.int32))


def held_load(chosen, first: int, held: int):
    """int32[2] from the routing ``chosen`` [T, k]: how many of the
    experts [first, first + held) received at least one pair, and how many
    pairs were routed to them. What a served step counts (a held expert
    without a pair has no weights to read); pairs of ids outside all
    experts (a position that is not there: -1) count nowhere."""
    local = chosen.astype(jnp.int32).reshape(-1) - first
    mine = local[None, :] == jnp.arange(held, dtype=jnp.int32)[:, None]
    return jnp.stack([jnp.sum(jnp.any(mine, axis=1)),
                      jnp.sum(mine)]).astype(jnp.int32)


class DroplessMoE(nn.Layer):
    """One chip's share of a routed expert layer, plus the shared expert.

    ``num_experts`` is what the router scores (all of them, on every
    chip); ``experts_held`` (a ``range``) are the ones whose weights live
    here. ``forward`` returns the held experts' part of sum_i w_i E_i(x)
    plus ``shared_experts`` gated MLPs of the same width applied to every
    token. What the absent experts would add is another chip's to compute
    and an all-to-all's to bring: this layer holds no stand-in for either.
    Routing: ``route_top_k`` (sigmoid scores, a selection bias buffer that
    no gradient moves, weights renormalised and scaled).

    The expert stacks carry ``tp_spec ('ep', ...)``. ``stats`` (a buffer,
    so that a compiled step carries it without a fetch) holds the last
    forward's share of the pairs, load imbalance and dropped pairs;
    ``publish_moe_stats`` turns it into telemetry when asked.
    """

    def __init__(self, d_model: int, d_ff: int, num_experts: int,
                 experts_held=None, top_k: int = 8, scale: float = 1.0,
                 renormalize: bool = True, shared_experts: int = 1,
                 weight_attr=None):
        super().__init__()
        held = range(num_experts) if experts_held is None else experts_held
        if (held.step != 1 or held.start < 0 or held.stop > num_experts
                or not len(held)):
            raise ValueError(f"experts_held {held!r} is no run of the "
                             f"{num_experts} routed experts")
        self.num_experts, self.experts_held = num_experts, held
        self.top_k, self.scale, self.renormalize = top_k, scale, renormalize
        self.gate = nn.Linear(d_model, num_experts, weight_attr,
                              bias_attr=False)
        init = (weight_attr.initializer if weight_attr is not None
                else I.Normal(0.0, 0.02))
        n = len(held)
        self.w_gate = self.create_parameter([n, d_model, d_ff],
                                            default_initializer=init)
        self.w_up = self.create_parameter([n, d_model, d_ff],
                                          default_initializer=init)
        self.w_down = self.create_parameter([n, d_ff, d_model],
                                            default_initializer=init)
        for p in (self.w_gate, self.w_up, self.w_down):
            p.tp_spec = ("ep", None, None)
        self.shared = (nn.SwiGLU(d_model, d_ff * shared_experts, weight_attr)
                       if shared_experts else None)
        self.register_buffer("select_bias", wrap_raw(
            jnp.zeros([num_experts], jnp.float32)))
        self.register_buffer("stats", wrap_raw(jnp.zeros([3], jnp.float32)))
        from ..profiler.telemetry import get_telemetry

        get_telemetry().gauge("moe/experts_held", n)

    def forward(self, x):
        """x: [B, L, D] (or [T, D]) -> same shape."""
        shape = x.shape
        flat = x.reshape([-1, shape[-1]])
        first, top_k = self.experts_held.start, self.top_k
        scale, renorm = self.scale, self.renormalize

        def routed(flat_raw, logits, bias, w_gate, w_up, w_down):
            chosen, weights = route_top_k(jax.nn.sigmoid(
                logits.astype(jnp.float32)), bias, top_k, scale, renorm)
            y, stats = held_experts_part(flat_raw, chosen, weights, w_gate,
                                         w_up, w_down, first)
            return y.astype(flat_raw.dtype), stats

        with jax.named_scope("moe"):
            y, stats = apply_op(
                routed, flat, self.gate(flat), self.select_bias.detach(),
                self.w_gate, self.w_up, self.w_down, multi_out=True,
                op_name="dropless_moe")
        self.stats._value = stats._value
        if self.shared is not None:
            y = y + self.shared(flat)
        return y.reshape(list(shape))


def publish_moe_stats(layer, telemetry=None) -> dict:
    """Read the ``stats`` buffer of every ``DroplessMoE`` under ``layer``
    (after ``sync_to_layer()`` where an engine holds the buffers) and set
    ``gauge/moe/pairs_here_share``, ``gauge/moe/load_max_over_mean`` (the
    worst layer's) and ``counter/moe/dropped_pairs``. This is the caller's
    fetch, made when it logs; a step makes none."""
    from ..profiler.telemetry import get_telemetry

    tel = telemetry or get_telemetry()
    out = {}
    for name, sub in layer.named_sublayers(include_self=True):
        if isinstance(sub, DroplessMoE):
            share, imbalance, dropped = (float(v) for v in np.asarray(
                sub.stats._value))
            out[name] = {"pairs_here_share": share,
                         "load_max_over_mean": imbalance,
                         "dropped_pairs": dropped}
    if out:
        tel.gauge("moe/pairs_here_share",
                  sum(v["pairs_here_share"] for v in out.values()) / len(out))
        tel.gauge("moe/load_max_over_mean",
                  max(v["load_max_over_mean"] for v in out.values()))
        tel.counter("moe/dropped_pairs",
                    int(sum(v["dropped_pairs"] for v in out.values())))
    return out
