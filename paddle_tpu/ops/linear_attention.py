"""Linear attention with a recurrent state: the gated delta rule with a
per-channel decay (Kimi Delta Attention), the scalar-decay state-space
recurrence of Mamba-2 (``chunk_ssd``, ``ssd_step``: at the end of this
file), and the short causal convolution that feeds either. ``chunk_kda`` is one algorithm on two lowerings, chosen by
rule (``_tier``): on a TPU, at head widths that are multiples of 128, the
Pallas kernel pair of ``ops/kda_tpu.py``, which holds a chunk's
intermediates in VMEM, forward and hand-written backward; everywhere else
plain XLA (``_chunk_kda``: batched matmuls inside chunks, one ``lax.scan``
over the chunks for the state, autodiff), which is also the definition the
kernels are tested against.

The recurrence, per head, with S in R^{dk x dv} and S_0 = 0:

    S~  = Diag(exp(g_t)) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

where q_t and k_t are what the caller hands over, each over its L2 norm
across a head's dk, q_t times dk^-1/2 besides. The norm belongs to the
operation, so that each lowering takes it in the layout it computes in: the
XLA form on [b, l, h, dk], the kernels' on [b, l, h dk] (``_unit_flat``),
where a sum over a head is a product with a 0/1 matrix and nothing asks for
the heads as an axis. Either way it is taken in float32 and its result
rounded once, to v's dtype.

``chunk_kda`` computes it a chunk of C tokens at a time. With G the
chunk's running sum of g and u_t = v_t - S~_t^T k_t (what token t writes):

    (I + A Diag(beta)) U = V - (K exp(G)) S_0,
        A[r, i] = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])   for i < r
    O   = (Q exp(G)) S_0 + B (beta U),
        B[r, i] = sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])   for i <= r
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T (beta U)

so that a chunk costs a unit lower-triangular inverse (the WY form) and a
few matmuls, and only S crosses chunks. Every exponent that is taken is
<= 0: A and B are built by sub-blocks, an off-diagonal sub-block through
the decays up to and from the start of its row block, a diagonal one from
the pairwise differences themselves, so that no ``exp(-cumsum g)`` is ever
formed, however fast a channel decays. The state, the decays, A, B and the
inverse are float32; the chunk's matmuls take operands in the inputs'
dtype and accumulate in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["short_conv", "chunk_kda", "chunk_ssd", "ssd_step"]

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_SUB = 16  # rows of a sub-block of A and B, and of the inverse's base case


def short_conv(x, w, tail=None, valid=None):
    """Depthwise causal convolution along the sequence: ``x`` [b, l, c],
    ``w`` [c, k]; y_t = sum_j w[:, j] x_{t-(k-1)+j}, zeros before the
    start (a ``Conv1d(c, c, k, groups=c, padding=k-1)`` cut to l).

    With ``tail`` [b, k-1, c], the inputs that came before ``x`` (a cached
    sequence's carried tail; zeros at its start), they stand before the
    start instead of zeros, and the call returns ``(y, new_tail)``: the
    last k-1 inputs of tail + x, or, with ``valid`` [b] (how many leading
    positions of each row are real), the k-1 inputs before the padding."""
    k = w.shape[-1]
    l = x.shape[1]
    x32, w32 = x.astype(F32), w.astype(F32)
    y = x32 * w32[:, k - 1]
    if tail is None:
        for back in range(1, k):
            shifted = jnp.pad(x32, ((0, 0), (back, 0), (0, 0)))[:, :l]
            y = y + shifted * w32[:, k - 1 - back]
        return y.astype(x.dtype)
    joined = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    before = joined.astype(F32)
    for back in range(1, k):
        y = y + before[:, k - 1 - back:k - 1 - back + l] * w32[:, k - 1 - back]
    if valid is None:
        return y.astype(x.dtype), joined[:, l:]
    at = valid[:, None] + jnp.arange(k - 1, dtype=valid.dtype)
    return y.astype(x.dtype), jnp.take_along_axis(joined, at[..., None],
                                                  axis=1)


def _mm(eq, a, b, dtype):
    """einsum on operands of ``dtype``, accumulated and returned in f32."""
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=F32,
                      precision=_HI if dtype == F32 else None)


def _inv_rows(n):
    """(I + n)^-1 of strictly lower ``n`` [..., s, s] by forward
    substitution, a row at a time: row r = e_r - sum_{i<r} n[r, i] row i
    (the rows not yet made are still the identity's, and n[r, i] = 0
    there)."""
    s = n.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(s, dtype=F32), n.shape)

    def row(inv, r):
        n_r = jax.lax.dynamic_index_in_dim(n, r, axis=-2, keepdims=True)
        e_r = jax.lax.dynamic_index_in_dim(eye, r, axis=-2, keepdims=True)
        new = e_r - jnp.matmul(n_r, inv, precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(inv, new, r, axis=-2), None

    return jax.lax.scan(row, eye, jnp.arange(1, s, dtype=jnp.int32))[0]


def _inv_unit_lower(n, sub):
    """(I + n)^-1 of strictly lower ``n`` [..., c, c], c = sub * 2^j: the
    diagonal blocks by substitution, then pairs of blocks merged,
    [[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]."""
    lead, c = n.shape[:-2], n.shape[-1]
    blocks = c // sub
    if blocks * sub != c or blocks & (blocks - 1):
        raise ValueError(f"chunk {c} is not sub-block {sub} times a power "
                         "of two")
    tiles = n.reshape(lead + (blocks, sub, blocks, sub))
    inv = _inv_rows(jnp.stack([tiles[..., a, :, a, :]
                               for a in range(blocks)], axis=-3))
    size = sub
    while size < c:
        pairs = c // (2 * size)
        inv = inv.reshape(lead + (pairs, 2, size, size))
        top, bottom = inv[..., 0, :, :], inv[..., 1, :, :]
        tiles = n.reshape(lead + (pairs, 2, size, pairs, 2, size))
        below = jnp.stack([tiles[..., p, 1, :, p, 0, :]
                           for p in range(pairs)], axis=-3)
        corner = -jnp.matmul(jnp.matmul(bottom, below, precision=_HI), top,
                             precision=_HI)
        inv = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], axis=-1),
            jnp.concatenate([corner, bottom], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _intra(q, k, G, sub):
    """A (strictly lower) and B (lower, with the diagonal) of every chunk,
    [b, n, h, c, c] in f32, from f32 ``q``, ``k`` and the chunk's running
    sum ``G``, all [b, n, h, c, d]."""
    b, n, h, c, d = k.shape
    blocks = c // sub
    lower = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    strict = jnp.tril(jnp.ones((sub, sub), bool), k=-1)

    @jax.checkpoint
    def diagonal(head):
        """The diagonal sub-blocks of one head, [b, n, blocks, r, i]: exp
        of the pairwise differences themselves. The [.., r, i, d] tensors
        are sub times the inputs' size, so they are made a head at a time
        and made again in the backward."""
        qb, kb, Gb = head
        diff = Gb[..., :, None, :] - Gb[..., None, :, :]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        kd = kb[..., None, :, :] * decay                 # k_i exp(G_r - G_i)
        return (jnp.where(strict, jnp.sum(kb[..., :, None, :] * kd, -1), 0.0),
                jnp.sum(qb[..., :, None, :] * kd, -1))

    heads_first = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape(b, n, h, blocks, sub, d), 2, 0)
    diag_a, diag_b = (jnp.moveaxis(t, 0, 2) for t in jax.lax.map(
        diagonal, (heads_first(q), heads_first(k), heads_first(G))))
    rows_a, rows_b = [], []
    for a in range(blocks):
        lo, hi = a * sub, (a + 1) * sub
        parts_a, parts_b = [diag_a[:, :, :, a]], [diag_b[:, :, :, a]]
        if a:
            # through the start of row block a: both exponents are <= 0
            ref = G[:, :, :, lo - 1:lo]                  # [b, n, h, 1, d]
            into = jnp.exp(G[:, :, :, lo:hi] - ref)
            upto = k[:, :, :, :lo] * jnp.exp(ref - G[:, :, :, :lo])
            eq = "bnhrd,bnhid->bnhri"
            parts_a.insert(0, _mm(eq, k[:, :, :, lo:hi] * into, upto, F32))
            parts_b.insert(0, _mm(eq, q[:, :, :, lo:hi] * into, upto, F32))
        if hi < c:
            zeros = jnp.zeros((b, n, h, sub, c - hi), F32)
            parts_a.append(zeros)
            parts_b.append(zeros)
        rows_a.append(jnp.concatenate(parts_a, axis=-1))
        rows_b.append(jnp.concatenate(parts_b, axis=-1))
    return jnp.concatenate(rows_a, axis=-2), jnp.concatenate(rows_b, axis=-2)


def _whose(heads, d):
    """[heads d, heads], 1 where the column is the head's."""
    return (jnp.arange(heads * d)[:, None] // d
            == jnp.arange(heads)).astype(F32)


def head_sums(x, heads):
    """[..., heads d] f32 -> [..., heads]: the sum over each head's d
    values as a product with ``_whose`` (f32 at ``HIGHEST``, exact to
    float32's rounding). On a TPU a reduction over the last axis of
    [..., heads, d] lays the whole tensor out anew, heads in sublanes, and
    back again; this does not."""
    return jnp.matmul(x, _whose(heads, x.shape[-1] // heads), precision=_HI)


def over_heads(s, d):
    """[..., heads] f32 -> [..., heads d]: each head's value under its d
    columns, ``head_sums``' transpose (exact: one term a column)."""
    return jnp.matmul(s, _whose(s.shape[-1], d).T, precision=_HI)


def _unit(t, eps):
    """t [b, l, h, dk] over its L2 norm a head (``eps`` under the root),
    in f32."""
    t = t.astype(F32)
    return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True) + eps)


def _unit_flat(t, heads, eps):
    """The same on [b, l, h dk]."""
    t = t.astype(F32)
    return t * over_heads(jax.lax.rsqrt(
        head_sums(jnp.square(t), heads) + eps), t.shape[-1] // heads)


def _handed(unit_q, unit_k, dk, dtype):
    """What the rule takes of q and k once they are unit vectors a head:
    both in ``dtype``, q times dk^-1/2 there."""
    return (unit_q.astype(dtype) * jnp.asarray(dk ** -0.5, dtype),
            unit_k.astype(dtype))


def _chunk_kda(q, k, v, g, beta, chunk, sub):
    """The chunked rule on q and k that are normalised already."""
    b, l, h, dk = k.shape
    dtype = q.dtype
    pad = -l % chunk
    if pad:
        # a padded token writes nothing (k = 0, beta = 0) and decays
        # nothing (g = 0): the state passes it unchanged
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad))
                                    + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    n = (l + pad) // chunk
    # [b, n, h, c, d]: the batch axes of every product lead
    q, k, v, g = (t.reshape(b, n, chunk, h, -1).transpose(0, 1, 3, 2, 4)
                  for t in (q, k, v, g))
    beta = beta.astype(F32).reshape(b, n, chunk, h).transpose(0, 1, 3, 2)
    q32, k32 = q.astype(F32), k.astype(F32)
    G = jnp.cumsum(g.astype(F32), axis=3)
    A, B = _intra(q32, k32, G, sub)
    T = _inv_unit_lower(A * beta[:, :, :, None, :], sub)  # [b, n, h, c, c]
    decayed = jnp.exp(G)
    last = G[:, :, :, -1:]
    # chunk-local: what the tokens would write into an empty state, and
    # what of the incoming state they take back
    fresh = _mm("bnhri,bnhiv->bnhrv", T, v, dtype)
    taken = _mm("bnhri,bnhik->bnhrk", T, k32 * decayed, dtype)
    k_end = k32 * jnp.exp(last - G)
    keep = jnp.exp(last[:, :, :, 0])                     # [b, n, h, dk]

    def step(S, xs):
        taken_n, fresh_n, k_end_n, keep_n, beta_n = xs
        u = fresh_n - _mm("bhrk,bhkv->bhrv", taken_n, S, dtype)
        wrote = _mm("bhrk,bhrv->bhkv", k_end_n, beta_n[..., None] * u, dtype)
        return keep_n[..., None] * S + wrote, (S, u)

    chunks_first = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    _, (S, U) = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), F32),
        tuple(chunks_first(t) for t in (taken, fresh, k_end, keep, beta)))
    S, U = jnp.moveaxis(S, 0, 1), jnp.moveaxis(U, 0, 1)
    out = (_mm("bnhrk,bnhkv->bnhrv", q32 * decayed, S, dtype)
           + _mm("bnhri,bnhiv->bnhrv", B, beta[..., None] * U, dtype))
    out = out.transpose(0, 1, 3, 2, 4).reshape(b, l + pad, h, -1)
    return out[:, :l].astype(dtype)


_INTERPRET = False  # a CPU test of the kernels sets it, with ``_on_tpu``


def _on_tpu():
    return jax.default_backend() == "tpu"


def _tier(k, v, chunk):
    """Which lowering a call takes: ``"pallas"`` on a TPU when v (whose
    dtype the rule's operands take) is bfloat16 or float32, dk and dv are
    multiples of 128 (a head's tile is whole lanes) and ``chunk`` is 16,
    32, 64 or 128 (16-row sub-blocks times a power of two, a whole
    bfloat16 tile of rows); ``"xla"`` for every other call. Nothing is
    timed and nothing is read from the machine."""
    fits = (v.dtype in (jnp.bfloat16, jnp.float32)
            and k.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
            and chunk in (16, 32, 64, 128))
    return "pallas" if fits and _on_tpu() else "xla"


def _whole_chunks(chunk, *tensors):
    """Pad [b, l, ...] tensors with zeros to a whole number of chunks: a
    padded token writes nothing, decays nothing and is asked nothing."""
    pad = -tensors[0].shape[1] % chunk
    return [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in tensors]


def _kda_xla(q, k, v, g, beta, eps, chunk):
    q, k = _handed(_unit(q, eps), _unit(k, eps), k.shape[-1], v.dtype)
    return _chunk_kda(q, k, v, g, beta, chunk, min(_SUB, chunk))


def _kernel_forward(inputs, chunk, keep_states):
    from . import kda_tpu

    out, states = kda_tpu.forward(*_whole_chunks(chunk, *inputs), chunk, _SUB,
                                  keep_states, interpret=_INTERPRET)
    return out[:, :inputs[0].shape[1]], states


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernels(q, k, v, g, beta, chunk):
    """The kernel pair on [b, l, h d] tensors, q and k normalised."""
    return _kernel_forward((q, k, v, g, beta), chunk, False)[0]


def _kernels_fwd(q, k, v, g, beta, chunk):
    out, states = _kernel_forward((q, k, v, g, beta), chunk, True)
    return out, (q, k, v, g, beta, states)


def _kernels_bwd(chunk, residuals, d_out):
    from . import kda_tpu

    *inputs, states = residuals
    grads = kda_tpu.backward(*_whole_chunks(chunk, *inputs), states,
                             *_whole_chunks(chunk, d_out), chunk, _SUB,
                             interpret=_INTERPRET)
    return tuple(t[:, :d_out.shape[1]] for t in grads)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _kda_pallas(q, k, v, g, beta, eps, chunk):
    """Everything on [b, l, h d], the kernels' layout and the layer's:
    the norm by products, then the kernel pair."""
    heads = beta.shape[-1]
    q, k = _handed(_unit_flat(q, heads, eps), _unit_flat(k, heads, eps),
                   k.shape[-1] // heads, v.dtype)
    return _kernels(q, k, v, g, beta, chunk)


def chunk_kda(q, k, v, g, beta, eps=1e-6, chunk=64, checkpoint=True):
    """The gated delta rule with a per-channel decay, chunked.

    ``q``, ``k`` [b, l, h, dk] as the layer's convolutions leave them, in
    any float dtype (float32 keeps what the layer computed): the rule runs
    on q / sqrt(sum q^2 + ``eps``) / sqrt(dk) and k / sqrt(sum k^2 +
    ``eps``), the sums over a head's dk, taken in float32 and rounded to
    v's dtype. ``v`` [b, l, h, dv], ``g`` [b, l, h, dk] the log of the
    decay (<= 0), ``beta`` [b, l, h] in (0, 1). Returns o [b, l, h, dv] in
    v's dtype; the state starts at zero. Any length: the tail is padded to
    a whole chunk with tokens that leave the state alone. On the kernels'
    lowering nothing computes on the four axes: a caller whose tensors are
    [b, l, h d] reshapes for free.

    The lowering is chosen by ``_tier``'s rule, and the norm's layout
    follows it. The XLA form's backward is autodiff; the kernels' is
    written by hand and keeps, beside their five inputs, each chunk's
    incoming state. Under ``checkpoint`` (the default) only the five
    inputs are kept and the rest is made again in the backward; a caller
    that recomputes the whole layer anyway passes False.
    """
    from ..profiler.telemetry import get_telemetry
    from .tier_policy import TIER_IDS

    tier = _tier(k, v, chunk)
    # trace-time facts, like attn/calls and attn/tier.*
    tel = get_telemetry()
    tel.counter("kda/calls")
    tel.gauge("kda/chunk", chunk)
    tel.gauge(f"kda/tier.{tier}", TIER_IDS[tier])
    tel.gauge("kda/qk_norm." + ("flat" if tier == "pallas" else "heads"),
              TIER_IDS[tier])
    fn = functools.partial(_kda_pallas if tier == "pallas" else _kda_xla,
                           eps=eps, chunk=chunk)
    if checkpoint:
        fn = jax.checkpoint(fn)
    if tier == "xla":
        return fn(q, k, v, g, beta)
    # what a checkpoint keeps, it keeps in the shape it crosses in: by
    # heads that would be a relayout, so the heads are flattened outside
    flat = lambda t: t.reshape(*t.shape[:2], -1)  # noqa: E731
    return fn(flat(q), flat(k), flat(v), flat(g), beta).reshape(v.shape)


# ---------------------------------------------------------------------------
# Mamba-2's state-space duality (SSD): a scalar decay a head, no delta term
# ---------------------------------------------------------------------------
# Per head, with S in R^{p x n} (p the head's width, n the state's), x_t in
# R^p, B_t and C_t in R^n shared by the heads of a group, dt_t > 0 and
# A < 0 scalars:
#
#     S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
#     y_t = S_t C_t + D x_t
#
# ``chunk_ssd`` takes it a chunk of c tokens at a time. With a the chunk's
# running sum of dt A (every exponent taken is a difference a_i - a_j,
# j <= i, so <= 0):
#
#     Y   = ((C B^T) * exp(a_i - a_j) * dt_j  for j <= i) X  +  exp(a_i) C S_0
#     S_c = exp(a_c) S_0 + sum_j exp(a_c - a_j) dt_j x_j B_j^T
#
# so only S crosses chunks. A position with dt = 0 decays nothing and writes
# nothing: that is the rule for positions that are not there (a chunk's
# padded tail, a bucket's padded rows); the caller zeroes their dt, and the
# state passes them unchanged. The state and every decay are float32; the
# products take float32 operands at ``HIGHEST`` (they are small beside a
# block's projections at any length served).

def _ssd_heads(t, heads):
    """[..., g, n] -> [..., heads, n]: head i reads group i // (heads/g)."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def chunk_ssd(x, dt, A, B, C, D, chunk=128, initial_state=None):
    """``x`` [b, l, h, p], ``dt`` [b, l, h] (after its softplus; 0 where a
    position is padding), ``A`` [h] (< 0), ``B``, ``C`` [b, l, g, n] with g
    dividing h, ``D`` [h], ``initial_state`` [b, h, p, n] float32 (None:
    zeros). Returns ``(y [b, l, h, p] in x's dtype, final_state float32)``.
    Any length: the tail is padded to a whole chunk with dt = 0."""
    from ..profiler.telemetry import get_telemetry

    tel = get_telemetry()  # trace-time facts, like kda/calls
    tel.counter("ssm/calls")
    tel.gauge("ssm/chunk", chunk)
    tel.gauge("ssm/form.chunked", 1)
    b, l, h, p = x.shape
    n = B.shape[-1]
    x_in = x
    x, dt, B, C = _whole_chunks(chunk, x.astype(F32), dt.astype(F32),
                                B.astype(F32), C.astype(F32))
    nc = x.shape[1] // chunk
    # [b, nc, c, ...]
    x, dt, B, C = (t.reshape(b, nc, chunk, *t.shape[2:])
                   for t in (x, dt, B, C))
    a = jnp.cumsum(dt * A.astype(F32), axis=2)           # [b, nc, c, h]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = a[:, :, :, None, :] - a[:, :, None, :, :]     # [b, nc, i, j, h]
    decay = jnp.where(lower[..., None],
                      jnp.exp(jnp.where(lower[..., None], diff, 0.0)), 0.0)
    g = B.shape[-2]
    scores = _mm("bzign,bzjgn->bzijg", C, B, F32)        # [b, nc, i, j, g]
    weights = _ssd_heads(scores[..., None], h)[..., 0] if g != h else scores
    weights = weights * decay * dt[:, :, None, :, :]     # [b, nc, i, j, h]
    y = _mm("bzijh,bzjhp->bzihp", weights, x, F32)
    # what each chunk writes into an empty state, and what it keeps of the
    # state that comes in
    last = a[:, :, -1:, :]                               # [b, nc, 1, h]
    wrote = _mm("bzjhp,bzjhn->bzhpn",
                x * (jnp.exp(last - a) * dt)[..., None], _ssd_heads(B, h),
                F32)                                     # [b, nc, h, p, n]
    keep = jnp.exp(last[:, :, 0])                        # [b, nc, h]
    S0 = (jnp.zeros((b, h, p, n), F32) if initial_state is None
          else initial_state.astype(F32))

    def step(S, xs):
        keep_c, wrote_c = xs
        return keep_c[..., None, None] * S + wrote_c, S

    final, entering = jax.lax.scan(
        step, S0, (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(wrote, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)              # [b, nc, h, p, n]
    y = y + _mm("bzihn,bzhpn->bzihp",
                _ssd_heads(C, h) * jnp.exp(a)[..., None], entering, F32)
    y = y + x * D.astype(F32)[:, None]
    y = y.reshape(b, nc * chunk, h, p)[:, :l]
    return y.astype(x_in.dtype), final


def ssd_step(x, dt, A, B, C, D, state):
    """One token of the same recurrence: ``x`` [b, h, p], ``dt`` [b, h],
    ``B``, ``C`` [b, g, n], ``state`` [b, h, p, n] float32. Returns ``(y
    [b, h, p] in x's dtype, new state)``. One pass over the state: it is
    read, decayed, written to and summed against C elementwise, in
    float32."""
    from ..profiler.telemetry import get_telemetry

    tel = get_telemetry()
    tel.counter("ssm/calls")
    tel.gauge("ssm/form.step", 1)
    h = x.shape[1]
    x32, dt = x.astype(F32), dt.astype(F32)
    B, C = _ssd_heads(B.astype(F32), h), _ssd_heads(C.astype(F32), h)
    keep = jnp.exp(dt * A.astype(F32))
    state = (keep[..., None, None] * state.astype(F32)
             + (dt[..., None] * x32)[..., None] * B[:, :, None, :])
    y = jnp.sum(state * C[:, :, None, :], axis=-1) + x32 * D.astype(F32)[:, None]
    return y.astype(x.dtype), state
