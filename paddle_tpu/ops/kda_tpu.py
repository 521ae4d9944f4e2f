"""``chunk_kda`` on the TPU: a Pallas kernel pair that holds a chunk of the
gated delta rule in VMEM (``ops/linear_attention.py`` has the mathematics
and the XLA form these kernels are held to).

Every tensor of a token is [b, l, h d], a head's values side by side: a
grid step's tile is whole lanes and a head a 128-lane slice of it
(``_head``). The layer around the kernels computes in the same shape (the
convolutions, the gates, q's and k's norm, the output norm), so nothing is
laid out anew on the way in or out (PERF.md section 6, PR 33). q and k come
normalised, q scaled.

Forward: grid (batch, head groups, chunks), the chunk axis sequential. A
grid step loads the chunk's q, k, v, g and beta tiles of its heads, makes G,
A, B, the unit-lower inverse T, u and o in VMEM and hands the f32 state of
each head to the next chunk in a scratch. The state is kept transposed,
[dv, dk], so that the chunk's decay exp(G_C), a row over dk, scales it
without a transpose. Called for differentiation it also writes each
chunk's incoming state ([b, n, h, dv, dk] f32), the one residual beside the
five inputs.

Backward: the same grid walked from the last chunk to the first with the
state's cotangent in the scratch. It makes the chunk's forward quantities
again and takes the gradient by hand; nothing of a chunk's inside is
written to HBM in either direction.

A and B follow the XLA form's sub-block rule (16 rows; an off-diagonal
sub-block through the decays up to and from the start of its row block, a
diagonal one from the pairwise differences, a sub-diagonal at a time), so
every exponent taken is <= 0. The inverse is the block recursion
[[P, 0], [C, Q]]^-1 = [[P^-1, 0], [-Q^-1 C P^-1, Q^-1]] from 2 x 2 blocks up,
in f32 matmuls at ``HIGHEST``, as are the running sum and the products
that make A and B; the chunk's other matmuls take operands in the inputs'
dtype and accumulate in f32, where ``_mm(..., dtype)`` has them.

What the time is made of (v5e, 1 x 8192 x 32 x 128, bf16, chunk 64; PERF.md
section 6, PR 31): the ten dependent f32 products of the inverse are over
half of a forward when each head takes them alone, so the heads of a grid
step go through them two side by side; the sub-diagonal loop and the
off-diagonal products are an eighth. A grid step's eight heads are unrolled
(one straight line for the scheduler), but each part of a head's work is an
inline ``jax.jit`` (``_traced_once``), so that tracing a kernel costs one
head's Python and not eight: the step's set-up pays for it in every run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
# this module is imported only by a call that takes the kernels
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dtype, dims=_NN):
    """``_mm`` of the XLA form: operands in ``dtype``, f32 out."""
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               precision=_HI if dtype == F32 else None,
                               preferred_element_type=F32)


def heads_per_step(h):
    """Heads a grid step holds: as many of 8, 4, 2, 1 as divide ``h``, so
    that the short dependent chains of one head overlap with another's."""
    return next(n for n in (8, 4, 2, 1) if h % n == 0)


def _traced_once(fn):
    """A part of a kernel's body that every head (or run of heads) of a
    grid step goes through: traced once for its shapes and inlined at each
    use, so that the kernel is the same straight-line code and a step's
    eight heads cost one head's tracing."""
    return jax.jit(fn, inline=True)


@jax.tree_util.register_pytree_node_class
class _Masks:
    """The iota masks of a [c, c] chunk, made once a grid step."""

    def __init__(self, c, sub):
        self.c, self.sub = c, sub
        bits = sub.bit_length() - 1
        row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.eye, self.lower, self.strict = row == col, col <= row, col < row
        # how many rows back the column lies, inside the row's own
        # diagonal sub-block (-1 outside it)
        self.back = jnp.where((row >> bits) == (col >> bits), row - col, -1)
        self.tri = jnp.where(self.lower, 1.0, 0.0).astype(F32)
        self.row_in_sub = jax.lax.broadcasted_iota(
            jnp.int32, (c, 1), 0) & (sub - 1)
        self.row1 = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)

    _ARRAYS = ("eye", "lower", "strict", "back", "tri", "row_in_sub", "row1")

    def tree_flatten(self):
        return [getattr(self, n) for n in self._ARRAYS], (self.c, self.sub)

    @classmethod
    def tree_unflatten(cls, sizes, arrays):
        m = object.__new__(cls)
        m.c, m.sub = sizes
        for n, a in zip(cls._ARRAYS, arrays):
            setattr(m, n, a)
        return m

    def as_row(self, col_vec):
        """[c, 1] -> [1, c], exactly."""
        return jnp.sum(jnp.where(self.eye, col_vec, 0.0), axis=0,
                       keepdims=True)

    def as_col(self, row_vec):
        return jnp.sum(jnp.where(self.eye, row_vec, 0.0), axis=1,
                       keepdims=True)


def _rows_back(x, j):
    """Row r of the result is row r - j of x [c, d] (the first j rows wrap
    around and are masked by the caller)."""
    return pltpu.roll(x, j, 0) if j else x


def _off_diagonal(G, a, m):
    """For row block ``a`` >= 1: the decays into its rows from the row
    before it, and the keys of the earlier rows decayed up to that row
    (zero from the block's start on). Both exponents are <= 0."""
    lo = a * m.sub
    ref = G[lo - 1:lo]
    into = jnp.exp(G[lo:lo + m.sub] - ref)
    before = m.row1 < lo
    upto_decay = jnp.where(before, jnp.exp(jnp.where(before, ref - G, 0.0)),
                           0.0)
    return into, upto_decay


def _diag_pairs(G, k32, j, m):
    """The pairs (r, r - j) inside a diagonal sub-block, for every row r
    at once: k_{r-j} exp(G_r - G_{r-j}) [c, d], zero where r - j lies
    before the sub-block's start; with the decay and the rows that have
    such a pair."""
    inside = m.row_in_sub >= j
    decay = jnp.exp(jnp.where(inside, G - _rows_back(G, j), 0.0))
    return (jnp.where(inside, _rows_back(k32, j) * decay, 0.0), decay,
            inside)


def _intra(q32, k32, G, m):
    """A (strictly lower) and B (lower) [c, c] of one chunk and head."""
    c, sub = m.c, m.sub
    zeros = jnp.zeros((sub, c), F32)
    rows_a, rows_b = [zeros], [zeros]
    for a in range(1, c // sub):
        lo = a * sub
        into, upto_decay = _off_diagonal(G, a, m)
        both = jnp.concatenate([k32[lo:lo + sub] * into,
                                q32[lo:lo + sub] * into], axis=0)
        prod = _dot(both, k32 * upto_decay, F32, _NT)    # [2 sub, c]
        rows_a.append(prod[:sub])
        rows_b.append(prod[sub:])
    A = jnp.concatenate(rows_a, axis=0)
    B = jnp.concatenate(rows_b, axis=0)
    # the diagonal sub-blocks, a sub-diagonal at a time
    for j in range(sub):
        kd, _, _ = _diag_pairs(G, k32, j, m)
        here = m.back == j
        if j:
            A = jnp.where(here, jnp.sum(k32 * kd, axis=1, keepdims=True), A)
        B = jnp.where(here, jnp.sum(q32 * kd, axis=1, keepdims=True), B)
    return A, B


def _intra_bwd(q32, k32, G, dA, dB, m):
    """The cotangents of A and B back through the pairwise decays: what
    reaches q, what reaches k as the row's key and as the column's, each
    [c, d]. (G's share is q dq + k dk_row - k dk_col.)"""
    c, sub = m.c, m.sub
    d = k32.shape[1]
    zeros = jnp.zeros((sub, d), F32)
    dq_rows, dk_rows = [zeros], [zeros]
    dk_col = jnp.zeros((c, d), F32)
    for a in range(1, c // sub):
        lo = a * sub
        into, upto_decay = _off_diagonal(G, a, m)
        both = jnp.concatenate([k32[lo:lo + sub] * into,
                                q32[lo:lo + sub] * into], axis=0)
        before = jax.lax.broadcasted_iota(
            jnp.int32, (2 * sub, c), 1) < lo
        d_both = jnp.where(before, jnp.concatenate(
            [dA[lo:lo + sub], dB[lo:lo + sub]], axis=0), 0.0)  # [2 sub, c]
        to_rows = _dot(d_both, k32 * upto_decay, F32)          # [2 sub, d]
        dk_rows.append(to_rows[:sub] * into)
        dq_rows.append(to_rows[sub:] * into)
        dk_col = dk_col + _dot(d_both, both, F32, _TN) * upto_decay
    dq = jnp.concatenate(dq_rows, axis=0)
    dk_row = jnp.concatenate(dk_rows, axis=0)
    for j in range(sub):
        kd, decay, inside = _diag_pairs(G, k32, j, m)
        here = m.back == j
        dB_j = jnp.sum(jnp.where(here, dB, 0.0), axis=1, keepdims=True)
        dq = dq + dB_j * kd
        reach = dB_j * q32
        if j:
            dA_j = jnp.sum(jnp.where(here, dA, 0.0), axis=1, keepdims=True)
            dk_row = dk_row + dA_j * kd
            reach = reach + dA_j * k32
        # what row r sends to the key of row r - j
        reach = jnp.where(inside, reach * decay, 0.0)
        dk_col = dk_col + (_rows_back(reach, c - j) if j else reach)
    return dq, dk_row, dk_col


@_traced_once
def _inverses(Ns, m):
    """(I + N)^-1 of each strictly lower N [c, c] of ``Ns``: blocks of 2
    by hand, then pairs of blocks merged, T <- T - T L T with L the part
    of N that joins the two blocks of a pair. The matrices lie side by
    side, [c, p c], and meet their right factors as one block diagonal
    [p c, p c], so that a pass of the MXU carries all p of them."""
    c, p = m.c, len(Ns)
    N = jnp.concatenate(Ns, axis=1) if p > 1 else Ns[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, p * c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, p * c), 1) & (c - 1)

    def same(size):
        shift = size.bit_length() - 1
        return (row >> shift) == (col >> shift)

    def block_diagonal(Y):
        if p == 1:
            return Y
        shift = c.bit_length() - 1
        own = (jax.lax.broadcasted_iota(jnp.int32, (p * c, p * c), 0) >> shift
               == jax.lax.broadcasted_iota(jnp.int32, (p * c, p * c), 1)
               >> shift)
        return jnp.where(own, jnp.concatenate([Y] * p, axis=0), 0.0)

    T = jnp.where(row == col, 1.0, 0.0) - jnp.where(same(2), N, 0.0)
    size = 2
    while size < c:
        L = jnp.where(same(2 * size) & ~same(size), N, 0.0)
        T = T - _dot(_dot(T, block_diagonal(L), F32), block_diagonal(T), F32)
        size *= 2
    return [T[:, i * c:(i + 1) * c] for i in range(p)]


@_traced_once
def _chunk_start(q, k, g, beta, m):
    """What of a chunk and head comes before the inverse: G, the decays, A
    and B, from q, k [c, dk], g [c, dk] f32 and beta [c, 1] f32."""
    q32, k32 = q.astype(F32), k.astype(F32)
    G = _dot(m.tri, g, F32)
    last = G[m.c - 1:m.c]
    D, E, keep = jnp.exp(G), jnp.exp(last - G), jnp.exp(last)
    A, B = _intra(q32, k32, G, m)
    beta_row = m.as_row(beta)
    return dict(q32=q32, k32=k32, G=G, D=D, E=E, keep=keep, A=A, B=B,
                beta_row=beta_row, N=A * beta_row, kD=k32 * D, kE=k32 * E,
                qD=q32 * D)


@_traced_once
def _chunk_rest(f, T, v, beta, St):
    """... and after it: W = T (k exp G), U and beta U, with the incoming
    state St [dv, dk] f32."""
    dtype = v.dtype
    W = _dot(T, f["kD"], dtype)                          # taken
    U = _dot(T, v, dtype) - _dot(W, St, dtype, _NT)
    return dict(f, T=T, W=W, U=U, Ub=beta * U)


@_traced_once
def _forward_heads(qs, ks, vs, gs, betas, Sts, m):
    """o [c, dv] f32 and the outgoing state [dv, dk] of each head."""
    dtype = qs[0].dtype
    starts = [_chunk_start(q, k, g, beta, m)
              for q, k, g, beta in zip(qs, ks, gs, betas)]
    Ts = _inverses([f["N"] for f in starts], m)
    outs = []
    for f, T, v, beta, St in zip(starts, Ts, vs, betas, Sts):
        f = _chunk_rest(f, T, v, beta, St)
        outs.append((
            _dot(f["qD"], St, dtype, _NT) + _dot(f["B"], f["Ub"], dtype),
            f["keep"] * St + _dot(f["Ub"], f["kE"], dtype, _TN)))
    return outs


@_traced_once
def _backward_head(f, v, beta, St, dO, dSt, m):
    """Cotangents of q, k, v [c, d] f32, g [c, dk], beta [c, 1] and of the
    incoming state [dv, dk], from the chunk's forward quantities ``f``, dO
    [c, dv] and the outgoing state's cotangent dSt [dv, dk]."""
    dtype = v.dtype
    q32, k32, T, W = f["q32"], f["k32"], f["T"], f["W"]
    dUb = _dot(f["B"], dO, dtype, _TN) + _dot(f["kE"], dSt, dtype, _NT)
    dB = jnp.where(m.lower, _dot(dO, f["Ub"], dtype, _NT), 0.0)
    dqD = _dot(dO, St, dtype)
    dkE = _dot(f["Ub"], dSt, dtype)
    d_keep = jnp.sum(St * dSt, axis=0, keepdims=True)
    dU = beta * dUb
    d_beta = jnp.sum(f["U"] * dUb, axis=1, keepdims=True)
    dW = -_dot(dU, St, dtype)
    dSt_in = (_dot(dO, f["qD"], dtype, _TN) + f["keep"] * dSt
              - _dot(dU, W, dtype, _TN))
    dT = _dot(dU, v, dtype, _NT) + _dot(dW, f["kD"], dtype, _NT)
    dv = _dot(T, dU, dtype, _TN)
    dkD = _dot(T, dW, dtype, _TN)
    # M = I + A Diag(beta), T = M^-1: dM = -T^T dT T^T, strictly lower
    dN = jnp.where(m.strict, -_dot(_dot(T, dT, F32, _TN), T, F32, _NT), 0.0)
    d_beta = d_beta + m.as_col(jnp.sum(f["A"] * dN, axis=0, keepdims=True))
    dq, dk_row, dk_col = _intra_bwd(q32, k32, f["G"], dN * f["beta_row"], dB,
                                    m)
    dkE_kE = dkE * f["kE"]
    dG = (q32 * dq + k32 * (dk_row - dk_col) + dqD * f["qD"]
          + dkD * f["kD"] - dkE_kE)
    # G_C enters E of every row and the state's decay
    at_last = jnp.sum(dkE_kE, axis=0, keepdims=True) + d_keep * f["keep"]
    dg = _dot(m.tri, dG, F32, _TN) + at_last
    return (dq + dqD * f["D"], dk_row + dk_col + dkD * f["D"] + dkE * f["E"],
            dv, dg, d_beta, dSt_in)


def _side_by_side(heads, chunk):
    """The heads of a grid step in runs whose [c, c] matrices fill 128
    lanes side by side (two at chunk 64)."""
    run = max(1, min(heads, 128 // chunk))
    return [range(at, min(at + run, heads)) for at in range(0, heads, run)]


def _head(ref, j, width):
    return ref[0, :, j * width:(j + 1) * width]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, heads,
                dk, dv, chunk, sub, keep_states):
    states_ref = rest[0] if keep_states else None
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    m = _Masks(chunk, sub)
    for js in _side_by_side(heads, chunk):
        Sts = [state[j] for j in js]
        if keep_states:
            for j, St in zip(js, Sts):
                states_ref[0, 0, j] = St
        outs = _forward_heads(
            [_head(q_ref, j, dk) for j in js],
            [_head(k_ref, j, dk) for j in js],
            [_head(v_ref, j, dv) for j in js],
            [_head(g_ref, j, dk) for j in js],
            [beta_ref[0, 0, :, j:j + 1] for j in js], Sts, m)
        for j, (out, St) in zip(js, outs):
            o_ref[0, :, j * dv:(j + 1) * dv] = out.astype(o_ref.dtype)
            state[j] = St


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state, *, heads,
                dk, dv, chunk, sub):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    m = _Masks(chunk, sub)
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, heads), 1)
    d_betas = jnp.zeros((chunk, heads), F32)
    for js in _side_by_side(heads, chunk):
        betas = [beta_ref[0, 0, :, j:j + 1] for j in js]
        starts = [_chunk_start(_head(q_ref, j, dk), _head(k_ref, j, dk),
                               _head(g_ref, j, dk), beta, m)
                  for j, beta in zip(js, betas)]
        Ts = _inverses([f["N"] for f in starts], m)
        for j, f, T, beta in zip(js, starts, Ts, betas):
            v, St = _head(v_ref, j, dv), states_ref[0, 0, j]
            dq, dk_, dv_, dg, d_beta, dSt = _backward_head(
                _chunk_rest(f, T, v, beta, St), v, beta, St,
                _head(do_ref, j, dv), d_state[j], m)
            dq_ref[0, :, j * dk:(j + 1) * dk] = dq.astype(dq_ref.dtype)
            dk_ref[0, :, j * dk:(j + 1) * dk] = dk_.astype(dk_ref.dtype)
            dv_ref[0, :, j * dv:(j + 1) * dv] = dv_.astype(dv_ref.dtype)
            dg_ref[0, :, j * dk:(j + 1) * dk] = dg
            d_betas = jnp.where(lane == j, d_beta, d_betas)
            d_state[j] = dSt
    dbeta_ref[0, 0] = d_betas


def _specs(n, heads, chunk, dk, dv, reverse):
    at = (lambda ic: n - 1 - ic) if reverse else (lambda ic: ic)
    tokens = lambda width: pl.BlockSpec(  # noqa: E731
        (1, chunk, heads * width), lambda ib, ig, ic: (ib, at(ic), ig))
    beta = pl.BlockSpec((1, 1, chunk, heads),
                        lambda ib, ig, ic: (ib, ig, at(ic), 0))
    states = pl.BlockSpec((1, 1, heads, dv, dk),
                          lambda ib, ig, ic: (ib, at(ic), ig, 0, 0))
    return tokens, beta, states


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _by_group(beta, heads):
    """[b, l, h] -> [b, h / heads, l, heads]: a grid step's heads are the
    lanes of its tile."""
    b, l, h = beta.shape
    return beta.astype(F32).reshape(b, l, h // heads, heads).transpose(
        0, 2, 1, 3)


def forward(q, k, v, g, beta, chunk, sub, keep_states, interpret=False):
    """o [b, l, h dv] in q's dtype and, with ``keep_states``, every
    chunk's incoming state [b, n, h, dv, dk] f32 (else None), from q, k, g
    [b, l, h dk], v [b, l, h dv] (a head's values side by side, as a grid
    step's tile has them) and beta [b, l, h]. l is a whole number of
    chunks."""
    b, l, h = beta.shape
    dk, dv, n = k.shape[-1] // h, v.shape[-1] // h, l // chunk
    heads = heads_per_step(h)
    tokens, beta_spec, states_spec = _specs(n, heads, chunk, dk, dv, False)
    out_specs = [tokens(dv)]
    out_shape = [jax.ShapeDtypeStruct((b, l, h * dv), q.dtype)]
    if keep_states:
        out_specs.append(states_spec)
        out_shape.append(jax.ShapeDtypeStruct((b, n, h, dv, dk), F32))
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            functools.partial(_fwd_kernel, heads=heads, dk=dk, dv=dv,
                              chunk=chunk, sub=sub, keep_states=keep_states),
            grid=(b, h // heads, n),
            in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk),
                      beta_spec],
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((heads, dv, dk), F32)],
            compiler_params=_params(), interpret=interpret,
            name="chunk_kda_fwd",
        )(q, k, v, g.astype(F32), _by_group(beta, heads))
    return outs[0], outs[1] if keep_states else None


def backward(q, k, v, g, beta, states, dO, chunk, sub, interpret=False):
    """The cotangents of ``forward``'s five inputs, in their shapes and
    dtypes."""
    b, l, h = beta.shape
    dk, dv, n = k.shape[-1] // h, v.shape[-1] // h, l // chunk
    heads = heads_per_step(h)
    tokens, beta_spec, states_spec = _specs(n, heads, chunk, dk, dv, True)
    with jax.enable_x64(False):
        dq, dk_, dv_, dg, d_beta = pl.pallas_call(
            functools.partial(_bwd_kernel, heads=heads, dk=dk, dv=dv,
                              chunk=chunk, sub=sub),
            grid=(b, h // heads, n),
            in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk),
                      beta_spec, states_spec, tokens(dv)],
            out_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk),
                       beta_spec],
            out_shape=[
                jax.ShapeDtypeStruct((b, l, h * dk), q.dtype),
                jax.ShapeDtypeStruct((b, l, h * dk), k.dtype),
                jax.ShapeDtypeStruct((b, l, h * dv), v.dtype),
                jax.ShapeDtypeStruct((b, l, h * dk), F32),
                jax.ShapeDtypeStruct((b, h // heads, l, heads), F32)],
            scratch_shapes=[pltpu.VMEM((heads, dv, dk), F32)],
            compiler_params=_params(), interpret=interpret,
            name="chunk_kda_bwd",
        )(q, k, v, g.astype(F32), _by_group(beta, heads), states,
          dO.astype(q.dtype))
    d_beta = d_beta.transpose(0, 2, 1, 3).reshape(b, l, h)
    return dq, dk_, dv_, dg.astype(g.dtype), d_beta.astype(beta.dtype)
