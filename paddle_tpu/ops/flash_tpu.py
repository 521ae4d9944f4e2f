"""TPU flash attention — repo-native Pallas kernels tuned for GPT-class
shapes (head_dim 64, moderate L, many heads).

Counterpart of the reference's fused attention CUDA kernels
(operators/fused/multihead_matmul_op.cu, fused_attention_op.cu), designed
TPU-first rather than translated:

- kernels consume the model's NATIVE ``[b, L, H*d]`` activation layout (the
  qkv projection's output), so XLA inserts no [b,h,l,d] transpose copies
  around the attention op (measured 6 × 16MB relayout copies per layer on
  the XLA einsum path);
- the O(L²) score tensor never touches HBM: per (batch, q-chunk) grid step
  the online-softmax recurrence runs per head over K blocks held in VMEM;
- causal skip: q-chunk i only loops K blocks ≤ its diagonal (bq == bk), so
  upper-triangle work is never issued;
- backward = two kernels (dq; dk+dv) recomputing probabilities from the
  saved logsumexp, flash-style, instead of materializing P.

All index math is pinned to i32 and every trace runs under
``jax.enable_x64(False)`` — the repo enables x64 globally and Mosaic cannot
legalize stray i64 scalars.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_blhd"]

_NEG_INF = -1e30


def _slc(h, d):
    return slice(h * d, (h + 1) * d)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, H, d, bq, bk, scale):
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    nkb = iq + 1  # bq == bk: causal q-chunk i needs K blocks [0, i]
    for h in range(H):
        # operands stay bf16 (full-rate MXU); accumulation is f32
        qh = (q_ref[0][:, _slc(h, d)].astype(jnp.float32)
              * scale).astype(q_ref.dtype)  # [bq, d]

        def body(j, carry, h=h, qh=qh):
            acc, m, l = carry
            kh = k_ref[0, pl.dslice(j * bk, bk), _slc(h, d)]
            vh = v_ref[0, pl.dslice(j * bk, bk), _slc(h, d)]
            s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[:, None] + jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc_new, m_new, l_new

        acc0 = jnp.zeros((bq, d), jnp.float32)
        m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq,), jnp.float32)
        acc, m, l = jax.lax.fori_loop(0, nkb, body, (acc0, m0, l0))
        l = jnp.maximum(l, 1e-30)
        o_ref[0, :, _slc(h, d)] = (acc / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, h, :] = m + jnp.log(l)


# ---------------------------------------------------------------------------
# backward: dq
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, H, d, bq, bk, scale):
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    nkb = iq + 1
    for h in range(H):
        qh = (q_ref[0][:, _slc(h, d)].astype(jnp.float32)
              * scale).astype(q_ref.dtype)
        doh = do_ref[0][:, _slc(h, d)]
        lse = lse_ref[0][h, :]          # [bq]
        delta = delta_ref[0][h, :]      # [bq] = rowsum(do * o)

        def body(j, dq, h=h, qh=qh, doh=doh, lse=lse, delta=delta):
            kh = k_ref[0, pl.dslice(j * bk, bk), _slc(h, d)]
            vh = v_ref[0, pl.dslice(j * bk, bk), _slc(h, d)]
            s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dp = jax.lax.dot_general(doh, vh, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(kh.dtype)
            return dq + jax.lax.dot_general(ds, kh, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

        dq = jax.lax.fori_loop(0, nkb, body, jnp.zeros((bq, d), jnp.float32))
        dq_ref[0, :, _slc(h, d)] = (dq * scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dk, dv
# ---------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, H, d, bq, bk, nq, scale):
    from jax.experimental import pallas as pl

    jk = pl.program_id(1)
    for h in range(H):
        kh = k_ref[0][:, _slc(h, d)]  # [bk, d]
        vh = v_ref[0][:, _slc(h, d)]

        def body(i, carry, h=h, kh=kh, vh=vh):
            dk, dv = carry
            qh = (q_ref[0, pl.dslice(i * bq, bq),
                        _slc(h, d)].astype(jnp.float32)
                  * scale).astype(q_ref.dtype)
            doh = do_ref[0, pl.dslice(i * bq, bq), _slc(h, d)]
            lse = lse_ref[0, h, pl.dslice(i * bq, bq)]
            delta = delta_ref[0, h, pl.dslice(i * bq, bq)]
            s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
            p = jnp.exp(s - lse[:, None])
            pb = p.astype(doh.dtype)
            dv = dv + jax.lax.dot_general(pb, doh, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(doh, vh, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(qh.dtype)
            dk = dk + jax.lax.dot_general(ds, qh, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
            return dk, dv

        dk0 = jnp.zeros((bk, d), jnp.float32)
        dv0 = jnp.zeros((bk, d), jnp.float32)
        # q-chunk i sees K block jk iff i >= jk (bq == bk)
        dk, dv = jax.lax.fori_loop(jk, nq, body, (dk0, dv0))
        dk_ref[0, :, _slc(h, d)] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, _slc(h, d)] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side plumbing
# ---------------------------------------------------------------------------
# Every kernel here keeps two whole [L, H*d] operands of one batch row in
# VMEM (K and V in fwd/dq, Q and dO in dkv). Measured on the v5e with the
# compiler's default scoped-VMEM limit (libtpu 0.0.34): 24 MiB of them
# compile (L=8192, H*d=768, bf16), 48 MiB are refused with "Ran out of
# memory in memory space vmem" (the same shape in f32, or L=16384). With
# ``vmem_limit_bytes`` raised, up to 96 MiB compiled (PERF.md, PR 21) —
# not passed here, so the gate stops at what the default accepts.
_RESIDENT_VMEM_BYTES = 24 << 20


def _fits(b, L, H, d, block, itemsize):
    return (jax.default_backend() == "tpu" and L % block == 0
            and L // block >= 1 and d % 8 == 0 and (H * d) % 128 == 0
            and 2 * L * H * d * itemsize <= _RESIDENT_VMEM_BYTES)


def _fwd_call(q3, k3, v3, b, L, H, d, block, scale):
    from jax.experimental import pallas as pl

    grid = (b, L // block)
    kw = dict(H=H, d=d, bq=block, bk=block, scale=scale)
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, **kw),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block, H * d), lambda ib, iq: (ib, iq, 0)),
                pl.BlockSpec((1, L, H * d), lambda ib, iq: (ib, 0, 0)),
                pl.BlockSpec((1, L, H * d), lambda ib, iq: (ib, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block, H * d), lambda ib, iq: (ib, iq, 0)),
                pl.BlockSpec((1, H, block), lambda ib, iq: (ib, 0, iq)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, L, H * d), q3.dtype),
                jax.ShapeDtypeStruct((b, H, L), jnp.float32),
            ],
        )(q3, k3, v3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_blhd(q, k, v, causal=True, block=256):
    """Flash attention over ``[b, L, H, d]`` operands (causal self-attention).

    Returns ``[b, L, H, d]``. Falls back to the XLA chunked path when the
    shape doesn't tile or off-TPU. ``causal=False`` is not supported by the
    kernel tier — callers dispatch elsewhere first.
    """
    out, _ = _flash_fwd(q, k, v, causal, block)
    return out


def _flash_fwd(q, k, v, causal, block):
    b, L, H, d = q.shape
    if not causal or not _fits(b, L, H, d, block, q.dtype.itemsize):
        from .attention import _count_fallback, xla_attention

        if jax.default_backend() == "tpu":
            # reaching this on TPU means the kernel was called with a
            # shape the dispatch gates should have filtered (or a direct
            # caller bypassed them): count it so the reroute is never
            # invisible (off-TPU the XLA path is documented behavior)
            _count_fallback(
                "flash_tpu", q.shape,
                f"flash_attention_blhd cannot take this shape (needs "
                f"causal, L % {block} == 0, H*d % 128 == 0, two "
                f"[L, H*d] operands within {_RESIDENT_VMEM_BYTES >> 20} "
                f"MiB) — materializing via the XLA tier")
        return xla_attention(q, k, v, causal=causal, layout="blhd"), None
    scale = 1.0 / math.sqrt(d)
    q3 = q.reshape(b, L, H * d)
    out, lse = _fwd_call(q3, k.reshape(b, L, H * d), v.reshape(b, L, H * d),
                         b, L, H, d, block, scale)
    return out.reshape(b, L, H, d), lse


def _flash_fwd_rule(q, k, v, causal, block):
    out, lse = _flash_fwd(q, k, v, causal, block)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block, res, g):
    from jax.experimental import pallas as pl

    q, k, v, out, lse = res
    b, L, H, d = q.shape
    if lse is None:  # fwd took the XLA fallback: differentiate that path
        from .attention import xla_attention

        _, vjp = jax.vjp(
            lambda q_, k_, v_: xla_attention(q_, k_, v_, causal=causal,
                                             layout="blhd"), q, k, v)
        return vjp(g)
    scale = 1.0 / math.sqrt(d)
    # delta[b, h, l] = rowsum(do * o) per head — cheap XLA reduce
    delta = jnp.einsum("blhd,blhd->bhl", g.astype(jnp.float32),
                       out.astype(jnp.float32))
    q3 = q.reshape(b, L, H * d)
    k3 = k.reshape(b, L, H * d)
    v3 = v.reshape(b, L, H * d)
    g3 = g.reshape(b, L, H * d).astype(q.dtype)
    nq = L // block
    kw = dict(H=H, d=d, bq=block, bk=block, scale=scale)
    act = pl.BlockSpec((1, block, H * d), lambda ib, i: (ib, i, 0))
    full = pl.BlockSpec((1, L, H * d), lambda ib, i: (ib, 0, 0))
    stats_blk = pl.BlockSpec((1, H, block), lambda ib, i: (ib, 0, i))
    stats_full = pl.BlockSpec((1, H, L), lambda ib, i: (ib, 0, 0))
    with jax.enable_x64(False):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **kw),
            grid=(b, nq),
            in_specs=[act, full, full, act, stats_blk, stats_blk],
            out_specs=pl.BlockSpec((1, block, H * d), lambda ib, i: (ib, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b, L, H * d), q.dtype),
        )(q3, k3, v3, g3, lse, delta)
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, nq=nq, **kw),
            grid=(b, nq),
            in_specs=[full, act, act, full, stats_full, stats_full],
            out_specs=[
                pl.BlockSpec((1, block, H * d), lambda ib, i: (ib, i, 0)),
                pl.BlockSpec((1, block, H * d), lambda ib, i: (ib, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, L, H * d), q.dtype),
                jax.ShapeDtypeStruct((b, L, H * d), q.dtype),
            ],
        )(q3, k3, v3, g3, lse, delta)
    rs = lambda t: t.reshape(b, L, H, d)
    return rs(dq), rs(dk), rs(dv)


flash_attention_blhd.defvjp(_flash_fwd_rule, _flash_bwd_rule)
