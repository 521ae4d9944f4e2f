"""Attention kernels — the TPU-native replacement for the reference's fused
attention CUDA kernels (operators/fused/multihead_matmul_op.cu,
fused_attention) plus net-new long-context support (ring/context parallelism,
absent in the reference — SURVEY.md §5 'Long-context: Absent').

Four tiers behind one call, ``dot_product_attention``, which picks one by
a rule on the call and the backend (`_tier`; nothing is timed, nothing on
disk is read):
- ``xla_attention``: the [Lq, Lk] scores materialized chunk by chunk at the
  XLA level. Every cell of the benchmark runs it: GPT-2 345M's causal
  L = 1024 call reads ``attention_ms.train`` 24.67 ms in the step, against
  87.43 ms for the jax-shipped Pallas flash kernel the same call once
  raced against and sometimes drew (ledger, PR 29's GPT row: 56,935
  against 38,355 tokens/s). That kernel and the race are gone (PR 32).
- ``flash_tpu`` (ops/flash_tpu.py): the repo's Pallas kernel, for a causal
  unbiased call on a TPU past L = 8192, where the scores no longer fit.
- ``blockwise_attention``: online-softmax scan over K blocks (the
  FlashAttention recurrence in pure lax): O(seq) memory, differentiable,
  runs anywhere. The tests' reference, the path off the TPU, ring's inner
  step.
- ``ring_attention``: sequence-parallel attention inside shard_map — K/V
  shards rotate around the 'sp' mesh axis via ppermute (ICI neighbor
  transfers) while each device keeps running softmax stats for its Q shard.
``paged_attention`` is the serving path's call over the KV-cache pool.
"""
from __future__ import annotations

import functools
import logging
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

logger = logging.getLogger("paddle_tpu.ops")

__all__ = [
    "blockwise_attention", "ring_attention",
    "xla_attention", "dot_product_attention", "set_attention_impl",
    "set_ring_context", "paged_attention",
]

# 'auto' is the rule of `_tier`; `set_attention_impl` names a tier instead
_IMPL = "auto"
# past these lengths the materialized scores dominate HBM and a call
# streams instead. The causal unbiased call runs q-chunked with its
# fully-masked blocks never computed (`_q_chunks`, about 0.53·L² of
# scores); every other call computes, and saves for its backward, the
# whole [b, h, L, L] square, and keeps the stricter length.
_XLA_MAX_SEQ = 4096
_XLA_MAX_SEQ_CAUSAL = 8192


def set_attention_impl(impl: str):
    """impl ∈ {'auto', 'xla', 'flash_tpu', 'blockwise'}: the one way to
    name a tier instead of taking the rule's (`_tier`), for a test or a
    user who must. A named tier also keeps a registered ring mesh from
    taking the call.

    'flash_tpu' is the repo's layout-native Pallas kernel
    (ops/flash_tpu.py); it holds on a TPU for a causal unbiased call and
    is 'xla' elsewhere. The selector is read at TRACE time: functions
    already jitted keep the implementation they compiled with (jit
    cache). Call before building the train/eval step, or clear caches,
    for the change to take effect.
    """
    global _IMPL
    if impl not in ("auto", "xla", "flash_tpu", "blockwise"):
        raise ValueError(f"unknown attention impl {impl!r}")
    _IMPL = impl

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Blockwise online-softmax attention (lax-level flash recurrence)
# ---------------------------------------------------------------------------
def _block_scan_attention(q, k, v, causal, q_offset, kv_offset, block_k, bias=None):
    """q: [Lq, d]; k/v: [Lk, d]. Online softmax over k blocks.

    ``q_offset``/``kv_offset`` are global position offsets (for ring /
    sharded causal masking)."""
    Lq, d = q.shape
    Lk = k.shape[0]
    scale = 1.0 / math.sqrt(d)
    nblocks = max((Lk + block_k - 1) // block_k, 1)
    pad = nblocks * block_k - Lk
    if pad:
        k = jnp.pad(k, ((0, pad), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=_NEG_INF)
    kb = k.reshape(nblocks, block_k, d)
    vb = v.reshape(nblocks, block_k, d)
    bb = bias.reshape(Lq, nblocks, block_k).swapaxes(0, 1) if bias is not None else None

    q_pos = q_offset + jnp.arange(Lq)

    def body(carry, blk):
        acc, m, l = carry
        if bb is not None:
            kblk, vblk, bblk, bi = blk
        else:
            kblk, vblk, bi = blk
            bblk = None
        s = (q.astype(jnp.float32) @ kblk.astype(jnp.float32).T) * scale  # [Lq, bk]
        k_pos = kv_offset + bi * block_k + jnp.arange(block_k)
        valid = k_pos < (kv_offset + Lk)
        mask = jnp.broadcast_to(valid[None, :], s.shape)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if bblk is not None:
            s = s + bblk
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(axis=-1)
        acc_new = acc * correction[:, None] + p @ vblk.astype(jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((Lq, d), jnp.float32)
    m0 = jnp.full((Lq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Lq,), jnp.float32)
    idx = jnp.arange(nblocks)
    xs = (kb, vb, bb, idx) if bb is not None else (kb, vb, idx)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), xs)
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    return out.astype(q.dtype), m + jnp.log(jnp.maximum(l, 1e-30))


def blockwise_attention(q, k, v, causal=False, block_k=512, bias=None,
                        q_offset=0, kv_offset=0):
    """q,k,v: [batch, heads, len, dim]. Returns [batch, heads, len, dim]."""

    def per_head(qh, kh, vh, bh):
        out, _ = _block_scan_attention(qh, kh, vh, causal, q_offset, kv_offset,
                                       block_k, bh)
        return out

    if bias is not None:
        # bias broadcastable to [b, h, lq, lk]
        b_full = jnp.broadcast_to(bias, q.shape[:2] + (q.shape[2], k.shape[2]))
        fn = jax.vmap(jax.vmap(per_head))
        return fn(q, k, v, b_full)
    fn = jax.vmap(jax.vmap(lambda a, b, c: per_head(a, b, c, None)))
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ring attention (sequence/context parallelism over a mesh axis)
# ---------------------------------------------------------------------------
def _ring_pass(q, k, v, axis_name, causal, fn, init):
    """One full rotation of K/V around ``axis_name``: ``fn(carry, kc, vc,
    q_off, kv_off)`` folds the resident shard into the carry, then K/V
    (plus any extra carried-with-K/V leaves ``fn`` returns) hop one
    neighbor (lax.ppermute → ICI point-to-point, overlapping the next
    step's compute). Shared by the forward and the recompute backward."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    L_local = q.shape[2]
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, i):
        state, kc, vc, rotating = carry
        src_idx = (my_idx - i) % axis_size  # whose shard we currently hold
        state, rotating = fn(state, kc, vc, my_idx * L_local,
                             src_idx * L_local, rotating)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        rotating = jax.tree_util.tree_map(
            lambda t: jax.lax.ppermute(t, axis_name, perm), rotating)
        return (state, kc, vc, rotating), None

    (state, _, _, rotating), _ = jax.lax.scan(
        step, (init[0], k, v, init[1]), jnp.arange(axis_size))
    return state, rotating


def _ring_fwd_impl(q, k, v, axis_name, causal):
    """Forward ring pass; returns (out, lse) with lse = m + log l per row
    ([b, h, L_local]) — the flash-style statistic the recompute backward
    normalizes against."""
    b, h, L_local, d = q.shape
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32) * scale

    def fold(state, kc, vc, q_off, kv_off, _):
        acc, m, l = state
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(jnp.float32))
        if causal:
            q_pos = q_off + jnp.arange(L_local)
            k_pos = kv_off + jnp.arange(kc.shape[2])
            s = jnp.where(k_pos[None, None, None, :]
                          <= q_pos[None, None, :, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
        l = l * corr + p.sum(axis=-1)
        return (acc, m_new, l), _

    acc0 = jnp.zeros((b, h, L_local, d), jnp.float32)
    m0 = jnp.full((b, h, L_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, L_local), jnp.float32)
    (acc, m, l), _ = _ring_pass(q, k, v, axis_name, causal, fold,
                                ((acc0, m0, l0), ()))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).astype(q.dtype)
    return out, m + jnp.log(l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention(q, k, v, axis_name, causal=False, block_k=512):
    """Attention where q/k/v ([b, h, L_local, d]) are sequence-sharded
    over ``axis_name``.

    Must be called inside shard_map/pjit with ``axis_name`` in scope. Each
    step every device computes attention between its local Q shard and the
    K/V shard currently resident, folds the result into running
    online-softmax statistics, then rotates K/V one hop around the ring
    (lax.ppermute → ICI neighbor copy, overlapping with the next compute).

    The backward is a hand-written recompute pass (custom_vjp, like the
    flash_tpu tier's and the latent call's): the forward saves only the
    [b, h, L_local] logsumexp — never the O(L·L/ring) probability blocks autodiff-through-
    scan would stack per rotation — and the backward re-runs the ring,
    recomputing each block's probabilities from the saved statistic while
    dK/dV partial sums travel around the ring WITH the K/V shards they
    belong to (after the full rotation they land back home).
    ``block_k`` is accepted for tier-API compatibility; the local shard is
    one block."""
    out, _ = _ring_fwd_impl(q, k, v, axis_name, causal)
    return out


def _ring_fwd_rule(q, k, v, axis_name, causal, block_k):
    out, lse = _ring_fwd_impl(q, k, v, axis_name, causal)
    return out, (q, k, v, out, lse)


def _ring_bwd_rule(axis_name, causal, block_k, res, g):
    q, k, v, out, lse = res
    b, h, L_local, d = q.shape
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    # delta = rowsum(dO ⊙ O) — the softmax-backward row statistic,
    # computed once on the [.., d] output instead of any [.., L] block
    delta = jnp.einsum("bhqd,bhqd->bhq", gf, out.astype(jnp.float32))

    def fold(dq, kc, vc, q_off, kv_off, rotating):
        dk, dv = rotating
        s = jnp.einsum("bhqd,bhkd->bhqk", qf,
                       kc.astype(jnp.float32)) * scale
        if causal:
            q_pos = q_off + jnp.arange(L_local)
            k_pos = kv_off + jnp.arange(kc.shape[2])
            s = jnp.where(k_pos[None, None, None, :]
                          <= q_pos[None, None, :, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])  # normalized probs, recomputed
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vc.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds,
                             kc.astype(jnp.float32)) * scale
        # dK/dV partials for the shard CURRENTLY resident: they rotate
        # onward with it and are complete once it returns home
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        return dq, (dk, dv)

    z = jnp.zeros((b, h, L_local, d), jnp.float32)
    dq, (dk, dv) = _ring_pass(q, k, v, axis_name, causal, fold,
                              (z, (z, z)))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention.defvjp(_ring_fwd_rule, _ring_bwd_rule)


# -- ring auto-promotion (engine-provided mesh context) ---------------------
def _ring_min_seq() -> int:
    """Minimum GLOBAL sequence length for 'auto' to route through the ring
    (below it the per-hop latency beats the sharded-compute win). Read per
    dispatch — trace-time only, so tests and bench configs can flip it."""
    try:
        return int(os.environ.get("PADDLE_TPU_ATTN_RING_MIN_SEQ", "8192"))
    except ValueError:
        return 8192


_ring_ctx = {"mesh": None, "axis": None, "batch": None}


def set_ring_context(mesh, axis: Optional[str], batch_axis=None) -> None:
    """Engine hook (``fleet.ParallelTrainStep(sp_axis=...)``): register a
    mesh axis carrying sequence shards so 'auto' can promote long-context
    causal attention onto the ring. ``batch_axis`` names the mesh axis (or
    axis tuple) the BATCH dim is sharded over, so the ring's shard_map
    region keeps the engine's data parallelism instead of gathering the
    batch. Read at TRACE time, like ``set_attention_impl`` — call before
    building the step. ``axis=None`` clears."""
    _ring_ctx["mesh"] = mesh if axis else None
    _ring_ctx["axis"] = axis
    _ring_ctx["batch"] = batch_axis if axis else None


def _ring_auto_ok(L: int, causal: bool, bias) -> bool:
    mesh, axis = _ring_ctx["mesh"], _ring_ctx["axis"]
    if mesh is None or axis is None or not causal or bias is not None:
        return False
    if axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return False
    return L % mesh.shape[axis] == 0 and L >= _ring_min_seq()


def _ring_sharded(q, k, v, causal, blhd):
    """Manually-partitioned ring region nested inside the engine's jitted
    GSPMD program: shard_map over the registered mesh with the sequence
    dim sharded on the ring axis — Q/K/V enter pre-rotated (the engine's
    batch sharding already lands them sequence-sharded, so no resharding
    happens at this boundary)."""
    from jax.sharding import PartitionSpec as P

    mesh, axis = _ring_ctx["mesh"], _ring_ctx["axis"]
    ba = _ring_ctx["batch"]  # keep the engine's dp sharding on the batch dim
    spec = P(ba, axis, None, None) if blhd else P(ba, None, axis, None)

    def local(q_, k_, v_):
        if blhd:  # local transpose to the ring's [b, h, l, d] layout
            tr = lambda t: t.transpose(0, 2, 1, 3)
            return tr(ring_attention(tr(q_), tr(k_), tr(v_), axis,
                                     causal, 512))
        return ring_attention(q_, k_, v_, axis, causal, 512)

    # replication checking off: ring's psums confuse the checker
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Paged attention (decode over the serving KV-cache pool)
# ---------------------------------------------------------------------------
# The token-level serving runtime (inference.serving.decode) keeps K/V in
# a blocked pool: pages [N, block_size, H, D] plus per-sequence block
# tables. Decode-time attention gathers a sequence's pages by table and
# attends the query chunk (T=1 for plain decode, T=k+1 for speculative
# verify, T=chunk for prefill) against them. Two XLA-level tiers with
# genuinely different memory/compute profiles, selected by
# tier_policy.select_paged (micro-benched + verdict-cached: the one
# choice of kernel here that is still measured, ROADMAP D2b):
# - 'paged_gather': one gather of the whole context then one fused
#   masked softmax — fastest while the context is score-tensor-small;
# - 'paged_scan': a loop over pages with online softmax, up to the
#   longest row's last page — O(block) live memory, int8 pages dequantize
#   one page at a time (the actual HBM win of int8 storage).
# Positions are logical: token p of a sequence lives in table slot
# p // block_size at offset p % block_size, so slot index IS position.


def _paged_widen(x, scale, compute_dtype):
    """Pages (possibly int8 + scales) -> compute dtype."""
    if scale is None:
        return x.astype(compute_dtype)
    from ..quant import dequantize_kv

    return dequantize_kv(x, scale, compute_dtype)


def _paged_mask(k_pos, q_positions, kv_lens):
    """[B, T, K] bool: causal (k_pos <= q_pos) AND within the written
    prefix (k_pos < kv_len) — the second clause keeps padded table slots
    and stale post-eviction entries unreadable."""
    return ((k_pos[None, None, :] <= q_positions[:, :, None])
            & (k_pos[None, None, :] < kv_lens[:, None, None]))


def _paged_scores(q, k, eq):
    """q [B, T, H, D] against k [B, S, Hkv, D] -> [B, H, T, S]. With as
    many key heads as query heads it is the one einsum ``eq``; with fewer
    (grouped queries: query head i reads key head i // (H / Hkv)) the
    query heads are split [Hkv, H / Hkv] and the keys are read as they
    are cached, never copied out to H heads."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv == H:
        return jnp.einsum(eq, q, k)
    s = jnp.einsum("btkgd,bskd->bkgts", q.reshape(B, T, Hkv, H // Hkv, D), k)
    return s.reshape(B, H, T, k.shape[1])


def _paged_pv(p, v, eq):
    """p [B, H, T, S] over v [B, S, Hkv, D]: what ``eq`` gives for equal
    head counts ([B, T, H, D] or [B, H, T, D]), with grouped queries by
    the same split."""
    B, H, T, S = p.shape
    Hkv = v.shape[2]
    if Hkv == H:
        return jnp.einsum(eq, p, v)
    out = jnp.einsum("bkgts,bskd->bkgtd", p.reshape(B, Hkv, H // Hkv, T, S),
                     v).reshape(B, H, T, v.shape[-1])
    return out if eq.endswith("bhtd") else out.transpose(0, 2, 1, 3)


def _paged_gather_impl(q, k_pages, v_pages, block_tables, q_positions,
                       kv_lens, k_scale=None, v_scale=None):
    """q: [B, T, H, D]; k_pages/v_pages: [N, bs, Hkv, D], or with the
    heads flattened [N, bs, Hkv D] (+ [N, bs, Hkv] scales for int8
    pools), H a multiple of Hkv; block_tables: [B, M] int32; q_positions:
    [B, T] int32 global positions; kv_lens: [B] int32 valid prefix."""
    B, T, H, D = q.shape
    bs = k_pages.shape[1]
    M = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    k = _paged_widen(k_pages[block_tables],
                     None if k_scale is None else k_scale[block_tables],
                     jnp.float32).reshape(B, M * bs, -1, D)
    v = _paged_widen(v_pages[block_tables],
                     None if v_scale is None else v_scale[block_tables],
                     jnp.float32).reshape(B, M * bs, -1, D)
    s = _paged_scores(q.astype(jnp.float32) * scale, k, "bthd,bkhd->bhtk")
    k_pos = jnp.arange(M * bs, dtype=jnp.int32)
    mask = _paged_mask(k_pos, q_positions, kv_lens)
    s = jnp.where(mask[:, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    out = _paged_pv(p, v, "bhtk,bkhd->bthd")
    return out.astype(q.dtype)


_LANES = 128  # the minor tile of the chip: a vector register's lanes


def _lane_group(T, H, D, k_pages, k_scale):
    """Heads that share one 128-lane group of a flat page, or 0 where the
    scan takes the heads as an axis. A group form is taken for a decode
    step (one query a row) over float pages with the heads flattened, as
    many key heads as query heads, a head narrower than the lanes and
    whole groups of heads (GPT's 16 heads of 64: 2 a group).

    Why, measured on a v5e at GPT-2 345M's widths (PERF.md, section 6,
    PR 36): a page reshaped to ``[.., heads, 64]`` is padded to 128 lanes
    and laid out anew, 4.8 us a gathered page of 64 rows, twice an
    iteration and once a page whatever the queries; cut into groups of 128
    lanes it keeps the layout it was gathered in, at the price of the
    neighbour heads' products, paid once a query. One query a row: a step
    of 28.4 ms against 51.8. A prefill chunk (one row of 256 queries, a
    page of 16 rows to relay): 16.3 ms against 12.0, so it keeps the heads
    as an axis. Between the two nothing is measured."""
    if T != 1 or k_scale is not None or k_pages.ndim != 3 or D >= _LANES \
            or _LANES % D or k_pages.shape[2] != H * D:
        return 0
    r = _LANES // D
    return 0 if H % r else r


def table_slots_live(kv_lens, block_size: int, width: int, xp=jnp):
    """Table slots that the round's longest row fills: where the paged
    scan stops. ``xp`` is ``jnp`` for the traced lengths of a step, ``np``
    for the scheduler's own, which counts them
    (``counter/serve/table_slots_live``)."""
    return xp.minimum((xp.max(kv_lens) + block_size - 1) // block_size,
                      width)


def _paged_scan_impl(q, k_pages, v_pages, block_tables, q_positions,
                     kv_lens, k_scale=None, v_scale=None):
    """Online-softmax scan over table slots — the flash recurrence over
    pages. Only one [B, bs, H, D] page pair is live (and, for int8
    pools, dequantized) per step. The walk stops at the slot that holds
    the longest row's last position (``table_slots_live``): a slot past it
    is masked in every row, so skipping it changes no live row's output
    (it was ``acc * 1 + 0``) and its pages are never read.

    Two forms of one recurrence, chosen by ``_lane_group`` from the
    queries, the pages and the heads: with the heads as an axis (``[B, bs,
    Hkv, D]`` pages: int8 pools, grouped queries, heads a lane group wide
    or wider, chunks of many queries), or, for a decode step over flat
    float pages of narrow heads, a lane group at a time: the page is read
    as ``[B, bs, G, 128]``, each head's query sits in its own lanes of its
    group with zeros in its neighbours', and the running output is kept
    flat, ``[B, G, T, 128]``, so nothing in the loop lays a page out anew.
    The products and their order are those of the first form plus exact
    zeros."""
    B, T, H, D = q.shape
    bs = k_pages.shape[1]
    M = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32) * scale
    r = _lane_group(T, H, D, k_pages, k_scale)

    if r:
        G = H // r
        # own[j, 0, lane]: the lane belongs to its group's j-th head
        own = (jnp.arange(_LANES)[None, :] // D
               == jnp.arange(r)[:, None])[:, None, :]
        qm = jnp.where(own[:, 0], qf.reshape(B, T, G, 1, _LANES), 0.0)

        def to_lanes(x):  # a number a head [B, H, T] -> over its lanes
            return jnp.where(own, x.reshape(B, G, r, T, 1), 0.0).sum(axis=2)

        def scores(kc):
            return jnp.einsum("btgjl,bsgl->bgjts", qm,
                              kc.reshape(B, bs, G, _LANES)
                              ).reshape(B, H, T, bs)

        def advance(acc, corr, p, vc):
            o = jnp.einsum("bgjts,bsgl->bgjtl", p.reshape(B, G, r, T, bs),
                           vc.reshape(B, bs, G, _LANES))
            return acc * to_lanes(corr) + jnp.where(own, o, 0.0).sum(axis=2)

        acc0 = jnp.zeros((B, G, T, _LANES), jnp.float32)
    else:
        def scores(kc):
            if kc.ndim == 3:  # a pool that keeps the heads flattened
                kc = kc.reshape(B, bs, -1, D)
            return _paged_scores(qf, kc, "bthd,bshd->bhts")

        def advance(acc, corr, p, vc):
            if vc.ndim == 3:
                vc = vc.reshape(B, bs, -1, D)
            return (acc * corr[..., None]
                    + _paged_pv(p, vc, "bhts,bshd->bhtd"))

        acc0 = jnp.zeros((B, H, T, D), jnp.float32)

    def body(i, carry):
        acc, m, l = carry
        pids = block_tables[:, i]  # [B]
        kc = _paged_widen(k_pages[pids],
                          None if k_scale is None else k_scale[pids],
                          jnp.float32)  # [B, bs, Hkv, D] or [B, bs, Hkv D]
        vc = _paged_widen(v_pages[pids],
                          None if v_scale is None else v_scale[pids],
                          jnp.float32)
        s = scores(kc)
        k_pos = i * bs + jnp.arange(bs, dtype=jnp.int32)
        mask = _paged_mask(k_pos, q_positions, kv_lens)
        s = jnp.where(mask[:, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        acc = advance(acc, corr, p, vc)
        l = l * corr + p.sum(axis=-1)
        return acc, m_new, l

    m0 = jnp.full((B, H, T), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    steps = table_slots_live(kv_lens.astype(jnp.int32), bs, M)
    acc, _, l = jax.lax.fori_loop(jnp.int32(0), steps, body,
                                  (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    if r:  # [B, G, T, lanes] -> [B, T, H, D]
        out = (acc / to_lanes(l)).transpose(0, 2, 1, 3).reshape(B, T, H, D)
    else:
        out = (acc / l[..., None]).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, q_positions,
                    kv_lens, k_scale=None, v_scale=None):
    """Attention of a query chunk against a paged KV cache.

    Args:
        q: [B, T, H, D] query chunk (T=1 plain decode; T=k+1 speculative
            verify; T=chunk_size chunked prefill).
        k_pages/v_pages: one layer's pool pages [N, bs, Hkv, D], or
            [N, bs, Hkv D] with the heads flattened (int8 or float
            storage); H is a multiple of Hkv (grouped queries), and the
            pages are read as they lie, never copied out to H heads.
        block_tables: [B, M] int32 page ids (scratch-padded).
        q_positions: [B, T] int32 global position of each query token.
        kv_lens: [B] int32 — number of valid cache positions (tokens of
            the sequence INCLUDING this chunk's writes).
        k_scale/v_scale: [N, bs, H] float32 per-token-head scales when
            the pool stores int8 (``quant.quantize_kv``), else None.

    Tier selection happens at TRACE time via
    ``tier_policy.select_paged`` — micro-benched on TPU, verdict-cached,
    zero per-step work — and every dispatch publishes its verdict to
    ``gauge/attn/tier.paged.*`` (the attribution tier gate covers decode
    records like every other attention-bearing record)."""
    from ..profiler.telemetry import get_telemetry
    from . import tier_policy

    get_telemetry().counter("attn/calls")
    B, T, H, D = q.shape
    bs = k_pages.shape[1]
    M = block_tables.shape[1]
    Hkv = k_pages.shape[2] if k_pages.ndim == 4 else k_pages.shape[2] // D
    tier = tier_policy.select_paged(T, H, D, M, bs, q.dtype,
                                    k_scale is not None, hkv=Hkv)
    get_telemetry().gauge(f"attn/tier.paged.t{T}.d{D}",
                          tier_policy.TIER_IDS.get(tier, -1))
    impl = (_paged_gather_impl if tier == "paged_gather"
            else _paged_scan_impl)
    return impl(q, k_pages, v_pages, block_tables, q_positions, kv_lens,
                k_scale, v_scale)


# ---------------------------------------------------------------------------
# Latent (MLA) attention over a paged latent cache
# ---------------------------------------------------------------------------
# A cached token is one row a layer, [c | rotated k_r] (512 + 64 columns,
# stored with zeros up to whole lanes: 640): one key shared by all the
# heads, whose first columns are also the value once W_kvb is folded into
# the query and the output. Two forms of one
# walk over the row's table, chosen by ``_mla_form`` from the queries a row:
# - 'mla_absorbed' (a decode or verify step): q_n W_k is made once a query
#   and scored against the latent rows as they lie; the output leaves the
#   latent space through W_v. 2 (576 + 512) operations a head, query and
#   cached row, and no key or value a head is ever made.
# - 'mla_expanded' (a prefill chunk): a group of cached rows is taken up
#   through W_kvb to keys and values a head (2 * 512 * 256 operations a
#   head and cached row, whatever the queries) and scored as plain
#   attention, 2 (192 + 128) a head, query and row.
# By operations alone the forms cross at 262,144 / (2,176 - 640) = 171
# queries a row. Read on a v5e at the served widths (128 heads, rows stored
# 640 wide, a table of 320 slots; my chip runs, PR 37, a probe that called
# this function alone, not kept, medians of 8 calls): a decode step of 64
# rows, one query each, 1.10 / 1.66 / 2.76 ms absorbed at 512 / 2,048 /
# 5,120 cached rows a sequence (2.69 with lengths as the cell mixes them:
# the walk goes to the longest row) against 36.7 ms expanded at 2,048; two
# queries a row (a verify step) 1.93 ms absorbed; a chunk of 512 queries
# 1.81 ms expanded against 3.02 absorbed at 512 cached rows and 8.63
# against 12.40 at 4,608. Between 2 and 512 queries a row nothing is
# served and nothing was measured: the rule takes the measured ends and
# puts the line just under the crossing of the operations.
_MLA_EXPAND_MIN_T = 128
# cached rows a step of the walk: the loop runs to the longest row's last
# group and no further, so a table slot that no row has filled costs nothing
# past it. The absorbed form reads alike at 256, 512 and 1,024 (2.82 / 2.76 /
# 2.69 ms at 5,120 cached); the expanded one, which makes keys and values a
# head of every group, reads 8.63 / 11.40 / 12.48 ms at 4,608 cached
_MLA_GROUP = {"mla_absorbed": 512, "mla_expanded": 256}


def _mla_form(T: int) -> str:
    return "mla_expanded" if T >= _MLA_EXPAND_MIN_T else "mla_absorbed"


def mla_paged_attention(q_nope, q_rope, w_kvb, latent_pages, block_tables,
                        q_positions, kv_lens, v_dim: int, form: str = None):
    """Latent attention of a query chunk against a paged latent cache.

    Args:
        q_nope: [B, T, H, nope] the queries' columns that meet k_n.
        q_rope: [B, T, H, rope] their rotated columns, which meet k_r.
        w_kvb: [latent, H * (nope + v_dim)]: the up-projection of the
            latent to a head's k_n and v.
        latent_pages: one layer's pages [N, bs, width], width at least
            latent + rope: a row is [c | rotated k_r | zeros], and the
            queries are met with zeros past their own columns, so that no
            product slices a row off its lanes.
        block_tables, q_positions, kv_lens: as ``paged_attention``.
        form: 'mla_absorbed' | 'mla_expanded'; None: ``_mla_form``'s rule.

    Returns [B, T, H, v_dim] in the queries' type. Products take their
    operands as they are stored (bfloat16 pages are never widened) and
    accumulate in float32; the softmax is float32."""
    from ..profiler.telemetry import get_telemetry
    from . import tier_policy

    B, T, H, nope = q_nope.shape
    width = latent_pages.shape[-1]
    latent = w_kvb.shape[0]
    if q_rope.shape[-1] + latent > width:
        raise ValueError(f"a row of {width} holds no latent of {latent} "
                         f"beside {q_rope.shape[-1]} rotated columns")
    rope = q_rope.shape[-1]
    # the rotated columns and the row's padding: one lane-aligned slice
    q_rope = jnp.pad(q_rope, ((0, 0),) * 3 + ((0, width - latent - rope),))
    bs = latent_pages.shape[1]
    form = form or _mla_form(T)
    if form not in _MLA_GROUP:
        raise ValueError(f"mla_paged_attention: no form {form!r}")
    tel = get_telemetry()
    tel.counter("attn/calls")
    tel.gauge(f"attn/tier.mla.t{T}", tier_policy.TIER_IDS[form])
    slots = max(1, min(block_tables.shape[1], _MLA_GROUP[form] // bs))
    pad = -block_tables.shape[1] % slots
    if pad:  # whole groups: the scratch page, which kv_lens masks
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    group = slots * bs
    steps = (jnp.max(kv_lens).astype(jnp.int32) + group - 1) // group
    scale = 1.0 / math.sqrt(nope + rope)
    w = w_kvb.reshape(latent, H, nope + v_dim)
    f32 = jnp.float32
    if form == "mla_absorbed":
        q_lat = jnp.einsum("bthn,chn->bthc", q_nope, w[..., :nope],
                           preferred_element_type=f32)
        qf = (jnp.concatenate([q_lat, q_rope.astype(f32)], axis=-1)
              * scale).astype(q_nope.dtype)

        def scores(rows):
            return jnp.einsum("bthw,bsw->bhts", qf, rows,
                              preferred_element_type=f32), rows[..., :latent]

        def weigh(p, values):
            return jnp.einsum("bhts,bsc->bhtc", p.astype(values.dtype),
                              values, preferred_element_type=f32)

        out_width = latent
    else:
        qn = (q_nope.astype(f32) * scale).astype(q_nope.dtype)
        qr = (q_rope.astype(f32) * scale).astype(q_rope.dtype)

        def scores(rows):
            kv = jnp.einsum("bsc,chk->bshk", rows[..., :latent], w,
                            preferred_element_type=f32).astype(rows.dtype)
            s = (jnp.einsum("bthn,bshn->bhts", qn, kv[..., :nope],
                            preferred_element_type=f32)
                 + jnp.einsum("bthr,bsr->bhts", qr, rows[..., latent:],
                              preferred_element_type=f32))
            return s, kv[..., nope:]

        def weigh(p, values):
            return jnp.einsum("bhts,bshv->bhtv", p.astype(values.dtype),
                              values, preferred_element_type=f32)

        out_width = v_dim

    def body(i, carry):
        acc, m, l = carry
        pids = jax.lax.dynamic_slice_in_dim(block_tables, i * slots, slots,
                                            axis=1)
        rows = latent_pages[pids].reshape(B, group, width)
        s, values = scores(rows)
        k_pos = i * group + jnp.arange(group, dtype=jnp.int32)
        s = jnp.where(_paged_mask(k_pos, q_positions, kv_lens)[:, None], s,
                      _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        return (acc * corr[..., None] + weigh(p, values), m_new,
                l * corr + p.sum(axis=-1))

    acc, _, l = jax.lax.fori_loop(
        jnp.int32(0), steps, body,
        (jnp.zeros((B, H, T, out_width), f32),
         jnp.full((B, H, T), _NEG_INF, f32), jnp.zeros((B, H, T), f32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    if form == "mla_absorbed":
        out = jnp.einsum("bhtc,chv->bthv", out.astype(q_nope.dtype),
                         w[..., nope:], preferred_element_type=f32)
    else:
        out = out.transpose(0, 2, 1, 3)
    return out.astype(q_nope.dtype)


# ---------------------------------------------------------------------------
# Materialized XLA attention (TPU fast path for moderate sequence lengths)
# ---------------------------------------------------------------------------
# One chunk body serves every call, causal or not, biased or not: a call
# is cut into q-chunks by `_q_chunks`; a chunk is query rows [lo, hi)
# against keys [0, ub), an optional static tril mask and an optional bias
# slice (`_chunk_logits`), then exp, row sum, PV and the divide on the
# output (`_weights_pv`; inline with bf16 row statistics for the causal
# unbiased call, `_bf16_row_stats`).
#
# least rows of a q-chunk, and most chunks of a call: more causal chunks
# skip more of the masked triangle but emit more operations. Together they
# give L = 1024 -> 8 chunks of 128 rows (GPT-2 345M's call) and
# L = 8192 -> 32 chunks of 256.
_CAUSAL_CHUNK = 128
_CAUSAL_MAX_CHUNKS = 32
# scores are STORED in the inputs' dtype for bf16/f16 inputs: the centred
# logits already round-trip through bf16 before exp, and softmax cancels
# the max shift exactly (m only guards overflow), so bf16-stored scores are
# numerically ~equivalent (~1 ulp of bf16 either way) while halving the
# O(L²) tensor's bytes.
_SCORE_BF16 = True
# the causal unbiased call's hand-written backward (`_causal_chunked_bwd`,
# the latent call's) makes the exp weights again from a chunk's row maxima
# instead of saving them: one more QK einsum and exp a chunk, for the
# largest residual of the call, flash attention's trade at the XLA level.
_REMAT_E = True


def _einsum_eqs(blhd: bool):
    return (("bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd") if blhd
            else ("bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"))


def _q_chunk_size(Lq: int, max_chunks: int = None):
    """Rows of a q-chunk, or None when no exact chunking exists (c must
    divide Lq — a truncated concat would silently drop query rows)."""
    c = max(_CAUSAL_CHUNK, Lq // max(max_chunks or _CAUSAL_MAX_CHUNKS, 1))
    if Lq % c != 0 or Lq // c < 2:
        return None
    return c


# a latent call (values narrower than the keys) has many wide heads: at
# L = 8192 its 32 unrolled chunks, forward and backward, were 48 MB of
# compiled code and 25 s of the v5e compiler's time for one layer; 16
# chunks of 512 rows halve both, for 3% more of the masked triangle
_LATENT_MAX_CHUNKS = 16


def _latent(q, v) -> bool:
    """Values narrower (or wider) than the keys: latent attention."""
    return v.shape[-1] != q.shape[-1]


def _call_chunks(q, k, v, blhd: bool, causal: bool):
    """The chunks of this call (`_q_chunks`), by its operands alone, so
    that a backward cuts as its forward did."""
    axis_l = 1 if blhd else 2
    return _q_chunks(q.shape[axis_l], k.shape[axis_l], causal,
                     _LATENT_MAX_CHUNKS if _latent(q, v) else None)


def _q_chunks(Lq: int, Lk: int, causal: bool, max_chunks: int = None):
    """The call's chunks as (lo, hi, ub): query rows [lo, hi) against keys
    [0, ub). Self-attention of a length `_q_chunk_size` divides is cut
    into chunks of that many rows: a causal chunk stops at its diagonal
    (ub = hi: the fully-masked upper-triangle blocks are never computed),
    a non-causal one sees every key. Everything else (Lq != Lk, the unit
    tests' L = 16) is one chunk over the whole score rectangle."""
    c = _q_chunk_size(Lq, max_chunks) if Lq == Lk else None
    if c is None:
        return ((0, Lq, Lk),)
    return tuple((lo, lo + c, lo + c if causal else Lk)
                 for lo in range(0, Lq, c))


# backward einsum equations per layout: dP ('dO,V->P-shape'), dq
# ('dS,K->q-shape'), dk ('dS,Q->k-shape'), dv ('E,dO->v-shape'), delta
# ('dO,O->rows')
_BWD_EQS = {
    True: ("bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd", "bhqk,bqhd->bkhd",
           "bhqk,bqhd->bkhd", "bqhd,bqhd->bhq"),
    False: ("bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd", "bhqk,bhqd->bhkd",
            "bhqk,bhqd->bhkd", "bhqd,bhqd->bhq"),
}


def _inv_rows(inv, blhd):
    """Broadcast a [b,h,q] row statistic against [.., q-axis, .., d]."""
    return inv.transpose(0, 2, 1)[..., None] if blhd else inv[..., None]


def _bf16_row_stats(causal: bool, bias) -> bool:
    """The causal unbiased call keeps autodiff's backward of the chunk's
    tail, with the weights' cotangent and 1/l rounded to the input dtype:
    the program GPT-2 345M was tuned and accepted with, pinned by
    tests/attention_fixtures. Every other call takes `_weights_pv`."""
    return causal and bias is None


def _chunk_logits(q, k, chunk, blhd, causal, bias=None, m=None):
    """Centred logits of one chunk, as f32: x = s − max(s), s = scaled QKᵀ
    (+ bias) under the chunk's static tril mask when causal. With the
    saved per-chunk max passed as ``m`` the values are BITWISE the
    forward's (same ops, same operands). ``bias`` broadcasts against
    [b, h, Lq, Lk] and is sliced to the chunk here. Returns (x, m)."""
    lo, hi, ub = chunk
    axis_l = 1 if blhd else 2
    sl = functools.partial(jax.lax.slice_in_dim, axis=axis_l)
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d)
    eq = _einsum_eqs(blhd)
    bf = (jnp.issubdtype(q.dtype, jnp.floating) and q.dtype != jnp.float32)
    sdt = q.dtype if (_SCORE_BF16 and bf) else jnp.float32
    neg = jnp.asarray(_NEG_INF if sdt == jnp.float32 else -3e38, sdt)
    qi = sl(q, lo, hi) * jnp.asarray(scale, q.dtype)
    ki = sl(k, 0, ub)
    if bias is None:
        s = jnp.einsum(eq[0], qi, ki, preferred_element_type=sdt)
    else:
        # the bias joins the f32-accumulated scores inside the QK fusion,
        # before they are rounded to their storage dtype; the row maximum
        # below is taken after it (a −1e9 padding column never sets it)
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
        if bias.shape[2] != 1:
            bias = jax.lax.slice_in_dim(bias, lo, hi, axis=2)
        if bias.shape[3] != 1:
            bias = jax.lax.slice_in_dim(bias, 0, ub, axis=3)
        s = (jnp.einsum(eq[0], qi, ki, preferred_element_type=jnp.float32)
             + bias).astype(sdt)
    if causal:
        # top-left aligned (k_pos <= q_pos), matching blockwise/flash so
        # the dispatch tiers agree for Lq != Lk
        mask = jnp.tril(jnp.ones((hi - lo, ub), bool), k=lo)
        s = jnp.where(mask, s, neg)
    if m is None:
        m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    if sdt != jnp.float32:
        return (s - m).astype(q.dtype).astype(jnp.float32), m
    return s - m, m


def _chunk_e(q, k, chunk, blhd, causal, m=None):
    """exp weights of one unbiased chunk, UNNORMALIZED and MATERIALIZED in
    the input dtype (exp computed in f32 per-element, rounded on store):
    for bf16 models this halves the O(L²) exp tensor's bytes in fwd AND in
    the saved residual the backward re-reads — values in (0, 1], safe in
    bf16, and the f32-accumulated row sum normalizes the same bf16 weights
    the PV einsum consumes (profiled: the f32 exp store was 25 ms/step of
    divide_subtract fusions). Returns (e, m)."""
    x, m = _chunk_logits(q, k, chunk, blhd, causal, m=m)
    return jnp.exp(x).astype(q.dtype), m


def _weights_pv_impl(x, v, blhd, dtype):
    e = jnp.exp(x).astype(dtype)
    inv = 1.0 / jnp.maximum(e.sum(axis=-1, dtype=jnp.float32), 1e-30)
    o = jnp.einsum(_einsum_eqs(blhd)[1], e, v,
                   preferred_element_type=jnp.float32)
    return o * _inv_rows(inv, blhd), (e, v, o, inv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _weights_pv(x, v, blhd, dtype):
    """A chunk's rows of softmax(x)·v, in f32, from its centred logits
    ``x`` and the (centred, `_centred`) values: the UNNORMALIZED weights
    e = exp(x), rounded to ``dtype``, feed the PV matmul, their f32 row
    sum l is taken over the rounded values, and the 1/l multiply runs on
    the [.., c, d] output.

    The backward is written out: dS = e ⊙ (dP − c), with dO rounded once
    for both the dP matmul and c, and c taken from the forward's own f32
    PV sums, so that a row of dS sums to zero (why that matters, and why
    the values come centred: `_centred`)."""
    return _weights_pv_impl(x, v, blhd, dtype)[0]


def _weights_pv_fwd(x, v, blhd, dtype):
    return _weights_pv_impl(x, v, blhd, dtype)


def _weights_pv_bwd(blhd, dtype, res, g):
    e, v, o, inv = res
    dP_eq, _, _, dv_eq, _ = _BWD_EQS[blhd]
    rows = lambda t: t.transpose(0, 2, 1) if blhd else t  # -> [b, h, q]
    dO = (g * _inv_rows(inv, blhd)).astype(dtype)
    dP = jnp.einsum(dP_eq, dO, v, preferred_element_type=jnp.float32)
    c = rows((dO.astype(jnp.float32) * o).sum(axis=-1)) * inv
    # masked positions need no re-masking: e is exactly 0 there
    return (e.astype(jnp.float32) * (dP - c[..., None]),
            jnp.einsum(dv_eq, e, dO))


_weights_pv.defvjp(_weights_pv_fwd, _weights_pv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _centred(v, axis):
    """(v − v̄, v̄): the values as their distance from their mean over the
    keys, rounded to their own dtype, and that mean in f32. Attention's
    output is v̄ + Σ softmax·(v − v̄), the same number, and its gradient
    for the values is the plain one (the two ways v̄ enters cancel), which
    is what the backward hands on.

    Why: dS = e ⊙ (dP − c), where c is the row's weighted mean of dP, and
    a row of dS sums to zero. Where a row's values are alike (BERT's deep
    post-LN layers at initialisation: near-uniform attention has averaged
    the tokens together) dP = dO·v is nearly constant along the row and
    dS is what is left of a cancellation. Whatever a one-pass backward
    takes c from (g·o in autodiff, dO·o in `_weights_pv`) has to agree
    with the dP matmul to far better than a bf16 rounding of the whole of
    dP, and on the v5e it does not: the rows stop summing to zero, the key
    bias's gradient, zero in exact arithmetic, carries 15 times the noise
    it has behind a softmax normalised in score space, and Adam turns
    that into whole steps (BERT-large: `change_norm_gap` 0.11-0.18 on
    `qkv_b[21..23]` against 0.006; PERF.md section 6 "PR 29"). With the
    mean taken off the values first, dP and c are both small where the
    values are alike, and nothing large has to cancel."""
    v32 = v.astype(jnp.float32)
    vbar = v32.mean(axis=axis, keepdims=True)
    return (v32 - vbar).astype(v.dtype), vbar


def _centred_fwd(v, axis):
    return _centred(v, axis), None


def _centred_bwd(axis, _, ct):
    return (ct[0],)


_centred.defvjp(_centred_fwd, _centred_bwd)


def _chunk_attend(q, k, vc, vbar, bias, chunk, blhd, causal):
    """One chunk's rows of the output, from the centred values
    (`_centred`): the 1/sqrt(d) scale folds into the [.., c, d] query
    chunk, not the score tensor, and the normalisation runs on the
    [.., c, d] output instead of the [.., c, L] scores — one full O(L²)
    elementwise pass (read + write) removed per chunk (flash's trick,
    expressed at the XLA level)."""
    axis_l = 1 if blhd else 2
    x, _ = _chunk_logits(q, k, chunk, blhd, causal, bias)
    vi = jax.lax.slice_in_dim(vc, 0, chunk[2], axis=axis_l)
    return (_weights_pv(x, vi, blhd, q.dtype) + vbar).astype(q.dtype)


# the chunks of one call, and of every layer after the first, are traced
# once a shape: an unrolled BERT-large (96 chunks, forward and backward)
# cost 3.9 s of set-up in tracing and lowering, 1.9 s with this (PERF.md
# section 6 "PR 29")
_chunk_attend_jit = jax.jit(_chunk_attend,
                            static_argnames=("chunk", "blhd", "causal"))


def _chunked_fwd_impl(q, k, v, blhd: bool, causal: bool, bias=None):
    """Forward pass; returns (out, residuals per chunk, for
    `_causal_chunked_bwd`, where `_bf16_row_stats`). Residual slot 4 holds
    the exp weights or, under `_REMAT_E`, their per-chunk row maxima."""
    from ..profiler.telemetry import get_telemetry

    axis_l = 1 if blhd else 2
    chunks = _call_chunks(q, k, v, blhd, causal)
    # trace-time fact, like attn/calls: the chunks this call emits
    get_telemetry().counter(
        "attn/xla_chunks." + ("c" if causal else "f"), len(chunks))
    join = lambda outs: (jnp.concatenate(outs, axis=axis_l)
                         if len(outs) > 1 else outs[0])
    if not _bf16_row_stats(causal, bias):
        vc, vbar = _centred(v, axis_l)
        return join([_chunk_attend_jit(q, k, vc, vbar, bias, chunk=chunk,
                                       blhd=blhd, causal=causal)
                     for chunk in chunks]), None
    sl = functools.partial(jax.lax.slice_in_dim, axis=axis_l)
    eq = _einsum_eqs(blhd)
    outs, aux, invs = [], [], []
    for chunk in chunks:
        e, m = _chunk_e(q, k, chunk, blhd, causal)
        vi = sl(v, 0, chunk[2])
        l_sum = jnp.maximum(e.sum(axis=-1, dtype=jnp.float32), 1e-30)
        o = jnp.einsum(eq[1], e.astype(q.dtype), vi)
        inv = (1.0 / l_sum).astype(q.dtype)
        outs.append(o * _inv_rows(inv, blhd))
        aux.append(m if _REMAT_E else e)
        invs.append(inv)
    out = join(outs)
    return out, (q, k, v, out, tuple(aux), tuple(invs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _causal_chunked(q, k, v, blhd: bool):
    """Causal unbiased self-attention through `_chunked_fwd_impl` with a
    hand-written backward (`_causal_chunked_bwd`), the latent call's by
    rule (`xla_attention`): every backward contraction stays in
    the forward's layout family and the 1/l normalization folds into the
    [.., c, d] dO chunk (flash's backward trick at the XLA level), so no
    O(L²) divide pass exists in either direction."""
    out, _ = _chunked_fwd_impl(q, k, v, blhd, True)
    return out


def _causal_chunked_fwd(q, k, v, blhd):
    return _chunked_fwd_impl(q, k, v, blhd, True)


def _causal_chunked_bwd(blhd, res, g):
    q, k, v, out, aux, invs = res
    axis_l = 1 if blhd else 2
    Lq = q.shape[axis_l]
    sl = functools.partial(jax.lax.slice_in_dim, axis=axis_l)
    d = q.shape[-1]
    scale = jnp.asarray(1.0 / math.sqrt(d), q.dtype)
    dP_eq, dq_eq, dk_eq, dv_eq, delta_eq = _BWD_EQS[blhd]

    dqs, dks, dvs = [], [], []
    for i, chunk in enumerate(_call_chunks(q, k, v, blhd, True)):
        lo, hi, ub = chunk
        qi = sl(q, lo, hi)
        ki, vi = sl(k, 0, ub), sl(v, 0, ub)
        gi = sl(g, lo, hi)
        oi = sl(out, lo, hi)
        if _REMAT_E:  # aux holds the chunk maxima; e recomputed bitwise
            e, _ = _chunk_e(q, k, chunk, blhd, True, m=aux[i])
        else:
            e = aux[i]
        inv = invs[i]
        # softmax backward with the normalization folded into dO:
        #   P = e·inv;  dS = P ⊙ (dP − rowsum(dP ⊙ P))
        #             = e ⊙ (dP·inv − rowsum(dO ⊙ O)·inv)
        # rowsum(dP ⊙ P) collapses to rowsum(dO ⊙ O) — computed on the
        # [.., c, d] output, never touching the [.., c, L] score tensor
        g_inv = (gi * _inv_rows(inv, blhd)).astype(q.dtype)
        delta = jnp.einsum(delta_eq, gi, oi,
                           preferred_element_type=jnp.float32)
        dP = jnp.einsum(dP_eq, g_inv, vi, preferred_element_type=jnp.float32)
        dS = (e.astype(jnp.float32)
              * (dP - (delta * inv.astype(jnp.float32))[..., None])
              ).astype(q.dtype)
        # masked positions need no re-masking: e is exactly 0 there
        dqs.append(jnp.einsum(dq_eq, dS, ki) * scale)
        # pad-to-L and tree-sum: measured BEST of three accumulation
        # shapes for the ragged dk/dv chunk contributions on v5e (ragged
        # per-block slice+sum+concat re-lowered to 2.8× the
        # dynamic-update-slice traffic)
        pad = [(0, 0)] * q.ndim
        pad[axis_l] = (0, Lq - ub)
        dks.append(jnp.pad(jnp.einsum(dk_eq, dS, qi) * scale, pad))
        dvs.append(jnp.pad(jnp.einsum(dv_eq, e.astype(q.dtype), g_inv), pad))
    dq = jnp.concatenate(dqs, axis=axis_l)
    dk = sum(dks[1:], dks[0])
    dv = sum(dvs[1:], dvs[0])
    return dq, dk, dv


_causal_chunked.defvjp(_causal_chunked_fwd, _causal_chunked_bwd)


def xla_attention(q, k, v, causal=False, bias=None, layout="bhld"):
    """softmax(QKᵀ + bias)V with the [Lq, Lk] scores materialized
    (XLA-level), one q-chunked body for every call (`_chunked_fwd_impl`).

    TPU-first details (profile-driven on the v5e; GPT-2 345M's causal call
    and BERT-large's biased non-causal one, PERF.md section 6):
    - scores ACCUMULATE in f32 on the MXU regardless of storage dtype; for
      bf16/f16 inputs the stored scores, centered logits, and unnormalized
      probabilities round-trip through the input dtype (`_SCORE_BF16`) —
      softmax cancels the max shift exactly, so this is numerically ~1 ulp
      of bf16 either way while halving the O(L²) HBM bytes;
    - self-attention runs q-chunked (`_q_chunks`): a **causal** chunk only
      matmuls keys ≤ its diagonal, skipping the fully-masked upper-triangle
      blocks (~45% of attention compute/bandwidth at 8 chunks); a
      non-causal chunk sees every key; cross-attention and lengths with no
      exact chunking run the same body as one chunk;
    - ``bias`` (anything that broadcasts against [b, h, Lq, Lk], under
      either layout) is added to the f32-accumulated scores inside the
      chunk, and the row maximum is taken after it;
    - softmax normalization is deferred until after the PV matmul: the
      divide runs on the [.., c, d] output, never in score space;
    - ``layout='blhd'`` contracts [b, l, h, d] operands directly, letting
      the model skip the four [b,h,l,d] transpose copies per layer.
    The causal unbiased call's backward is autodiff of that forward (or,
    for a latent call, `_causal_chunked_bwd`); every other call sees its
    values centred on their mean over the keys (`_centred`) and takes a
    hand-written rule for the chunk's tail (`_weights_pv`), so that the
    rows of dS sum to zero in bf16 too.
    """
    blhd = layout == "blhd"
    # the hand-written backward keeps a chunk's row maxima and makes its
    # exp weights again: a latent call takes it by rule, because at 32
    # heads of 8192 keys autodiff's saved weights are 2.3 GB a layer and
    # were the step's peak
    if (_latent(q, v) and causal and bias is None
            and len(_call_chunks(q, k, v, blhd, True)) > 1):
        return _causal_chunked(q, k, v, blhd)
    return _chunked_fwd_impl(q, k, v, blhd, causal, bias)[0]


# ---------------------------------------------------------------------------
# Public dispatch
# ---------------------------------------------------------------------------
# one-shot fallback warnings, keyed (tier, shape, reason)
_fallback_warned: set = set()


def _count_fallback(tier: str, shape, reason: str) -> None:
    """A dispatch decision silently rerouted off a fast tier: count it
    (``counter/attn/tier_fallbacks`` — gated to ZERO over bench records
    by tools/check_attribution.py) and warn once per (tier, shape). A
    10x slowdown must never be invisible."""
    from ..profiler.telemetry import get_telemetry

    get_telemetry().counter("attn/tier_fallbacks")
    key = (tier, tuple(shape), reason)
    if key not in _fallback_warned:
        _fallback_warned.add(key)
        logger.warning(
            "attention: %s tier fell back for shape %s — %s (counted in "
            "counter/attn/tier_fallbacks; warned once per shape)",
            tier, tuple(shape), reason)


def dot_product_attention(q, k, v, causal=False, bias=None, sp_axis=None,
                          use_flash=True, layout="bhld"):
    """Attention, on the tier that `_tier` names for this call: a rule on
    the call, the backend and ``set_attention_impl``, decided at TRACE
    time and baked into the compiled program (zero per-step work, zero
    extra retraces). The tier's id is published as ``gauge/attn/tier.*``.

    ``layout='blhd'`` passes [b, l, h, d] operands straight into the XLA
    path (causal or not, with or without a ``bias``) and the flash_tpu
    path (no transpose copies); blockwise and ring get a transposed view
    and transpose back. ``v`` may be narrower or wider than ``q`` and
    ``k``: such a call takes the XLA path. ``bias`` broadcasts against
    [b, h, Lq, Lk] under either layout. ``use_flash=False`` asks for the
    exact f32 blockwise recurrence (the model-level flag selects numerics,
    not just a kernel).

    Whatever tier runs, its operations carry the ``attention`` scope:
    score space only (QK, mask or bias, softmax, PV), which a trace
    reduction tells from the projections around it."""
    with jax.named_scope("attention"):
        return _dispatch(q, k, v, causal, bias, sp_axis, use_flash, layout)


def _tier(q, k, v, causal, bias, sp_axis, use_flash, blhd) -> str:
    """The tier a call takes, the only place that decides it:

    - ``sp_axis`` given, or a ring mesh registered and `_ring_auto_ok`:
      ``ring``;
    - values narrower or wider than the keys (latent attention: 192
      against 128): ``xla``, the one tier that takes two head widths;
    - a tier named by ``set_attention_impl``: that tier (``flash_tpu``
      holds on a TPU for a causal unbiased call and is ``xla`` elsewhere);
    - ``use_flash=False``, or off the TPU: ``blockwise``;
    - on a TPU up to L = 8192 for a causal unbiased call and up to 4096
      for any other: ``xla``, which read 24.67 ms of the step against the
      jax-shipped Pallas kernel's 87.43 at GPT-2 345M's call (ledger,
      PR 29's GPT row);
    - past that, ``flash_tpu`` for a causal unbiased call and
      ``blockwise`` for the rest: 8-10x slower than either, but O(L) in
      memory;
    - ``flash_tpu`` on a shape its kernel does not tile
      (`_flash_tpu_fits`): ``blockwise``, counted in
      ``counter/attn/tier_fallbacks``."""
    L = q.shape[1 if blhd else 2]
    if sp_axis is not None or (_IMPL == "auto"
                               and _ring_auto_ok(L, causal, bias)):
        return "ring"
    if _latent(q, v):
        return "xla"
    on_tpu = jax.default_backend() == "tpu"
    plain = causal and bias is None
    if _IMPL != "auto":
        tier = _IMPL if (_IMPL != "flash_tpu" or (on_tpu and plain)) else "xla"
    elif not use_flash or not on_tpu:
        tier = "blockwise"
    elif L <= (_XLA_MAX_SEQ_CAUSAL if plain else _XLA_MAX_SEQ):
        tier = "xla"
    else:
        tier = "flash_tpu" if plain else "blockwise"
    if tier == "flash_tpu" and not _flash_tpu_fits(q, k, blhd=blhd):
        # the kernel's own fallback is the materialized O(L²) form, wrong
        # at this length: keep the memory-safe streaming path, and say so
        _count_fallback(
            "flash_tpu", q.shape,
            "shape does not fit the flash_tpu kernel (needs Lq == Lk, "
            "L % 256 == 0, heads*dim % 128 == 0, K and V of one batch "
            "row within its VMEM budget) — streaming via blockwise "
            "instead, ~8-10x slower at long L")
        tier = "blockwise"
    return tier


def _dispatch(q, k, v, causal, bias, sp_axis, use_flash, layout):
    from ..profiler.telemetry import get_telemetry
    from . import tier_policy

    blhd = layout == "blhd"
    # trace-time fact: how many attention dispatches the compiled entry
    # contains (marks a bench record "attention-bearing" for the tier
    # gate); in eager mode it counts calls, which is equally true
    get_telemetry().counter("attn/calls")
    tier = _tier(q, k, v, causal, bias, sp_axis, use_flash, blhd)
    # every call publishes its tier (with ``sp_axis`` L is the LOCAL
    # shard): the tier gate requires one on every attention-bearing record
    tier_policy.publish_tier(q.shape[1 if blhd else 2], q.shape[-1], causal,
                             tier)
    tr = lambda t: t.transpose(0, 2, 1, 3)
    if tier == "xla":
        return xla_attention(q, k, v, causal=causal, bias=bias, layout=layout)
    if tier == "flash_tpu":
        from .flash_tpu import flash_attention_blhd

        if blhd:
            return flash_attention_blhd(q, k, v, causal)
        return tr(flash_attention_blhd(tr(q), tr(k), tr(v), causal))
    if tier == "ring" and sp_axis is None:
        return _ring_sharded(q, k, v, causal, blhd)
    if blhd:  # ring_attention and the recurrence take [b, h, l, d]
        q, k, v = tr(q), tr(k), tr(v)
    if tier == "ring":
        out = ring_attention(q, k, v, sp_axis, causal, 512)
    else:
        out = blockwise_attention(q, k, v, causal=causal, bias=bias,
                                  block_k=_block_k(bias, use_flash))
    return tr(out) if blhd else out


def _block_k(bias, use_flash) -> int:
    """Key-block rows of the blockwise tier. Off the TPU the unbiased call
    reached this recurrence through the Pallas tier's fallback, in blocks
    of 256; it keeps them, so that the order of summation, and every CPU
    test's numbers, are what they were."""
    if (_IMPL == "auto" and use_flash and bias is None
            and jax.default_backend() != "tpu"):
        return 256
    return 512


def _flash_tpu_fits(q, k, blhd):
    """Shape gate of the flash_tpu kernel for `_tier`: self-attention only
    (Lq == Lk — the kernel reshapes k to q's length) and the kernel's own
    tiling and VMEM constraints."""
    from .flash_tpu import _fits

    if blhd:
        b, L, H, d = q.shape
        Lk = k.shape[1]
    else:
        b, H, L, d = q.shape
        Lk = k.shape[2]
    return Lk == L and _fits(b, L, H, d, 256, q.dtype.itemsize)
