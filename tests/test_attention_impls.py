"""Attention implementation tiers agree numerically (blockwise is the
reference recurrence; xla_attention is the materialized TPU fast path;
flash falls back to blockwise off-TPU) and the dispatch honors
set_attention_impl."""
import collections
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention as att
from paddle_tpu.profiler import get_telemetry, hlo_attrib

# operation histogram of `causal_program_ops()` at commit 4bd524f (PR 27),
# the parent of the PR that gave every call the one chunk body
CAUSAL_OPS_FIXTURE = os.path.join(os.path.dirname(__file__),
                                  "attention_fixtures",
                                  "causal_blhd_l1024_bf16_ops.json")


def rand_qkv(rng, b=2, h=4, L=64, d=32, dtype=jnp.float32):
    mk = lambda: jnp.asarray(rng.randn(b, h, L, d), dtype)
    return mk(), mk(), mk()


def naive_attention(q, k, v, causal=False, bias=None, blhd=False):
    """The plain masked softmax over the whole score rectangle, in the
    inputs' own precision: the tests' reference for the chunk body."""
    if blhd:
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    if bias is not None:
        s = s + bias
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    return o.transpose(0, 2, 1, 3) if blhd else o


def padding_bias(rng, b, Lk):
    """[b, 1, 1, Lk]: BertModel's bias, -1e9 on each row's padded tail."""
    lens = rng.randint(Lk // 2, Lk, size=b)
    return jnp.asarray(
        np.where(np.arange(Lk)[None] < lens[:, None], 0.0, -1e9),
        jnp.float32)[:, None, None, :]


class TestXlaAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_blockwise_f32(self, rng, causal):
        q, k, v = rand_qkv(rng)
        a = att.xla_attention(q, k, v, causal=causal)
        b = att.blockwise_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    # (Lq, Lk, causal, bias, chunks): what `_q_chunks` makes of a call
    @pytest.mark.parametrize("Lq,Lk,causal,bias,chunks", [
        (512, 512, False, None, 4),          # BERT's square, four chunks
        (512, 512, False, "padding", 4),     # [b, 1, 1, Lk], -1e9 columns
        (512, 512, False, "square", 4),      # [1, 1, Lq, Lk], sliced by rows
        (512, 512, True, "padding", 4),      # causal and biased
        (256, 256, True, "rank2", 2),        # [Lq, Lk]: any rank broadcasts
        (16, 16, False, "padding", 1),       # no exact chunking: one chunk
        (256, 128, False, "square", 1),      # cross-attention: one chunk
        (128, 256, True, None, 1),           # causal, top-left aligned
    ])
    @pytest.mark.parametrize("layout", ["bhld", "blhd"])
    def test_chunk_body_matches_blockwise_fwd_and_grads(
            self, rng, Lq, Lk, causal, bias, chunks, layout):
        """Every call takes the one chunk body: forward and the gradients
        for q, k and v against the blockwise recurrence, in f32."""
        b, h, d = 2, 2, 16
        assert len(att._q_chunks(Lq, Lk, causal)) == chunks
        q = jnp.asarray(rng.randn(b, h, Lq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, Lk, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, Lk, d), jnp.float32)
        cot = jnp.asarray(rng.randn(b, h, Lq, d), jnp.float32)
        bias = {None: None, "padding": padding_bias(rng, b, Lk),
                "square": jnp.asarray(rng.randn(1, 1, Lq, Lk), jnp.float32),
                "rank2": jnp.asarray(rng.randn(Lq, Lk), jnp.float32)}[bias]
        tr = ((lambda t: t.transpose(0, 2, 1, 3)) if layout == "blhd"
              else (lambda t: t))

        def new(q_, k_, v_):
            return tr(att.xla_attention(tr(q_), tr(k_), tr(v_), causal=causal,
                                        bias=bias, layout=layout))

        def ref(q_, k_, v_):
            return att.blockwise_attention(q_, k_, v_, causal=causal,
                                           bias=bias)

        out_n, vjp_n = jax.vjp(new, q, k, v)
        out_r, vjp_r = jax.vjp(ref, q, k, v)
        np.testing.assert_allclose(np.asarray(out_n), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)
        for gn, gr, name in zip(vjp_n(cot), vjp_r(cot), "qkv"):
            np.testing.assert_allclose(np.asarray(gn), np.asarray(gr),
                                       rtol=5e-4, atol=5e-4,
                                       err_msg=f"d{name} mismatch")

    def test_key_bias_gradient_stays_noise_in_bf16(self, rng):
        """Softmax ignores a constant added to a row of scores, so the
        gradient for a key bias (dk summed over the keys) is zero in exact
        arithmetic and only rounding noise in bf16. Where the values are
        alike along the keys, as in BERT's deep layers at initialisation,
        dP is nearly constant along a row: rounded to bf16 before the
        row's mean is taken off, it leaves the rows of dS summing to
        something, and that noise read 157 times dk's own RMS here with
        the causal tier's bf16 row statistics, against 0.16 with the
        values centred (`_centred`). On the v5e the forms between the two
        read as badly as the former (PERF.md section 6 "PR 29"): BERT-large
        failed its `change_norm_gap` on `qkv_b` with each of them."""
        b, L, h, d = 4, 512, 4, 64
        mk = lambda s: jnp.asarray(rng.randn(b, L, h, d) * s, jnp.bfloat16)
        q, k, cot = mk(0.1), mk(0.1), mk(1.0)
        v = jnp.asarray(rng.randn(b, 1, h, d) + 0.05 * rng.randn(b, L, h, d),
                        jnp.bfloat16)
        bias = jnp.zeros((b, 1, 1, L), jnp.float32)
        dk = jax.grad(lambda k_: (
            att.xla_attention(q, k_, v, bias=bias, layout="blhd").astype(
                jnp.float32) * cot.astype(jnp.float32)).sum())(k)
        dk = np.asarray(dk, np.float32)
        rms = lambda t: float(np.sqrt((t ** 2).mean()))
        assert rms(dk.sum(axis=(0, 1))) < 0.5 * rms(dk)

    def test_padded_columns_get_no_weight(self, rng):
        """A -1e9 column carries exactly zero weight: keys and values
        under it reach the output only through the point the values are
        centred on (their mean over all keys), which moves the roundings
        and nothing else, in f32 and in bf16."""
        b, h, L, d = 2, 2, 512, 16
        bias = padding_bias(rng, b, L)
        pad = np.asarray(bias[:, 0, 0, :] < 0)
        for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 0.03)):
            q, k, v = rand_qkv(rng, b=b, h=h, L=L, d=d, dtype=dtype)
            noise = jnp.asarray(
                pad[:, None, :, None] * rng.randn(b, h, L, d) * 50, dtype)
            a = att.xla_attention(q, k, v, bias=bias)
            c = att.xla_attention(q, k + noise, v + noise, bias=bias)
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(c, np.float32),
                                       rtol=tol, atol=tol)

    def test_matches_naive_softmax(self, rng):
        q, k, v = rand_qkv(rng, L=16, d=8)
        s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k))
        s = s / np.sqrt(q.shape[-1])
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        exp = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))
        out = att.xla_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-5, atol=1e-5)

    def test_bf16_prob_roundtrip_close(self, rng):
        q, k, v = rand_qkv(rng, dtype=jnp.bfloat16)
        a = att.xla_attention(q, k, v, causal=True).astype(jnp.float32)
        b = att.blockwise_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True)
        # bf16 inputs + bf16 probs: agreement within bf16 tolerance
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.05, atol=0.05)

    def test_chunked_causal_path_exact(self, rng):
        # L=256 crosses the q-chunk threshold (2 chunks of 128): the chunked
        # causal path must be numerically identical to the single-block form
        q, k, v = rand_qkv(rng, L=256, d=16)
        a = att.xla_attention(q, k, v, causal=True)
        b = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
        c = att.blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-5, atol=2e-5)

    def test_bias(self, rng):
        q, k, v = rand_qkv(rng, L=16, d=8)
        bias = jnp.asarray(rng.randn(1, 1, 16, 16), jnp.float32)
        a = att.xla_attention(q, k, v, bias=bias)
        b = att.blockwise_attention(q, k, v, bias=bias)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    def test_grad_flows(self, rng):
        q, k, v = rand_qkv(rng, L=16, d=8)
        g = jax.grad(lambda q: att.xla_attention(q, k, v, causal=True).sum())(q)
        assert np.isfinite(np.asarray(g)).all()

    @pytest.mark.parametrize("blhd", [False, True])
    def test_chunked_manual_vjp_matches_autodiff(self, rng, blhd):
        """The hand-written _causal_chunked backward must agree with
        autodiff of the plain masked-softmax form for dq/dk/dv."""
        b, h, L, d = 2, 3, 256, 16  # L=256 -> 2 chunks of 128
        q = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
        if blhd:
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        assert att._q_chunk_size(L) is not None

        def ref(q_, k_, v_):
            return naive_attention(q_, k_, v_, causal=True, blhd=blhd)

        cot = jnp.asarray(rng.randn(*q.shape), jnp.float32)
        out_m, vjp_m = jax.vjp(lambda *a: att._causal_chunked(*a, blhd), q, k, v)
        out_r, vjp_r = jax.vjp(ref, q, k, v)
        np.testing.assert_allclose(np.asarray(out_m), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)
        for gm, gr, name in zip(vjp_m(cot), vjp_r(cot), "qkv"):
            np.testing.assert_allclose(np.asarray(gm), np.asarray(gr),
                                       rtol=5e-4, atol=5e-4,
                                       err_msg=f"d{name} mismatch")

    @pytest.mark.parametrize("call", ["causal_manual_vjp", "causal",
                                      "full", "full_padding_bias"])
    def test_chunked_bf16_grads_finite_and_close(self, rng, call):
        """bf16 through the chunk body (stored scores and weights rounded
        to bf16) against the plain softmax in f32, for the hand-written
        causal backward and for autodiff of every kind of call."""
        b, h, L, d = 2, 2, 256, 16
        mk = lambda: jnp.asarray(rng.randn(b, L, h, d), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        causal = call.startswith("causal")
        bias = padding_bias(rng, b, L) if call.endswith("bias") else None

        def loss(q_, k_, v_):
            if call == "causal_manual_vjp":
                o = att._causal_chunked(q_, k_, v_, True)
            else:
                o = att.xla_attention(q_, k_, v_, causal=causal, bias=bias,
                                      layout="blhd")
            return o.astype(jnp.float32).sum()

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        f32 = lambda t: t.astype(jnp.float32)
        rq, rk, rv = jax.grad(
            lambda a, b_, c: naive_attention(
                a, b_, c, causal=causal, bias=bias, blhd=True).sum(),
            argnums=(0, 1, 2))(f32(q), f32(k), f32(v))
        for g, r in zip((gq, gk, gv), (rq, rk, rv)):
            assert np.isfinite(np.asarray(f32(g))).all()
            np.testing.assert_allclose(np.asarray(f32(g)), np.asarray(r),
                                       rtol=0.1, atol=0.1)


class TestDispatch:
    def test_set_attention_impl_validates(self):
        with pytest.raises(ValueError):
            att.set_attention_impl("nope")

    def test_explicit_xla_impl(self, rng):
        att.set_attention_impl("xla")
        try:
            q, k, v = rand_qkv(rng)
            out = att.dot_product_attention(q, k, v, causal=True)
            ref = att.blockwise_attention(q, k, v, causal=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
        finally:
            att.set_attention_impl("auto")

    def test_blockwise_impl(self, rng):
        att.set_attention_impl("blockwise")
        try:
            q, k, v = rand_qkv(rng)
            out = att.dot_product_attention(q, k, v, causal=True)
            assert out.shape == q.shape
        finally:
            att.set_attention_impl("auto")


def test_auto_long_sequence_resolves_to_flash_kernel(monkeypatch):
    """Causal unbiased dispatch keeps the q-chunked XLA tier up to
    _XLA_MAX_SEQ_CAUSAL=8192 (r5: measured 46.5k vs 27.5k tok/s at the
    longctx shape) and picks the Pallas flash kernel past it; biased/
    non-causal calls keep the stricter 4096 guard (their full [L, L]
    scores have no masked blocks to skip) and stream via blockwise."""
    monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
    assert att._resolve_impl(8192, None, True, causal=True) == "xla"
    assert att._resolve_impl(16384, None, True, causal=True) == "flash_tpu"
    assert att._resolve_impl(8192, object(), True, causal=True) == "blockwise"
    assert att._resolve_impl(8192, None, True, causal=False) == "blockwise"
    assert att._resolve_impl(4096, None, True, causal=False) == "xla"
    assert att._resolve_impl(1024, None, True, causal=True) == "xla"


def test_auto_long_nonfitting_falls_back_to_blockwise(monkeypatch):
    """Shapes the kernel can't take (L % 256, Lq != Lk, operands past its
    VMEM budget) must stream via blockwise, not materialize O(L^2) through
    the kernel's internal fallback."""
    import jax.numpy as jnp

    monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 9000, 4, 64), jnp.float32)   # 9000 % 256 != 0
    assert not att._flash_tpu_fits(q, q, blhd=True)
    k = jnp.zeros((1, 4096, 4, 64), jnp.float32)   # cross-attention
    q2 = jnp.zeros((1, 8192, 4, 64), jnp.float32)
    assert not att._flash_tpu_fits(q2, k, blhd=True)
    assert att._flash_tpu_fits(q2, q2, blhd=True)
    # the kernels hold two whole [L, H*d] operands in VMEM: the longctx
    # bench shape compiled on the v5e in bf16 (24 MiB) and was refused in
    # f32 (48 MiB), so the gate must not offer the latter
    wide = jnp.zeros((1, 8192, 12, 64), jnp.bfloat16)
    assert att._flash_tpu_fits(wide, wide, blhd=True)
    assert not att._flash_tpu_fits(wide.astype(jnp.float32),
                                   wide.astype(jnp.float32), blhd=True)


def causal_program_ops():
    """Opcode histogram of the optimised program of the call GPT's step
    makes: causal, unbiased, [b, l, h, d], bf16, L = 1024, forward and the
    gradients for q, k and v, on the XLA tier."""
    x = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        o = att.dot_product_attention(q, k, v, causal=True, layout="blhd")
        return o.astype(jnp.float32).sum()

    att.set_attention_impl("xla")
    try:
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile().as_text()
    finally:
        att.set_attention_impl("auto")
    ops = hlo_attrib.parse_hlo_text(text)
    return dict(collections.Counter(op.opcode for op in ops.values()))


def test_causal_unbiased_program_is_the_parents():
    """The biased and non-causal calls moved onto the causal tier's chunk
    body; the causal unbiased call must compile to what it compiled to
    before (the fixture was taken with this function on the parent)."""
    with open(CAUSAL_OPS_FIXTURE) as f:
        assert causal_program_ops() == json.load(f)


@pytest.mark.parametrize("causal,L,chunks", [(True, 1024, 8),
                                             (False, 512, 4),
                                             (False, 16, 1)])
def test_xla_chunks_counter(causal, L, chunks):
    """`attn/xla_chunks.<c|f>` counts the chunks a traced call emits:
    8 for GPT's L = 1024 causal call, 4 for BERT's L = 512 square."""
    tel = get_telemetry()
    name = "attn/xla_chunks." + ("c" if causal else "f")
    before = tel.counter_value(name)
    x = jax.ShapeDtypeStruct((1, L, 2, 8), jnp.bfloat16)
    jax.eval_shape(lambda q: att.xla_attention(q, q, q, causal=causal,
                                               layout="blhd"), x)
    assert tel.counter_value(name) - before == chunks
