"""Attention implementation tiers agree numerically (blockwise is the
reference recurrence; xla_attention is the materialized TPU fast path;
flash falls back to blockwise off-TPU) and the dispatch honors
set_attention_impl."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention as att


def rand_qkv(rng, b=2, h=4, L=64, d=32, dtype=jnp.float32):
    mk = lambda: jnp.asarray(rng.randn(b, h, L, d), dtype)
    return mk(), mk(), mk()


class TestXlaAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_blockwise_f32(self, rng, causal):
        q, k, v = rand_qkv(rng)
        a = att.xla_attention(q, k, v, causal=causal)
        b = att.blockwise_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

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
        b = att._attention_core(
            q, k, v, jnp.tril(jnp.ones((256, 256), bool)))
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
        assert att._causal_chunk_size(L) is not None

        def ref(q_, k_, v_):
            mask = jnp.tril(jnp.ones((L, L), bool))
            return att._attention_core(q_, k_, v_, mask, blhd=blhd)

        cot = jnp.asarray(rng.randn(*q.shape), jnp.float32)
        out_m, vjp_m = jax.vjp(lambda *a: att._causal_chunked(*a, blhd), q, k, v)
        out_r, vjp_r = jax.vjp(ref, q, k, v)
        np.testing.assert_allclose(np.asarray(out_m), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)
        for gm, gr, name in zip(vjp_m(cot), vjp_r(cot), "qkv"):
            np.testing.assert_allclose(np.asarray(gm), np.asarray(gr),
                                       rtol=5e-4, atol=5e-4,
                                       err_msg=f"d{name} mismatch")

    def test_chunked_manual_vjp_bf16_grads_finite_and_close(self, rng):
        b, h, L, d = 2, 2, 256, 16
        mk = lambda: jnp.asarray(rng.randn(b, L, h, d), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()

        def loss(q_, k_, v_):
            return att._causal_chunked(q_, k_, v_, True).astype(
                jnp.float32).sum()

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        f32 = lambda t: t.astype(jnp.float32)
        rq, rk, rv = jax.grad(
            lambda a, b_, c: att._attention_core(
                a, b_, c, jnp.tril(jnp.ones((L, L), bool)), blhd=True
            ).sum(), argnums=(0, 1, 2))(f32(q), f32(k), f32(v))
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
