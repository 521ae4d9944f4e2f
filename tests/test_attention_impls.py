"""Attention implementation tiers agree numerically (blockwise is the
reference recurrence; xla_attention is the materialized TPU fast path),
the dispatch takes the tier its rule names for the call and nothing else,
and it honors set_attention_impl."""
import collections
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.ops import attention as att
from paddle_tpu.ops import tier_policy
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


def _call(L, causal=True, bias=None, heads=2, d=64, dv=None, layout="blhd",
          dtype=jnp.bfloat16, **kw):
    """One row of `TIER_RULE`: the operands' shapes and the keywords of a
    ``dot_product_attention`` call. ``bias`` is a shape."""
    shape = lambda w: ((1, L, heads, w) if layout == "blhd"
                       else (1, heads, L, w))
    x = jax.ShapeDtypeStruct(shape(d), dtype)
    v = jax.ShapeDtypeStruct(shape(dv or d), dtype)
    if bias is not None:
        bias = jax.ShapeDtypeStruct(bias, jnp.float32)
    return (x, x, v, bias), dict(causal=causal, layout=layout, **kw)


# (backend, set_attention_impl, ring mesh registered, call, tier): the
# table of `ops.attention._tier`, top to bottom
TIER_RULE = {
    "sp_axis_given": ("tpu", "auto", False, _call(256, sp_axis="sp"), "ring"),
    "ring_mesh_registered": ("tpu", "auto", True, _call(8192), "ring"),
    "ring_mesh_short_call": ("tpu", "auto", True, _call(1024), "xla"),
    "named_tier_outranks_ring": ("tpu", "xla", True, _call(8192), "xla"),
    "kimi_latent_L8192": ("tpu", "auto", False,
                          _call(8192, heads=4, d=192, dv=128), "xla"),
    "latent_use_flash_false": ("cpu", "auto", False,
                               _call(256, d=24, dv=16, use_flash=False),
                               "xla"),
    "named_blockwise": ("tpu", "blockwise", False, _call(1024), "blockwise"),
    "named_flash_tpu_non_causal": ("tpu", "flash_tpu", False,
                                   _call(512, causal=False), "xla"),
    "named_flash_tpu_off_tpu": ("cpu", "flash_tpu", False, _call(512), "xla"),
    "use_flash_false": ("tpu", "auto", False,
                        _call(1024, use_flash=False), "blockwise"),
    "gpt2_causal_L1024": ("tpu", "auto", False,
                          _call(1024, heads=16), "xla"),
    "causal_L8192": ("tpu", "auto", False, _call(8192), "xla"),
    "bert_biased_L512": ("tpu", "auto", False,
                         _call(512, causal=False, heads=16,
                               bias=(1, 1, 1, 512)), "xla"),
    "non_causal_L4096": ("tpu", "auto", False,
                         _call(4096, causal=False), "xla"),
    "causal_L16384": ("tpu", "auto", False, _call(16384), "flash_tpu"),
    "causal_L16384_bhld": ("tpu", "auto", False,
                           _call(16384, layout="bhld"), "flash_tpu"),
    "causal_biased_L8192": ("tpu", "auto", False,
                            _call(8192, bias=(1, 1, 1, 8192)), "blockwise"),
    "non_causal_L8192": ("tpu", "auto", False,
                         _call(8192, causal=False), "blockwise"),
    "causal_L9000_does_not_tile": ("tpu", "auto", False,
                                   _call(9000), "blockwise"),
    "off_tpu": ("cpu", "auto", False, _call(1024, heads=16), "blockwise"),
    "off_tpu_biased": ("cpu", "auto", False,
                       _call(512, causal=False, bias=(1, 1, 1, 512)),
                       "blockwise"),
}

# the key of GPT-2 345M's call in the verdict file of the race that was
GPT_KEY = "tpu:TPU_v5_lite:h16:L1024:d64:bfloat16:causal"


class TestDispatch:
    @pytest.fixture(autouse=True)
    def _auto_again(self):
        yield
        att.set_attention_impl("auto")
        att.set_ring_context(None, None)

    @pytest.mark.parametrize("impl", ["nope", "pallas"])
    def test_set_attention_impl_validates(self, impl):
        with pytest.raises(ValueError):
            att.set_attention_impl(impl)

    def test_explicit_xla_impl(self, rng):
        att.set_attention_impl("xla")
        q, k, v = rand_qkv(rng)
        out = att.dot_product_attention(q, k, v, causal=True)
        ref = att.blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_blockwise_impl(self, rng):
        att.set_attention_impl("blockwise")
        q, k, v = rand_qkv(rng)
        out = att.dot_product_attention(q, k, v, causal=True)
        assert out.shape == q.shape

    @staticmethod
    def _traced_tier(operands, kwargs):
        """Trace the call (nothing runs) and read back the tier it
        published in ``gauge/attn/tier.*``."""
        q, k, v, bias = operands
        blhd = kwargs["layout"] == "blhd"
        L, d = q.shape[1 if blhd else 2], q.shape[-1]
        call = lambda q_, k_, v_, b_: att.dot_product_attention(
            q_, k_, v_, bias=b_, **kwargs)
        if kwargs.get("sp_axis"):  # the axis has to be bound; L is local
            mesh = Mesh(np.array(jax.devices()[:4]), (kwargs["sp_axis"],))
            spec = (P(None, kwargs["sp_axis"]) if blhd
                    else P(None, None, kwargs["sp_axis"]))
            call = jax.shard_map(call, mesh=mesh, out_specs=spec,
                                 in_specs=(spec, spec, spec, None),
                                 check_vma=False)
            L //= 4
        gauge = f"attn/tier.{tier_policy.gauge_key(L, d, kwargs['causal'])}"
        tel = get_telemetry()
        tel.gauge(gauge, -1)
        jax.eval_shape(call, q, k, v, bias)
        return tel.scalars()["gauge/" + gauge]

    @pytest.mark.parametrize("row", list(TIER_RULE))
    def test_tier_is_a_rule_of_the_call(self, monkeypatch, row):
        """Every row of the table in `ops.attention._tier`: the tier is a
        function of the call, the backend, ``set_attention_impl`` and a
        registered ring mesh, and the call publishes its id."""
        backend, impl, ring, (operands, kwargs), tier = TIER_RULE[row]
        monkeypatch.setattr(att.jax, "default_backend", lambda: backend)
        att.set_attention_impl(impl)
        if ring:
            att.set_ring_context(Mesh(np.array(jax.devices()[:4]), ("sp",)),
                                 "sp")
        tel = get_telemetry()
        fallbacks = tel.counter_value("attn/tier_fallbacks")
        q, k, v, bias = operands
        assert att._tier(q, k, v, kwargs["causal"], bias,
                         kwargs.get("sp_axis"), kwargs.get("use_flash", True),
                         kwargs["layout"] == "blhd") == tier
        assert self._traced_tier(operands, kwargs) == \
            tier_policy.TIER_IDS[tier]
        # only the call that wanted the kernel and does not tile is counted
        assert (tel.counter_value("attn/tier_fallbacks") - fallbacks
                == (2 if row == "causal_L9000_does_not_tile" else 0))

    def test_a_stale_verdict_file_decides_nothing(self, monkeypatch,
                                                  tmp_path):
        """A machine may still hold the verdicts of the race that was
        (``attn_tiers.json`` beside its compile cache): GPT's call takes
        `xla` whatever they say, nothing is timed and the file is left as
        it was."""
        cache = tmp_path / "attn_tiers.json"
        cache.write_text(json.dumps({GPT_KEY: {
            "tier": "pallas", "candidates": ["xla", "pallas", "blockwise"],
            "timings_ms": {"xla": 1.71, "pallas": 1.7, "blockwise": 9.0},
            "ts": 0.0}}))
        stale = cache.read_bytes()
        monkeypatch.setenv("PADDLE_TPU_ATTN_TIER_CACHE", str(cache))
        monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            tier_policy, "_backend_key", lambda: "tpu:TPU_v5_lite")
        tier_policy.reset()
        tel = get_telemetry()
        benches = tel.counter_value("attn/tier_bench")
        operands, kwargs = _call(1024, heads=16)
        assert self._traced_tier(operands, kwargs) == \
            tier_policy.TIER_IDS["xla"]
        assert tel.counter_value("attn/tier_bench") == benches
        assert cache.read_bytes() == stale
        tier_policy.reset()


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
