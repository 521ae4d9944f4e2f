"""Per-op micro-benchmark harness.

Counterpart of the reference's op benchmark CI
(tools/test_op_benchmark.sh, paddle/fluid/operators/benchmark/op_tester.cc):
a config-driven timing tool whose JSON results feed the relative regression
gate in ``check_op_benchmark_result.py`` (the reference publishes no
absolute numbers — perf is guarded PR-vs-baseline).

Usage:
  python tools/op_benchmark.py                       # all cases -> stdout
  python tools/op_benchmark.py --out results.json    # save for the gate
  python tools/op_benchmark.py --filter matmul       # subset
  python tools/op_benchmark.py --backend cpu         # force backend

Timing protocol: per case, one warmup call (compile), then the median of
3 windows of `repeat` calls; each window ends on a host read of a scalar
reduction of the result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cases():
    """(name, builder) pairs. Builders return (fn, args) with fn jittable."""
    import jax
    import jax.numpy as jnp

    r = np.random.RandomState(0)
    f32 = lambda *s: jnp.asarray(r.randn(*s).astype(np.float32))
    bf16 = lambda *s: f32(*s).astype(jnp.bfloat16)
    i32 = lambda hi, *s: jnp.asarray(r.randint(0, hi, s).astype(np.int32))

    def case_matmul():
        return (lambda a, b: a @ b), (bf16(4096, 4096), bf16(4096, 4096))

    def case_conv2d():
        from paddle_tpu.nn import functional as F

        x, w = f32(8, 64, 56, 56), f32(128, 64, 3, 3)

        def f(a, b):
            out = F.conv2d(a, b, padding=1)
            return getattr(out, "_value", out)

        return f, (x, w)

    def case_attention():
        from paddle_tpu.ops.attention import xla_attention

        q = bf16(8, 1024, 16, 64)
        return (lambda q, k, v: xla_attention(q, k, v, causal=True,
                                              layout="blhd")), (q, q, q)

    def case_layer_norm():
        from paddle_tpu.ops.fused import _ln_reference

        return (lambda x, w, b: _ln_reference(x, w, b, 1e-5)), (
            bf16(8, 1024, 1024), bf16(1024), bf16(1024))

    def case_fused_layer_norm():
        from paddle_tpu.ops.fused import fused_layer_norm

        return (lambda x, w, b: fused_layer_norm(x, w, b, 1e-5)), (
            bf16(8, 1024, 1024), bf16(1024), bf16(1024))

    def case_softmax():
        return (lambda x: jax.nn.softmax(x, axis=-1)), (f32(8192, 4096),)

    def case_cross_entropy():
        from paddle_tpu.nn.functional.loss import cross_entropy
        from paddle_tpu.core.tensor import Tensor

        logits, lab = bf16(8192, 50304), i32(50304, 8192)
        return (lambda a, b: cross_entropy(Tensor(a), Tensor(b))._value), (
            logits, lab)

    def case_embedding_grad():
        ids = i32(50304, 8192)
        w = f32(50304, 1024)

        def f(w, ids):
            return jax.grad(lambda w_: jnp.take(w_, ids, axis=0).sum())(w)

        return f, (w, ids)

    def case_adam_update():
        p, g, m, v = (f32(354 * 10**5) for _ in range(4))

        def f(p, g, m, v):
            m2 = 0.9 * m + 0.1 * g
            v2 = 0.999 * v + 0.001 * g * g
            return p - 1e-3 * m2 / (jnp.sqrt(v2) + 1e-8), m2, v2

        return f, (p, g, m, v)

    def case_gelu():
        return (lambda x: jax.nn.gelu(x, approximate=True)), (
            bf16(8, 1024, 4096),)

    def case_reduce_sum():
        return (lambda x: x.sum(axis=-1)), (f32(8192, 4096),)

    def _longctx_grad_case(attn_fn):
        """fwd+bwd of one causal attention layer at the longctx bench
        shape (b=1, L=8192, h=12, d=64) — the single-chip tier comparison
        the longctx config's 47k-tok/s number rests on."""
        q = bf16(1, 8192, 12, 64)

        def f(q, k, v):
            y, vjp = jax.vjp(attn_fn, q, k, v)
            return vjp(y)[0]

        return f, (q, q, q)

    def case_longctx_attn_chunked():
        # through the PUBLIC xla_attention so the case measures whatever
        # backward the model dispatch actually runs (autodiff)
        from paddle_tpu.ops.attention import xla_attention

        return _longctx_grad_case(
            lambda q, k, v: xla_attention(q, k, v, causal=True,
                                          layout="blhd"))

    def case_longctx_attn_flash_tpu():
        from paddle_tpu.ops.flash_tpu import flash_attention_blhd

        return _longctx_grad_case(
            lambda q, k, v: flash_attention_blhd(q, k, v, causal=True))

    def case_longctx_attn_blockwise():
        from paddle_tpu.ops.attention import blockwise_attention

        def attn(q, k, v):
            # blockwise layout is [b, h, L, d]
            qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            return blockwise_attention(qt, kt, vt, causal=True
                                       ).transpose(0, 2, 1, 3)

        return _longctx_grad_case(attn)

    def case_multiclass_nms():
        from paddle_tpu.vision.ops import multiclass_nms
        from paddle_tpu.core.tensor import Tensor

        boxes = f32(4, 512, 4)
        scores = jnp.abs(f32(4, 8, 512))

        def f(b, s):
            out, cnt = multiclass_nms(Tensor(b), Tensor(s), 0.1, 128, 64,
                                      0.5)
            return out._value

        return f, (boxes, scores)

    return {
        "matmul_4096_bf16": case_matmul,
        "conv2d_r50_block": case_conv2d,
        "attention_causal_gpt2m": case_attention,
        "layer_norm_xla": case_layer_norm,
        "layer_norm_pallas": case_fused_layer_norm,
        "softmax_8192x4096": case_softmax,
        "cross_entropy_lm_head": case_cross_entropy,
        "embedding_grad_scatter": case_embedding_grad,
        "adam_update_35m": case_adam_update,
        "gelu_mlp": case_gelu,
        "reduce_sum": case_reduce_sum,
        "multiclass_nms": case_multiclass_nms,
        "longctx_attn_L8192_chunked": case_longctx_attn_chunked,
        "longctx_attn_L8192_flash_tpu": case_longctx_attn_flash_tpu,
        "longctx_attn_L8192_blockwise": case_longctx_attn_blockwise,
    }


def _block(out):
    """Block on completion by materializing a SCALAR reduction of the first
    output leaf — a full np.asarray would ship the whole tensor (tens of
    MB) to the host inside the timed window."""
    import jax
    import jax.numpy as jnp

    leaf = jax.tree_util.tree_leaves(out)[0]
    s = jnp.sum(leaf) if getattr(leaf, "ndim", 0) else leaf
    np.asarray(s)


def run_case(name, builder, repeat, chain=8):
    """One dispatch runs the op ``chain`` times with a data dependency
    between iterations (a vanishing perturbation of the first float input),
    amortizing the per-call dispatch latency, which is above most single
    ops."""
    import jax
    import jax.numpy as jnp

    fn, args = builder()
    fidx = next((i for i, a in enumerate(args)
                 if jnp.issubdtype(a.dtype, jnp.floating)), None)

    def chained(*xs):
        xs = list(xs)
        out = fn(*xs)
        if fidx is None:
            return out
        for _ in range(chain - 1):
            s = jnp.sum(jax.tree_util.tree_leaves(out)[0]).astype(
                xs[fidx].dtype)
            xs[fidx] = xs[fidx] + s * jnp.asarray(1e-30, xs[fidx].dtype)
            out = fn(*xs)
        return out

    eff_chain = chain if fidx is not None else 1
    jitted = jax.jit(chained)
    out = jitted(*args)  # compile + warmup
    _block(out)
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(repeat):
            out = jitted(*args)
        _block(out)
        windows.append((time.perf_counter() - t0) / (repeat * eff_chain))
    return sorted(windows)[1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--filter", default=None)
    ap.add_argument("--repeat", type=int, default=20)
    ap.add_argument("--backend", default=None,
                    help="force a jax platform (e.g. cpu)")
    args = ap.parse_args()
    if args.backend:
        import os

        os.environ["JAX_PLATFORMS"] = args.backend
        import jax

        jax.config.update("jax_platforms", args.backend)
    import jax

    import paddle_tpu  # noqa: F401  (x64 policy, op registration)

    results = {"backend": jax.default_backend(), "cases": {}}
    for name, builder in _cases().items():
        if args.filter and args.filter not in name:
            continue
        try:
            ms = run_case(name, builder, args.repeat) * 1e3
            results["cases"][name] = {"ms": round(ms, 4)}
            print(f"{name:28s} {ms:9.4f} ms", flush=True)
        except Exception as e:  # record failures, keep benching
            results["cases"][name] = {"error": repr(e)[:200]}
            print(f"{name:28s} ERROR {repr(e)[:120]}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
