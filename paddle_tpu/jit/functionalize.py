"""Functionalize imperative Layers for XLA compilation.

This is the TPU-native replacement for the reference's dygraph→static AST
transpiler (fluid/dygraph/dygraph_to_static/program_translator.py + 24 AST
transformers): instead of rewriting Python source into ProgramDesc, we trace
the Layer's forward with JAX tracers threaded through the same eager ops.
Parameters/buffers are lifted into pytrees, so the result is a pure function
``apply(params, buffers, *args)`` that jax.jit/pjit compiles — no per-op
dispatch at runtime, full XLA fusion.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Parameter, Tensor, no_grad
from ..nn.layer_base import Layer

__all__ = ["functionalize", "get_params", "get_buffers", "set_params",
           "cast_floats", "checkpointed_call", "TracedLayer"]


def cast_floats(tree, dtype):
    """Cast the FLOAT leaves of a pytree to ``dtype`` (everything else
    passes through untouched). The serving-precision primitive shared by
    ``jit.save(precision=...)`` (bake cast weights into the artifact)
    and ``inference.Predictor`` (cast a live layer at load, cast inputs
    in / outputs back out) — one definition so the two paths cannot
    silently diverge on what "cast the floats" means."""
    dtype = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype)
        if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating) else p, tree)


def get_params(layer: Layer) -> Dict[str, Any]:
    """Named parameter pytree (raw jax arrays)."""
    return {name: p._value for name, p in layer.named_parameters()}


def get_buffers(layer: Layer) -> Dict[str, Any]:
    return {name: b._value for name, b in layer.named_buffers()}


def set_params(layer: Layer, params: Dict[str, Any]):
    named = dict(layer.named_parameters())
    for name, v in params.items():
        named[name]._value = v


def set_buffers(layer: Layer, buffers: Dict[str, Any]):
    named = dict(layer.named_buffers())
    for name, v in buffers.items():
        named[name]._value = v


@contextlib.contextmanager
def _swapped_state(layer: Layer, params, buffers):
    named_p = dict(layer.named_parameters())
    named_b = dict(layer.named_buffers())
    saved_p = {n: p._value for n, p in named_p.items()}
    saved_b = {n: b._value for n, b in named_b.items()}
    try:
        for n, v in params.items():
            if n in named_p:
                named_p[n]._value = v
        for n, v in (buffers or {}).items():
            if n in named_b:
                named_b[n]._value = v
        yield named_b
    finally:
        for n, v in saved_p.items():
            named_p[n]._value = v
        for n, v in saved_b.items():
            named_b[n]._value = v


def functionalize(layer: Layer, with_buffers: bool = True, training: bool | None = None):
    """Return ``apply(params, buffers, *raw_args) -> (raw_out, new_buffers)``.

    The returned function is pure: it swaps the pytree leaves into the layer,
    runs forward under no-grad (JAX handles differentiation outside), and
    restores. Buffer mutations (e.g. BN running stats) are captured and
    returned functionally so the caller can carry them through a jitted loop.
    """

    def apply(params, buffers, *raw_args, **raw_kwargs):
        with _swapped_state(layer, params, buffers or {}) as named_b:
            prev_training = layer.training
            if training is not None:
                layer.training = training
                for l in layer.sublayers():
                    l.training = training
            try:
                with no_grad():
                    args = [
                        Tensor(a) if not isinstance(a, Tensor) and hasattr(a, "dtype") else a
                        for a in raw_args
                    ]
                    kwargs = {
                        k: Tensor(v) if not isinstance(v, Tensor) and hasattr(v, "dtype") else v
                        for k, v in raw_kwargs.items()
                    }
                    out = layer(*args, **kwargs)
                new_buffers = {n: b._value for n, b in named_b.items()}
            finally:
                layer.training = prev_training
                for l in layer.sublayers():
                    l.training = prev_training
        return _unwrap_tree(out), new_buffers

    return apply


def checkpointed_call(fn, layers, x):
    """``fn(x)``, and under ``remat='layer'`` (``ops.remat_policy``) the
    same under ``jax.checkpoint``: ``fn`` (a bound method, a sub-layer)
    as a pure function of ``x`` and of the parameters and buffers of
    ``layers``, which are all it reads; its backward makes its activations
    again. What a model calls on each part of a block. Buffers that ``fn``
    writes (a counter a layer carries through the step) are written back.
    """
    from ..core.tensor import apply_op
    from ..ops import remat_policy

    if not remat_policy.layers_checkpointed():
        return fn(x)
    params = [p for layer in layers for _, p in layer.named_parameters()]
    buffers = [b for layer in layers for _, b in layer.named_buffers()]

    @jax.checkpoint
    def pure(x_raw, buffer_values, *param_values):
        saved = [t._value for t in params + buffers]
        try:
            for t, v in zip(params + buffers,
                            tuple(param_values) + tuple(buffer_values)):
                t._value = v
            with no_grad():
                out = fn(Tensor(x_raw))
            return (out._value,) + tuple(b._value for b in buffers)
        finally:
            for t, v in zip(params + buffers, saved):
                t._value = v

    out, *new_values = apply_op(
        pure, x, tuple(b._value for b in buffers), *params,
        multi_out=True, op_name="checkpointed")
    for b, new in zip(buffers, new_values):
        b._value = new._value
    return out


def _unwrap_tree(out):
    if isinstance(out, Tensor):
        return out._value
    if isinstance(out, (list, tuple)):
        return type(out)(_unwrap_tree(o) for o in out)
    if isinstance(out, dict):
        return {k: _unwrap_tree(v) for k, v in out.items()}
    return out


def _wrap_tree(out):
    if isinstance(out, (list, tuple)):
        return type(out)(_wrap_tree(o) for o in out)
    if isinstance(out, dict):
        return {k: _wrap_tree(v) for k, v in out.items()}
    if hasattr(out, "dtype") and hasattr(out, "shape"):
        return Tensor(out)
    return out


class TracedLayer:
    """jit-compiled inference wrapper over a Layer (parity with
    fluid/dygraph/jit.py TracedLayer)."""

    def __init__(self, layer: Layer, training=False, donate_buffers=False):
        self._layer = layer
        self._apply = functionalize(layer, training=training)
        self._jitted = jax.jit(self._apply)

    def __call__(self, *args):
        params = get_params(self._layer)
        buffers = get_buffers(self._layer)
        raw_args = [a._value if isinstance(a, Tensor) else a for a in args]
        out, new_buffers = self._jitted(params, buffers, *raw_args)
        set_buffers(self._layer, new_buffers)
        return _wrap_tree(out)
