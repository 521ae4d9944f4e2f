"""paddle.distribution — probability distributions.

Parity with the reference's python/paddle/distribution.py:41 (Distribution /
Uniform / Normal / Categorical: sample, entropy, log_prob, probs,
kl_divergence). TPU-native: sampling draws keys from the global RNG chain
(core/rng.py) and lowers to jax.random — stateless keys under the stateful
paddle facade, so sampling is reproducible under ``paddle.seed`` and usable
inside jitted code via the same ops.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .core import rng as rng_mod
from .core.tensor import Tensor, apply_op, to_tensor, wrap_raw

__all__ = ["Distribution", "Uniform", "Normal", "Categorical",
           "kl_divergence"]


def _raw(x):
    if isinstance(x, Tensor):
        return x._value
    if isinstance(x, jax.core.Tracer) or isinstance(x, jnp.ndarray):
        return x  # already a jax value (possibly traced): no host round-trip
    return jnp.asarray(np.asarray(x, np.float32))


class Distribution:
    """Abstract base (reference distribution.py:41)."""

    def sample(self, shape=(), seed=0):
        raise NotImplementedError

    def entropy(self):
        raise NotImplementedError

    def kl_divergence(self, other):
        raise NotImplementedError

    def log_prob(self, value):
        raise NotImplementedError

    def probs(self, value):
        raise NotImplementedError

    @staticmethod
    def _key(seed):
        if seed:
            return jax.random.key(int(seed))
        return rng_mod.next_key()


class Uniform(Distribution):
    """U(low, high); endpoints broadcast."""

    def __init__(self, low, high, name=None):
        self.low = _raw(low)
        self.high = _raw(high)

    def sample(self, shape=(), seed=0):
        shape = tuple(shape)
        key = self._key(seed)
        b = jnp.broadcast_shapes(self.low.shape, self.high.shape)
        u = jax.random.uniform(key, shape + b, jnp.float32)
        return wrap_raw(self.low + u * (self.high - self.low))

    def log_prob(self, value):
        v = _raw(value)
        inside = (v >= self.low) & (v < self.high)
        lp = -jnp.log(self.high - self.low)
        return wrap_raw(jnp.where(inside, lp, -jnp.inf))

    def probs(self, value):
        v = _raw(value)
        inside = (v >= self.low) & (v < self.high)
        return wrap_raw(jnp.where(inside, 1.0 / (self.high - self.low), 0.0))

    def entropy(self):
        return wrap_raw(jnp.log(self.high - self.low)
                        + jnp.zeros(jnp.broadcast_shapes(
                            self.low.shape, self.high.shape)))


class Normal(Distribution):
    """N(loc, scale); parameters broadcast."""

    def __init__(self, loc, scale, name=None):
        self.loc = _raw(loc)
        self.scale = _raw(scale)

    def sample(self, shape=(), seed=0):
        shape = tuple(shape)
        key = self._key(seed)
        b = jnp.broadcast_shapes(self.loc.shape, self.scale.shape)
        z = jax.random.normal(key, shape + b, jnp.float32)
        return wrap_raw(self.loc + z * self.scale)

    def entropy(self):
        b = jnp.broadcast_shapes(self.loc.shape, self.scale.shape)
        return wrap_raw(0.5 + 0.5 * math.log(2 * math.pi)
                        + jnp.log(jnp.broadcast_to(self.scale, b)))

    def log_prob(self, value):
        v = _raw(value)
        var = self.scale * self.scale
        return wrap_raw(-((v - self.loc) ** 2) / (2 * var)
                        - jnp.log(self.scale) - 0.5 * math.log(2 * math.pi))

    def probs(self, value):
        return wrap_raw(jnp.exp(self.log_prob(value)._value))

    def kl_divergence(self, other):
        """KL(self ‖ other), closed form (reference distribution.py:595):
        log(σ2/σ1) + (σ1² + (μ1-μ2)²)/(2σ2²) − 1/2."""
        if not isinstance(other, Normal):
            raise TypeError("kl_divergence target must be Normal")
        var1 = self.scale ** 2
        var2 = other.scale ** 2
        return wrap_raw(jnp.log(other.scale / self.scale)
                        + (var1 + (self.loc - other.loc) ** 2) / (2 * var2)
                        - 0.5)


def _categorical_validate_nonneg(orig) -> bool:
    """True when ``orig`` (the user's ORIGINAL input, pre-conversion) is
    checkable WITHOUT a device sync and holds a negative entry. Host
    values (numpy/list/scalars) check for free; device-resident
    Tensors/arrays are only checked under
    PADDLE_TPU_VALIDATE_DISTRIBUTIONS=1 (each check is a blocking D2H
    roundtrip per eager construction otherwise); traced values never."""
    import os

    val = orig._value if isinstance(orig, Tensor) else orig
    if isinstance(val, jax.core.Tracer):
        return False
    on_host = isinstance(val, (np.ndarray, np.generic, list, tuple, float,
                               int))
    if not on_host and os.environ.get(
            "PADDLE_TPU_VALIDATE_DISTRIBUTIONS", "0") != "1":
        return False
    return bool(np.any(np.asarray(val) < 0))


class Categorical(Distribution):
    """Categorical over unnormalized ``logits`` (the reference accepts
    unnormalized probabilities; log-space here is the numerically stable
    equivalent — pass probabilities and they are log'd)."""

    def __init__(self, logits, name=None):
        raw = _raw(logits)
        # reference semantics: `logits` holds unnormalized NON-NEGATIVE
        # probabilities for probs()/sample() (distribution.py Categorical),
        # while entropy()/kl_divergence() run softmax over the same values
        # as if they were log-space logits (distribution.py:812-860) —
        # both faithfully mirrored, including the asymmetry.
        # validation policy (r5): NEVER force a device sync at construction.
        # - traced values (jit/grad/vmap) cannot be bool()'d at all;
        # - host values (numpy/list) are checked for free;
        # - device arrays would pay a blocking D2H roundtrip per eager
        #   construction just to validate — skipped unless FLAGS/env
        #   debug opt-in
        #   (PADDLE_TPU_VALIDATE_DISTRIBUTIONS=1). The reference does no
        #   validation at all; entropy()/kl run softmax so log-space
        #   logits are legitimate inputs for those methods.
        if _categorical_validate_nonneg(logits):
            raise ValueError(
                "Categorical expects non-negative unnormalized "
                "probabilities (negative entries would produce negative "
                "'probabilities' in probs()/sample())")
        self._raw = raw
        self._probs = raw / jnp.sum(raw, axis=-1, keepdims=True)
        self._log_probs = jnp.log(jnp.maximum(self._probs, 1e-38))

    def sample(self, shape=(), seed=0):
        shape = tuple(shape)
        key = self._key(seed)
        out = jax.random.categorical(key, self._log_probs,
                                     shape=shape + self._log_probs.shape[:-1])
        return wrap_raw(out.astype(jnp.int64))

    def entropy(self):
        # softmax-over-raw semantics, like the reference's entropy()
        logp = jax.nn.log_softmax(self._raw, axis=-1)
        return wrap_raw(-jnp.sum(jnp.exp(logp) * logp, axis=-1))

    def kl_divergence(self, other):
        if not isinstance(other, Categorical):
            raise TypeError("kl_divergence target must be Categorical")
        logp = jax.nn.log_softmax(self._raw, axis=-1)
        logq = jax.nn.log_softmax(other._raw, axis=-1)
        return wrap_raw(jnp.sum(jnp.exp(logp) * (logp - logq), axis=-1))

    def probs(self, value):
        v = _raw(value).astype(jnp.int32)
        p = self._probs
        if p.ndim == 1:
            return wrap_raw(p[v])
        vb = jnp.broadcast_to(v, p.shape[:-1])
        return wrap_raw(jnp.take_along_axis(p, vb[..., None], axis=-1)[..., 0])

    def log_prob(self, value):
        return wrap_raw(jnp.log(jnp.maximum(self.probs(value)._value,
                                            1e-38)))


def kl_divergence(p: Distribution, q: Distribution):
    """Functional form (paddle.distribution.kl_divergence)."""
    return p.kl_divergence(q)
