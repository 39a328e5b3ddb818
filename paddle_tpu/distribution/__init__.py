"""paddle.distribution parity (python/paddle/distribution.py, 967 LoC:
Distribution/Normal/Uniform/Categorical; + the v2.3 additions Beta/Dirichlet/
Exponential-family helpers kept minimal).

Gradients flow to distribution parameters: log_prob/entropy route the
parameters through `core.dispatch.apply` as differentiable inputs (matching
the reference, where e.g. Normal.log_prob builds ops over the loc/scale
variables), so `Normal(net_out, s).log_prob(a).backward()` reaches net_out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import apply, unwrap
from ..core.random import next_key
from ..core.tensor import Tensor

__all__ = ["Distribution", "Normal", "Uniform", "Categorical", "Bernoulli",
           "Beta", "Multinomial", "kl_divergence", "MultivariateNormalDiag", "sampling_id"]


def _keep(x):
    """Preserve Tensor identity (for autograd); coerce python/numpy to jnp."""
    if isinstance(x, Tensor):
        return x
    return jnp.asarray(np.asarray(x, dtype=np.float32))


def _raw(x):
    return x._value if isinstance(x, Tensor) else x


class Distribution:
    def sample(self, shape=()):
        raise NotImplementedError

    def rsample(self, shape=()):
        raise NotImplementedError

    def log_prob(self, value):
        raise NotImplementedError

    def probs(self, value):
        from ..tensor.math import exp
        return exp(self.log_prob(value))

    def entropy(self):
        raise NotImplementedError

    def kl_divergence(self, other):
        return kl_divergence(self, other)


class Normal(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = _keep(loc)
        self.scale = _keep(scale)

    @property
    def mean(self):
        base = jnp.broadcast_shapes(jnp.shape(_raw(self.loc)),
                                    jnp.shape(_raw(self.scale)))

        def prim(loc):
            return jnp.broadcast_to(loc, base)
        return apply(prim, self.loc, name="normal_mean")

    @property
    def variance(self):
        base = jnp.broadcast_shapes(jnp.shape(_raw(self.loc)),
                                    jnp.shape(_raw(self.scale)))

        def prim(scale):
            return jnp.broadcast_to(scale ** 2, base)
        return apply(prim, self.scale, name="normal_variance")

    def sample(self, shape=(), seed=0):
        shape = tuple(shape)
        loc, scale = _raw(self.loc), _raw(self.scale)
        base = jnp.broadcast_shapes(jnp.shape(loc), jnp.shape(scale))
        z = jax.random.normal(next_key(), shape + base, dtype=jnp.float32)

        def prim(l, s):
            return l + s * z
        return apply(prim, self.loc, self.scale, name="normal_sample")

    rsample = sample

    def log_prob(self, value):
        def prim(v, loc, scale):
            var = scale ** 2
            return (-((v - loc) ** 2) / (2 * var)
                    - jnp.log(scale) - 0.5 * math.log(2 * math.pi))
        return apply(prim, value, self.loc, self.scale,
                     name="normal_log_prob")

    def entropy(self):
        base = jnp.broadcast_shapes(jnp.shape(_raw(self.loc)),
                                    jnp.shape(_raw(self.scale)))

        def prim(scale):
            return jnp.broadcast_to(
                0.5 + 0.5 * math.log(2 * math.pi) + jnp.log(scale), base)
        return apply(prim, self.scale, name="normal_entropy")


class Uniform(Distribution):
    def __init__(self, low, high, name=None):
        self.low = _keep(low)
        self.high = _keep(high)

    def sample(self, shape=(), seed=0):
        shape = tuple(shape)
        low, high = _raw(self.low), _raw(self.high)
        base = jnp.broadcast_shapes(jnp.shape(low), jnp.shape(high))
        u = jax.random.uniform(next_key(), shape + base, dtype=jnp.float32)

        def prim(lo, hi):
            return lo + (hi - lo) * u
        return apply(prim, self.low, self.high, name="uniform_sample")

    rsample = sample

    def log_prob(self, value):
        def prim(v, lo, hi):
            inside = (v >= lo) & (v < hi)
            lp = -jnp.log(hi - lo)
            return jnp.where(inside, lp, -jnp.inf)
        return apply(prim, value, self.low, self.high,
                     name="uniform_log_prob")

    def entropy(self):
        def prim(lo, hi):
            return jnp.log(hi - lo)
        return apply(prim, self.low, self.high, name="uniform_entropy")


def _norm_log_p(logits):
    """paddle semantics: input is UNNORMALIZED PROBABILITIES
    (distribution.py Categorical docstring)."""
    return jnp.log(jnp.maximum(
        logits / jnp.sum(logits, axis=-1, keepdims=True), 1e-30))


class Categorical(Distribution):
    def __init__(self, logits, name=None):
        self.logits = _keep(logits)
        self._log_p_cache = None

    @property
    def _log_p(self):
        # cache the normalized log-probs per raw logits value (sampling loops
        # call this every draw; autograd doesn't go through here — log_prob/
        # entropy renormalize inside their prim)
        raw = _raw(self.logits)
        if self._log_p_cache is None or self._log_p_cache[0] is not raw:
            self._log_p_cache = (raw, _norm_log_p(raw))
        return self._log_p_cache[1]

    def sample(self, shape=()):
        shape = tuple(shape)
        log_p = self._log_p
        out = jax.random.categorical(next_key(), log_p,
                                     shape=shape + log_p.shape[:-1])
        return Tensor(out.astype(jnp.int32))

    def log_prob(self, value):
        idx = unwrap(value).astype(jnp.int32)

        def prim(logits):
            log_p = _norm_log_p(logits)
            if log_p.ndim == 1:
                return jnp.take(log_p, idx)
            return jnp.take_along_axis(log_p, idx[..., None], axis=-1)[..., 0]
        return apply(prim, self.logits, name="categorical_log_prob")

    def probs(self, value):
        idx = unwrap(value).astype(jnp.int32)

        def prim(logits):
            p = jnp.exp(_norm_log_p(logits))
            if p.ndim == 1:
                return jnp.take(p, idx)
            return jnp.take_along_axis(p, idx[..., None], axis=-1)[..., 0]
        return apply(prim, self.logits, name="categorical_probs")

    def entropy(self):
        def prim(logits):
            log_p = _norm_log_p(logits)
            return -jnp.sum(jnp.exp(log_p) * log_p, axis=-1)
        return apply(prim, self.logits, name="categorical_entropy")


class Bernoulli(Distribution):
    def __init__(self, probs, name=None):
        self.p = _keep(probs)

    def sample(self, shape=()):
        shape = tuple(shape)
        p = _raw(self.p)
        u = jax.random.uniform(next_key(), shape + jnp.shape(p))
        return Tensor((u < p).astype(jnp.float32))

    def log_prob(self, value):
        def prim(v, p):
            return v * jnp.log(jnp.maximum(p, 1e-30)) + \
                (1 - v) * jnp.log(jnp.maximum(1 - p, 1e-30))
        return apply(prim, value, self.p, name="bernoulli_log_prob")

    def entropy(self):
        def prim(p):
            return -(p * jnp.log(jnp.maximum(p, 1e-30))
                     + (1 - p) * jnp.log(jnp.maximum(1 - p, 1e-30)))
        return apply(prim, self.p, name="bernoulli_entropy")


class Beta(Distribution):
    def __init__(self, alpha, beta, name=None):
        self.alpha = _keep(alpha)
        self.beta = _keep(beta)

    def sample(self, shape=()):
        shape = tuple(shape)
        a, b = _raw(self.alpha), _raw(self.beta)
        out = jax.random.beta(next_key(), a, b,
                              shape=shape + jnp.broadcast_shapes(
                                  jnp.shape(a), jnp.shape(b)))
        return Tensor(out)

    def log_prob(self, value):
        def prim(v, a, b):
            lbeta = (jax.lax.lgamma(a) + jax.lax.lgamma(b)
                     - jax.lax.lgamma(a + b))
            return (a - 1) * jnp.log(v) + (b - 1) * jnp.log1p(-v) - lbeta
        return apply(prim, value, self.alpha, self.beta, name="beta_log_prob")


class Multinomial(Distribution):
    def __init__(self, total_count, probs, name=None):
        self.n = int(total_count)
        self.p = _keep(probs)

    def sample(self, shape=()):
        p = _raw(self.p)
        logp = jnp.log(jnp.maximum(p / jnp.sum(p, -1, keepdims=True), 1e-30))
        draws = jax.random.categorical(
            next_key(), logp, shape=tuple(shape) + (self.n,) + p.shape[:-1])
        k = p.shape[-1]
        onehot = jax.nn.one_hot(draws, k)
        return Tensor(jnp.sum(onehot, axis=len(tuple(shape))))


def kl_divergence(p, q):
    if isinstance(p, Normal) and isinstance(q, Normal):
        def prim(pl, ps, ql, qs):
            var_ratio = (ps / qs) ** 2
            t1 = ((pl - ql) / qs) ** 2
            return 0.5 * (var_ratio + t1 - 1 - jnp.log(var_ratio))
        return apply(prim, p.loc, p.scale, q.loc, q.scale, name="kl_normal")
    if isinstance(p, Categorical) and isinstance(q, Categorical):
        def prim(pl, ql):
            plog, qlog = _norm_log_p(pl), _norm_log_p(ql)
            return jnp.sum(jnp.exp(plog) * (plog - qlog), axis=-1)
        return apply(prim, p.logits, q.logits, name="kl_categorical")
    if isinstance(p, Uniform) and isinstance(q, Uniform):
        def prim(pl, ph, ql, qh):
            return jnp.log((qh - ql) / (ph - pl))
        return apply(prim, p.low, p.high, q.low, q.high, name="kl_uniform")
    raise NotImplementedError(
        f"kl_divergence({type(p).__name__}, {type(q).__name__})")


class MultivariateNormalDiag(Distribution):
    """fluid.layers.distributions MultivariateNormalDiag parity: Normal with
    diagonal covariance (loc vector + diag scale vector)."""

    def __init__(self, loc, scale):
        super().__init__()
        self._n = Normal(loc, scale)
        self.loc = self._n.loc
        self.scale = self._n.scale

    def sample(self, shape=()):
        return self._n.sample(shape)

    def log_prob(self, value):
        import jax.numpy as jnp

        from ..core.dispatch import apply
        per = self._n.log_prob(value)
        return apply(lambda v: jnp.sum(v, axis=-1), per,
                     name="mvn_diag_logprob")

    def entropy(self):
        import jax.numpy as jnp

        from ..core.dispatch import apply
        per = self._n.entropy()
        return apply(lambda v: jnp.sum(v, axis=-1), per,
                     name="mvn_diag_entropy")

    def kl_divergence(self, other):
        import jax.numpy as jnp

        from ..core.dispatch import apply
        per = self._n.kl_divergence(other._n if isinstance(
            other, MultivariateNormalDiag) else other)
        return apply(lambda v: jnp.sum(v, axis=-1), per, name="mvn_diag_kl")


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="int64"):  # noqa: A002
    """fluid.layers.sampling_id parity: sample a category index per row from
    the given probability matrix."""
    import jax
    import jax.numpy as jnp

    from ..core.dispatch import apply
    from ..core.dtypes import convert_dtype
    from ..core.random import next_key_data

    # narrow the requested dtype through the x64 policy (int64 -> int32,
    # README §Scope) BEFORE astype, so jax never sees — and warns about —
    # an unavailable 64-bit request
    dtype = convert_dtype(dtype)

    if seed:  # reference contract: fixed nonzero seed -> deterministic
        def prim_seeded(p):
            key = jax.random.PRNGKey(seed)
            logits = jnp.log(jnp.maximum(p, 1e-12))
            return jax.random.categorical(key, logits, axis=-1).astype(dtype)
        return apply(prim_seeded, x, name="sampling_id")

    key_data = next_key_data()

    def prim(p, kd):
        key = jax.random.wrap_key_data(kd)
        logits = jnp.log(jnp.maximum(p, 1e-12))
        return jax.random.categorical(key, logits, axis=-1).astype(dtype)

    return apply(prim, x, key_data, name="sampling_id")
