"""First-party probability distributions for policy heads.

The reference leans on distrax/tensorflow-probability (reference
stoix/networks/distributions.py, heads.py); neither is a dependency here, so
this module provides the needed surface natively in JAX:

    d.sample(seed=key)   d.log_prob(x)   d.entropy()   d.mode()   d.mean()
    d.kl_divergence(other)

All math is elementwise fp32 and shape-static so distributions can live inside
jit/scan/shard_map without tracing hazards. Distributions are plain Python
objects over traced arrays — they never cross a jit boundary.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

_HALF_LOG_2PI = 0.5 * math.log(2.0 * math.pi)

# Widest last axis that `pick_along_last` picks from by select; above it, by
# gather. From the chip (TPU v5e, PR 30, PERF.md §6): forward + gradient of one
# pick from normalised logits, gather against select, in ns a pick:
#   W        2     18    128    512   2,048   50,304
#   gather  11.2   15.5   19.4   29.0   73.7   1,603
#   select   0.3    0.7    5.3   14.4   57.6   1,415
# and of the pick alone 11.0 / 18.3 / 14.5 / 16.0 / 25.4 / 302 against
# 0.2 / 0.3 / 1.7 / 6.3 / 25.1 / 612: alone the forms cross at about 2,048,
# so the constant is the widest width measured at which the select still wins
# by half in both settings.
PICK_SELECT_MAX_WIDTH = 512


def pick_along_last(x: jax.Array, index: jax.Array) -> jax.Array:
    """`x[..., index]`: the entry of the last axis of `x` [..., W] that `index`
    [...] names, i.e. `take_along_axis(x, index[..., None], -1)[..., 0]`.

    `index` must lie in `[0, W)`: every caller passes an action that the same
    head sampled or a buffer stored. (Outside it `take_along_axis` wraps a
    negative index and fills NaN; the select gives 0. Neither is emulated.)

    The form is chosen by the static width W. XLA:TPU runs a gather of single
    elements as a serial loop, 11 ns a pick and more, whatever W is; up to
    `PICK_SELECT_MAX_WIDTH` the pick is therefore a masked sum over the row,
    elementwise work that fuses into its neighbours and shows in a device trace
    under `pick_select`. `where`, not a product with a one-hot: a masked logit
    holds `finfo.min`, and an unpicked NaN or inf must not reach the sum. The
    sum has one non-zero term, so value and gradient are the gather's and its
    scatter's bit for bit (but a picked -0.0 comes back +0.0). Above the
    threshold the gather stays exactly as it was: on its own a wide row costs
    more to read than a pick costs to loop over.
    """
    index = jnp.asarray(index, jnp.int32)
    if x.shape[-1] > PICK_SELECT_MAX_WIDTH:
        return jnp.take_along_axis(x, index[..., None], axis=-1)[..., 0]
    if index.ndim != x.ndim - 1:
        raise ValueError(f"index of shape {index.shape} picks from no x of shape {x.shape}")
    with jax.named_scope("pick_select"):
        iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        return jnp.sum(jnp.where(iota == index[..., None], x, 0), axis=-1)


class Distribution:
    """Minimal distribution interface."""

    def sample(self, *, seed: jax.Array) -> jax.Array:
        raise NotImplementedError

    def sample_n(self, n: int, *, seed: jax.Array) -> jax.Array:
        keys = jax.random.split(seed, n)
        return jax.vmap(lambda k: self.sample(seed=k))(keys)

    def log_prob(self, value: jax.Array) -> jax.Array:
        raise NotImplementedError

    def entropy(self) -> jax.Array:
        raise NotImplementedError

    def mode(self) -> jax.Array:
        raise NotImplementedError

    def mean(self) -> jax.Array:
        raise NotImplementedError

    def sample_and_log_prob(self, *, seed: jax.Array):
        x = self.sample(seed=seed)
        return x, self.log_prob(x)

    def kl_divergence(self, other: "Distribution") -> jax.Array:
        raise NotImplementedError


class Categorical(Distribution):
    """Categorical over the last axis of `logits`, with optional action mask."""

    def __init__(self, logits: jax.Array, mask: Optional[jax.Array] = None):
        if mask is not None:
            neg_inf = jnp.finfo(logits.dtype).min
            logits = jnp.where(mask > 0, logits, neg_inf)
        self.logits = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)

    @property
    def num_categories(self) -> int:
        return self.logits.shape[-1]

    @property
    def probs(self) -> jax.Array:
        return jnp.exp(self.logits)

    def sample(self, *, seed: jax.Array) -> jax.Array:
        return jax.random.categorical(seed, self.logits, axis=-1)

    def log_prob(self, value: jax.Array) -> jax.Array:
        return pick_along_last(self.logits, value)

    def entropy(self) -> jax.Array:
        p = self.probs
        return -jnp.sum(p * jnp.where(p > 0, self.logits, 0.0), axis=-1)

    def mode(self) -> jax.Array:
        return jnp.argmax(self.logits, axis=-1)

    def mean(self) -> jax.Array:
        return jnp.sum(self.probs * jnp.arange(self.num_categories), axis=-1)

    def kl_divergence(self, other: "Categorical") -> jax.Array:
        p = self.probs
        return jnp.sum(p * jnp.where(p > 0, self.logits - other.logits, 0.0), axis=-1)


def _mask_preferences(preferences: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    if mask is None:
        return preferences
    return jnp.where(mask > 0, preferences, jnp.finfo(preferences.dtype).min)


class EpsilonGreedy(Categorical):
    """Epsilon-greedy over Q-values — returned by DiscreteQNetworkHead so acting
    is `dist.sample(...)` uniformly across value- and policy-based systems
    (reference stoix/networks/heads.py:202-217 returns distrax.EpsilonGreedy).

    With a mask, the greedy argmax is taken over LEGAL actions only and the
    epsilon mass is spread uniformly over legal actions.
    """

    def __init__(self, preferences: jax.Array, epsilon: float, mask: Optional[jax.Array] = None):
        self.preferences = preferences
        self.epsilon = epsilon
        num = preferences.shape[-1]
        masked_prefs = _mask_preferences(preferences, mask)
        self._masked_preferences = masked_prefs
        greedy = jax.nn.one_hot(jnp.argmax(masked_prefs, axis=-1), num)
        if mask is None:
            uniform = jnp.ones_like(preferences) / num
        else:
            valid = (mask > 0).astype(preferences.dtype)
            uniform = valid / jnp.sum(valid, axis=-1, keepdims=True)
        probs = (1.0 - epsilon) * greedy + epsilon * uniform
        super().__init__(jnp.log(probs + 1e-12), mask=mask)

    def mode(self) -> jax.Array:
        return jnp.argmax(self._masked_preferences, axis=-1)


class Greedy(Categorical):
    def __init__(self, preferences: jax.Array, mask: Optional[jax.Array] = None):
        self.preferences = preferences
        masked_prefs = _mask_preferences(preferences, mask)
        self._masked_preferences = masked_prefs
        num = preferences.shape[-1]
        probs = jax.nn.one_hot(jnp.argmax(masked_prefs, axis=-1), num)
        super().__init__(jnp.log(probs + 1e-12), mask=mask)

    def mode(self) -> jax.Array:
        return jnp.argmax(self._masked_preferences, axis=-1)


class Normal(Distribution):
    def __init__(self, loc: jax.Array, scale: jax.Array):
        self.loc = loc
        self.scale = scale

    def sample(self, *, seed: jax.Array) -> jax.Array:
        eps = jax.random.normal(seed, jnp.shape(self.loc), dtype=jnp.result_type(self.loc))
        return self.loc + self.scale * eps

    def log_prob(self, value: jax.Array) -> jax.Array:
        z = (value - self.loc) / self.scale
        return -0.5 * z**2 - jnp.log(self.scale) - _HALF_LOG_2PI

    def entropy(self) -> jax.Array:
        return 0.5 + _HALF_LOG_2PI + jnp.log(self.scale)

    def mode(self) -> jax.Array:
        return self.loc

    def mean(self) -> jax.Array:
        return self.loc

    def stddev(self) -> jax.Array:
        return self.scale

    def kl_divergence(self, other: "Normal") -> jax.Array:
        var_ratio = (self.scale / other.scale) ** 2
        t1 = ((self.loc - other.loc) / other.scale) ** 2
        return 0.5 * (var_ratio + t1 - 1.0 - jnp.log(var_ratio))


class Independent(Distribution):
    """Sums log_prob/entropy/kl over the last `reinterpreted_batch_ndims` dims."""

    def __init__(self, distribution: Distribution, reinterpreted_batch_ndims: int = 1):
        self.distribution = distribution
        self._ndims = int(reinterpreted_batch_ndims)

    def _reduce(self, x: jax.Array) -> jax.Array:
        return jnp.sum(x, axis=tuple(range(-self._ndims, 0)))

    def sample(self, *, seed: jax.Array) -> jax.Array:
        return self.distribution.sample(seed=seed)

    def sample_and_log_prob(self, *, seed: jax.Array):
        x, lp = self.distribution.sample_and_log_prob(seed=seed)
        return x, self._reduce(lp)

    def log_prob(self, value: jax.Array) -> jax.Array:
        return self._reduce(self.distribution.log_prob(value))

    def entropy(self) -> jax.Array:
        return self._reduce(self.distribution.entropy())

    def mode(self) -> jax.Array:
        return self.distribution.mode()

    def mean(self) -> jax.Array:
        return self.distribution.mean()

    def stddev(self) -> jax.Array:
        return self.distribution.stddev()

    def kl_divergence(self, other: "Independent") -> jax.Array:
        return self._reduce(self.distribution.kl_divergence(other.distribution))


class MultivariateNormalDiag(Independent):
    def __init__(self, loc: jax.Array, scale_diag: jax.Array):
        super().__init__(Normal(loc, scale_diag), 1)
        self.loc = loc
        self.scale_diag = scale_diag


class Deterministic(Distribution):
    """A point mass — deterministic policies (DDPG/TD3) behind the same API."""

    def __init__(self, loc: jax.Array):
        self.loc = loc

    def sample(self, *, seed: jax.Array) -> jax.Array:
        del seed
        return self.loc

    def log_prob(self, value: jax.Array) -> jax.Array:
        return jnp.zeros(jnp.shape(self.loc)[:-1] if jnp.ndim(self.loc) else ())

    def entropy(self) -> jax.Array:
        return jnp.zeros(jnp.shape(self.loc)[:-1] if jnp.ndim(self.loc) else ())

    def mode(self) -> jax.Array:
        return self.loc

    def mean(self) -> jax.Array:
        return self.loc


class TanhNormal(Distribution):
    """tanh-squashed Normal, affinely rescaled to [minimum, maximum].

    Equivalent of the reference's `AffineTanhTransformedDistribution`
    (reference stoix/networks/distributions.py:24-95): log_prob is clipped at
    the boundaries (atanh diverges) via a `threshold` below the max action.
    """

    def __init__(
        self,
        loc: jax.Array,
        scale: jax.Array,
        minimum: jax.Array = -1.0,
        maximum: jax.Array = 1.0,
        threshold: float = 0.999,
    ):
        self.base = Normal(loc, scale)
        self._scale = (jnp.asarray(maximum) - jnp.asarray(minimum)) / 2.0
        self._shift = (jnp.asarray(maximum) + jnp.asarray(minimum)) / 2.0
        self._threshold = threshold

    def _forward(self, x: jax.Array) -> jax.Array:
        return jnp.tanh(x) * self._scale + self._shift

    def _inverse(self, y: jax.Array) -> jax.Array:
        u = (y - self._shift) / self._scale
        u = jnp.clip(u, -self._threshold, self._threshold)
        return jnp.arctanh(u)

    def _log_det_jacobian(self, x: jax.Array) -> jax.Array:
        # d/dx [scale * tanh(x)] = scale * (1 - tanh^2 x); numerically stable form.
        return jnp.log(self._scale) + 2.0 * (math.log(2.0) - x - jax.nn.softplus(-2.0 * x))

    def sample(self, *, seed: jax.Array) -> jax.Array:
        return self._forward(self.base.sample(seed=seed))

    def sample_and_log_prob(self, *, seed: jax.Array):
        x = self.base.sample(seed=seed)
        y = self._forward(x)
        lp = self.base.log_prob(x) - self._log_det_jacobian(x)
        return y, lp

    def log_prob(self, value: jax.Array) -> jax.Array:
        x = self._inverse(value)
        return self.base.log_prob(x) - self._log_det_jacobian(x)

    def entropy(self) -> jax.Array:
        # Base entropy + expected log-det-jacobian at the mean (the reference's
        # single-sample estimator uses the mode; this matches distrax's approach
        # of estimating with one point).
        return self.base.entropy() + self._log_det_jacobian(self.base.loc)

    def mode(self) -> jax.Array:
        return self._forward(self.base.loc)

    def mean(self) -> jax.Array:
        return self._forward(self.base.loc)


class Beta(Distribution):
    """Beta(alpha, beta) on [0, 1], sampled via Gamma draws; `ClippedBeta`
    equivalent (reference distributions.py:97-113) clips samples away from
    exact 0/1 for log_prob stability.
    """

    _eps = 1e-6

    def __init__(self, alpha: jax.Array, beta: jax.Array):
        self.alpha = alpha
        self.beta = beta

    def sample(self, *, seed: jax.Array) -> jax.Array:
        k1, k2 = jax.random.split(seed)
        ga = jax.random.gamma(k1, self.alpha)
        gb = jax.random.gamma(k2, self.beta)
        x = ga / (ga + gb)
        return jnp.clip(x, self._eps, 1.0 - self._eps)

    def log_prob(self, value: jax.Array) -> jax.Array:
        a, b = self.alpha, self.beta
        lbeta = jax.scipy.special.gammaln(a) + jax.scipy.special.gammaln(b) - jax.scipy.special.gammaln(a + b)
        return (a - 1) * jnp.log(value) + (b - 1) * jnp.log1p(-value) - lbeta

    def entropy(self) -> jax.Array:
        a, b = self.alpha, self.beta
        dg = jax.scipy.special.digamma
        lbeta = jax.scipy.special.gammaln(a) + jax.scipy.special.gammaln(b) - jax.scipy.special.gammaln(a + b)
        return lbeta - (a - 1) * dg(a) - (b - 1) * dg(b) + (a + b - 2) * dg(a + b)

    def mode(self) -> jax.Array:
        a, b = self.alpha, self.beta
        interior = (a - 1) / jnp.maximum(a + b - 2, self._eps)
        return jnp.clip(jnp.where((a > 1) & (b > 1), interior, jnp.where(a >= b, 1.0, 0.0)), self._eps, 1 - self._eps)

    def mean(self) -> jax.Array:
        return self.alpha / (self.alpha + self.beta)


class AffineBeta(Independent):
    """Beta rescaled to an action interval [minimum, maximum]."""

    def __init__(self, alpha: jax.Array, beta: jax.Array, minimum: jax.Array, maximum: jax.Array):
        self._base = Beta(alpha, beta)
        self._lo = jnp.asarray(minimum)
        self._width = jnp.asarray(maximum) - jnp.asarray(minimum)
        super().__init__(self._base, 1)

    def _fwd(self, x: jax.Array) -> jax.Array:
        return self._lo + self._width * x

    def _inv(self, y: jax.Array) -> jax.Array:
        return jnp.clip((y - self._lo) / self._width, Beta._eps, 1 - Beta._eps)

    def sample(self, *, seed: jax.Array) -> jax.Array:
        return self._fwd(self._base.sample(seed=seed))

    def log_prob(self, value: jax.Array) -> jax.Array:
        return jnp.sum(self._base.log_prob(self._inv(value)) - jnp.log(self._width), axis=-1)

    def entropy(self) -> jax.Array:
        return jnp.sum(self._base.entropy() + jnp.log(self._width), axis=-1)

    def mode(self) -> jax.Array:
        return self._fwd(self._base.mode())

    def mean(self) -> jax.Array:
        return self._fwd(self._base.mean())


class DiscreteValued(Distribution):
    """A categorical over a fixed real-valued support — the distributional
    critic used by D4PG-style heads and the `DiscreteValuedTfpDistribution`
    (reference distributions.py:116-208). Exposes mean/variance over the support.
    """

    def __init__(self, logits: jax.Array, values: jax.Array):
        self.dist = Categorical(logits)
        self.values = values  # [num_atoms]

    @property
    def logits(self) -> jax.Array:
        return self.dist.logits

    @property
    def probs(self) -> jax.Array:
        return self.dist.probs

    def sample(self, *, seed: jax.Array) -> jax.Array:
        idx = self.dist.sample(seed=seed)
        return self.values[idx]

    def mean(self) -> jax.Array:
        return jnp.sum(self.probs * self.values, axis=-1)

    def variance(self) -> jax.Array:
        m = self.mean()
        return jnp.sum(self.probs * (self.values - m[..., None]) ** 2, axis=-1)

    def mode(self) -> jax.Array:
        return self.values[jnp.argmax(self.logits, axis=-1)]

    def entropy(self) -> jax.Array:
        return self.dist.entropy()


class MultiDiscrete(Distribution):
    """Factorized categorical over several discrete action dimensions
    (reference distributions.py:211-242): log_prob/entropy sum across dims.
    """

    def __init__(self, flat_logits: jax.Array, num_values: Sequence[int]):
        self.num_values = tuple(int(n) for n in num_values)
        self.dists = []
        start = 0
        for n in self.num_values:
            self.dists.append(Categorical(flat_logits[..., start : start + n]))
            start += n

    def sample(self, *, seed: jax.Array) -> jax.Array:
        keys = jax.random.split(seed, len(self.dists))
        return jnp.stack([d.sample(seed=k) for d, k in zip(self.dists, keys)], axis=-1)

    def log_prob(self, value: jax.Array) -> jax.Array:
        lps = [d.log_prob(value[..., i]) for i, d in enumerate(self.dists)]
        return sum(lps)

    def entropy(self) -> jax.Array:
        return sum(d.entropy() for d in self.dists)

    def mode(self) -> jax.Array:
        return jnp.stack([d.mode() for d in self.dists], axis=-1)

    def kl_divergence(self, other: "MultiDiscrete") -> jax.Array:
        return sum(a.kl_divergence(b) for a, b in zip(self.dists, other.dists))
