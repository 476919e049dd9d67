"""Invertible value transforms for scale-robust value learning.

The reference uses rlax's SIGNED_HYPERBOLIC_PAIR inside R2D2
(reference stoix/systems/q_learning/rec_r2d2.py:18,346-347); this module
provides the pair natively plus the identity pair, and a helper for
transformed n-step Q targets.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from stoix_tpu.ops.distributions import pick_along_last

Array = jax.Array


class TxPair(NamedTuple):
    apply: Callable[[Array], Array]
    apply_inv: Callable[[Array], Array]


def signed_hyperbolic(x: Array, eps: float = 1e-3) -> Array:
    """h(x) = sign(x) * (sqrt(|x| + 1) - 1) + eps * x (Pohlen et al. 2018)."""
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def signed_parabolic(x: Array, eps: float = 1e-3) -> Array:
    """Inverse of signed_hyperbolic."""
    z = jnp.sqrt(1.0 + 4.0 * eps * (eps + 1.0 + jnp.abs(x))) / (2.0 * eps) - 1.0 / (2.0 * eps)
    return jnp.sign(x) * (jnp.square(z) - 1.0)


IDENTITY_PAIR = TxPair(lambda x: x, lambda x: x)
SIGNED_HYPERBOLIC_PAIR = TxPair(signed_hyperbolic, signed_parabolic)


def transformed_n_step_q_learning_td(
    q_tm1: Array,
    a_tm1: Array,
    target_q_t: Array,
    a_t: Array,
    r_t: Array,
    discount_t: Array,
    n: int,
    tx_pair: TxPair = SIGNED_HYPERBOLIC_PAIR,
) -> Array:
    """TD errors for transformed n-step Q-learning over 1-D time sequences
    (vmap over batch). Matches the behavior of rlax.transformed_n_step_q_learning:
    targets are built in raw space from untransformed bootstrap values, then
    re-transformed for comparison with q_tm1.

    q_tm1:       [T+1, A] online Q-values (transformed space).
    a_tm1:       [T+1]   actions actually taken.
    target_q_t:  [T+1, A] target-network Q-values (transformed space).
    a_t:         [T+1]   selector actions for the bootstrap (e.g. argmax online).
    r_t, discount_t: [T].
    Returns TD errors [T].
    """
    from stoix_tpu.ops.multistep import n_step_bootstrapped_returns

    v_t = tx_pair.apply_inv(pick_along_last(target_q_t, a_t))
    targets = n_step_bootstrapped_returns(
        r_t[None], discount_t[None], v_t[1:][None], n=n, batch_major=True
    )[0]
    targets = tx_pair.apply(targets)
    qa_tm1 = pick_along_last(q_tm1, a_tm1)
    return jax.lax.stop_gradient(targets) - qa_tm1[:-1]


class CategoricalTxPair(NamedTuple):
    """Scalar <-> categorical transform pair for distributional MuZero heads.

    `apply` maps raw scalars to two-hot probability vectors over a fixed atom
    support laid out in TRANSFORMED space; `apply_inv` maps logits back to raw
    scalars via the support expectation. Native replacement for
    rlax.muzero_pair as used at reference stoix/systems/search/ff_mz.py:537.
    """

    apply: Callable[[Array], Array]
    apply_inv: Callable[[Array], Array]
    num_atoms: int


def twohot(x: Array, atoms: Array) -> Array:
    """Project scalars [...] onto probs [..., N] over a uniform atom support:
    each scalar becomes weight split between its two neighbouring atoms."""
    vmin, vmax = atoms[0], atoms[-1]
    step = (vmax - vmin) / (atoms.shape[0] - 1)
    x = jnp.clip(x, vmin, vmax)
    pos = (x - vmin) / step
    low = jnp.clip(jnp.floor(pos), 0, atoms.shape[0] - 1)
    up_w = pos - low
    low = low.astype(jnp.int32)
    high = jnp.clip(low + 1, 0, atoms.shape[0] - 1)
    one_hot_low = jax.nn.one_hot(low, atoms.shape[0])
    one_hot_high = jax.nn.one_hot(high, atoms.shape[0])
    return one_hot_low * (1.0 - up_w[..., None]) + one_hot_high * up_w[..., None]


def muzero_pair(
    num_atoms: int = 601,
    vmin: float = -300.0,
    vmax: float = 300.0,
    tx_pair: TxPair = SIGNED_HYPERBOLIC_PAIR,
) -> CategoricalTxPair:
    """Categorical value/reward codec: scalar -> tx -> two-hot over the support
    (training target); logits -> softmax expectation -> tx^-1 (scalar read)."""
    atoms = jnp.linspace(vmin, vmax, num_atoms)

    def apply(scalar: Array) -> Array:
        return twohot(tx_pair.apply(scalar), atoms)

    def apply_inv(logits: Array) -> Array:
        probs = jax.nn.softmax(logits, axis=-1)
        return tx_pair.apply_inv(jnp.sum(probs * atoms, axis=-1))

    return CategoricalTxPair(apply=apply, apply_inv=apply_inv, num_atoms=num_atoms)
