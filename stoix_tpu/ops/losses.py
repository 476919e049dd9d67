"""RL loss functions, batched.

Parity surface: reference stoix/utils/loss.py:17-314 (PPO clip/penalty, DPO,
clipped value loss, categorical double-Q / C51, (double) Q-learning with
optional Huber, TD, categorical TD, Munchausen-Q, quantile regression /
QR-Q-learning). The categorical projection (rlax.categorical_l2_project in the
reference) is implemented natively here.

All functions take batched arrays ([B, ...]) and return scalar means unless
noted; everything is elementwise/matmul-free math that XLA fuses into the
surrounding update step.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from stoix_tpu.ops.distributions import pick_along_last

Array = jax.Array


def huber_loss(x: Array, delta: float = 1.0) -> Array:
    abs_x = jnp.abs(x)
    quadratic = jnp.minimum(abs_x, delta)
    return 0.5 * quadratic**2 + delta * (abs_x - quadratic)


# --------------------------------------------------------------------------- #
# Policy-gradient losses
# --------------------------------------------------------------------------- #


# Numerical guard for exp(log_ratio) in the ratio-based surrogates. The clip
# region only ever involves |log_ratio| <= log(1 +/- eps) ~ 0.2, so clamping
# at +/-20 (ratio <= 4.9e8) is semantically free — but it keeps the loss and
# its gradients FINITE when a sharpened continuous policy (sigma -> min_scale)
# meets a stale minibatch sample. Without it the loss overflows (observed:
# 3.4e27 on hopper+obs-norm at 192k steps), the global-norm clip divides by
# inf, and the params go NaN — the root cause of the "0.0 forever" locomotion
# runs (a NaN action terminates the episode at step 1 with return exactly 0).
_LOG_RATIO_CLAMP = 20.0


def _safe_ratio(log_prob: Array, old_log_prob: Array) -> Array:
    return jnp.exp(jnp.clip(log_prob - old_log_prob, -_LOG_RATIO_CLAMP, _LOG_RATIO_CLAMP))


def ppo_clip_loss(log_prob: Array, old_log_prob: Array, advantage: Array, epsilon: float) -> Array:
    """PPO clipped surrogate objective (Schulman et al. 2017)."""
    ratio = _safe_ratio(log_prob, old_log_prob)
    unclipped = ratio * advantage
    clipped = jnp.clip(ratio, 1.0 - epsilon, 1.0 + epsilon) * advantage
    return -jnp.mean(jnp.minimum(unclipped, clipped))


def impact_loss(
    log_prob: Array,
    behavior_log_prob: Array,
    target_log_prob: Array,
    advantage: Array,
    epsilon: float,
    rho_clip: float,
) -> Array:
    """IMPACT surrogate (Luo et al. 2019, arXiv:1912.00167): PPO's clipped
    objective taken against a slow-moving TARGET policy, importance-weighted
    from the BEHAVIOR policy that actually collected the (possibly stale)
    trajectory:

        rho  = min(exp(log pi_target - log pi_behavior), rho_clip)
        r    = exp(log pi_theta - log pi_target)
        L    = -E[ min(rho * r * A, rho * clip(r, 1-eps, 1+eps) * A) ]

    `rho` is a stop-gradient-free constant w.r.t. theta (neither policy in it
    is the online one), so no stop_gradient is needed. When the target and
    behavior policies coincide (fresh on-policy data, rho_clip >= 1) rho is
    exactly 1.0 and the expression reduces BITWISE to `ppo_clip_loss` —
    tests/test_impact.py pins that identity. Both log-ratios reuse the
    +/-_LOG_RATIO_CLAMP guard (see above) so a sharpened policy meeting a
    very stale sample cannot overflow the loss.
    """
    ratio = _safe_ratio(log_prob, target_log_prob)
    is_ratio = jnp.minimum(_safe_ratio(target_log_prob, behavior_log_prob), rho_clip)
    unclipped = is_ratio * ratio * advantage
    clipped = is_ratio * jnp.clip(ratio, 1.0 - epsilon, 1.0 + epsilon) * advantage
    return -jnp.mean(jnp.minimum(unclipped, clipped))


def ppo_penalty_loss(
    log_prob: Array, old_log_prob: Array, advantage: Array, beta: float, kl_approx: Array
) -> Array:
    """PPO with a KL penalty instead of clipping."""
    ratio = _safe_ratio(log_prob, old_log_prob)
    return -jnp.mean(ratio * advantage - beta * kl_approx)


def dpo_loss(
    log_prob: Array, old_log_prob: Array, advantage: Array, alpha: float, beta: float
) -> Array:
    """Drift-based PPO alternative (DPO, Garcin et al.): asymmetric drift
    penalties replace the hard clip."""
    log_ratio = jnp.clip(log_prob - old_log_prob, -_LOG_RATIO_CLAMP, _LOG_RATIO_CLAMP)
    ratio = jnp.exp(log_ratio)
    drift_pos = jax.nn.relu((ratio - 1.0) * advantage - alpha * jnp.tanh((ratio - 1.0) * advantage / alpha))
    drift_neg = jax.nn.relu(log_ratio * advantage - beta * jnp.tanh(log_ratio * advantage / beta))
    drift = jnp.where(advantage >= 0.0, drift_pos, drift_neg)
    return -jnp.mean(ratio * advantage - drift)


def clipped_value_loss(pred_value: Array, old_value: Array, targets: Array, epsilon: float) -> Array:
    """PPO-style value clipping: max of clipped and unclipped squared errors."""
    value_clipped = old_value + jnp.clip(pred_value - old_value, -epsilon, epsilon)
    return jnp.mean(jnp.maximum(jnp.square(pred_value - targets), jnp.square(value_clipped - targets)))


# --------------------------------------------------------------------------- #
# Q-learning losses
# --------------------------------------------------------------------------- #


def q_learning(
    q_tm1: Array,
    a_tm1: Array,
    r_t: Array,
    d_t: Array,
    q_t: Array,
    use_huber: bool = False,
    huber_delta: float = 1.0,
) -> Array:
    """One-step Q-learning: target r + γ max_a Q(s', a)."""
    target = r_t + d_t * jnp.max(q_t, axis=-1)
    qa_tm1 = pick_along_last(q_tm1, a_tm1)
    td = jax.lax.stop_gradient(target) - qa_tm1
    return jnp.mean(huber_loss(td, huber_delta) if use_huber else 0.5 * td**2)


def double_q_learning(
    q_tm1: Array,
    a_tm1: Array,
    r_t: Array,
    d_t: Array,
    q_t_value: Array,
    q_t_selector: Array,
    use_huber: bool = False,
    huber_delta: float = 1.0,
) -> Array:
    """Double Q-learning: online net selects, target net evaluates."""
    best_a = jnp.argmax(q_t_selector, axis=-1)
    target = r_t + d_t * pick_along_last(q_t_value, best_a)
    qa_tm1 = pick_along_last(q_tm1, a_tm1)
    td = jax.lax.stop_gradient(target) - qa_tm1
    return jnp.mean(huber_loss(td, huber_delta) if use_huber else 0.5 * td**2)


def td_learning(v_tm1: Array, r_t: Array, d_t: Array, v_t: Array, use_huber: bool = False) -> Array:
    td = jax.lax.stop_gradient(r_t + d_t * v_t) - v_tm1
    return jnp.mean(huber_loss(td) if use_huber else 0.5 * td**2)


def munchausen_q_learning(
    q_tm1: Array,
    a_tm1: Array,
    r_t: Array,
    d_t: Array,
    q_t_target: Array,
    q_tm1_target: Array,
    entropy_temperature: float,
    munchausen_coefficient: float,
    clip_value_min: float = -1e3,
) -> Array:
    """Munchausen-DQN (Vieillard et al. 2020): adds a scaled-log-policy bonus to
    the reward and a soft (log-sum-exp) backup."""
    tau = entropy_temperature
    # Soft target backup: tau * logsumexp(q'/tau) with policy weights.
    logits_t = q_t_target / tau
    lse_t = tau * jax.nn.logsumexp(logits_t, axis=-1)
    pi_t = jax.nn.softmax(logits_t, axis=-1)
    soft_v_t = jnp.sum(pi_t * (q_t_target - tau * jnp.log(pi_t + 1e-8)), axis=-1)
    del lse_t  # soft_v_t is the explicit expectation form of the same quantity.

    # Munchausen bonus: alpha * tau * log pi(a_tm1 | s_tm1), clipped.
    log_pi_tm1 = jax.nn.log_softmax(q_tm1_target / tau, axis=-1)
    red_term = pick_along_last(log_pi_tm1, a_tm1)
    munchausen = munchausen_coefficient * tau * jnp.clip(red_term, clip_value_min, 0.0)

    target = r_t + munchausen + d_t * soft_v_t
    qa_tm1 = pick_along_last(q_tm1, a_tm1)
    td = jax.lax.stop_gradient(target) - qa_tm1
    return jnp.mean(0.5 * td**2)


# --------------------------------------------------------------------------- #
# Distributional losses (C51 / QR)
# --------------------------------------------------------------------------- #


def categorical_l2_project(z_p: Array, probs: Array, z_q: Array) -> Array:
    """Project distribution (z_p, probs) onto support z_q (Bellemare et al. 2017).

    z_p: [B, M] source support; probs: [B, M]; z_q: [N] target support.
    Returns projected probs [B, N]. Native replacement for
    rlax.categorical_l2_project used at reference loss.py:81-104.
    """
    vmin, vmax = z_q[0], z_q[-1]
    n = z_q.shape[0]
    delta_z = (vmax - vmin) / (n - 1)
    clipped = jnp.clip(z_p, vmin, vmax)  # [B, M]
    # Fractional index of each source atom on the target grid.
    bj = (clipped - vmin) / delta_z  # [B, M]
    lower = jnp.floor(bj)
    upper = jnp.ceil(bj)
    # When lower == upper (atom exactly on a grid point), give full mass to it.
    eq = (upper == lower).astype(probs.dtype)
    lower_w = (upper - bj) + eq
    upper_w = bj - lower
    lower_idx = jnp.asarray(lower, jnp.int32)
    upper_idx = jnp.asarray(upper, jnp.int32)

    def project_one(p, lo, up, lw, uw):
        out = jnp.zeros((n,), probs.dtype)
        out = out.at[lo].add(p * lw)
        out = out.at[up].add(p * uw)
        return out

    return jax.vmap(project_one)(probs, lower_idx, upper_idx, lower_w, upper_w)


def categorical_double_q_learning(
    q_logits_tm1: Array,
    q_atoms_tm1: Array,
    a_tm1: Array,
    r_t: Array,
    d_t: Array,
    q_logits_t: Array,
    q_atoms_t: Array,
    q_t_selector: Array,
) -> Array:
    """C51 double-Q loss: project r + γ z onto the fixed support, cross-entropy
    against the online logits of the taken action (reference loss.py:81-104)."""
    best_a = jnp.argmax(q_t_selector, axis=-1)  # [B]
    num_atoms = q_atoms_tm1.shape[-1]
    # Atoms may be shared ([M], as the heads return) or per-batch ([B, M]).
    z_q = q_atoms_tm1 if q_atoms_tm1.ndim == 1 else q_atoms_tm1[0]
    target_z = r_t[..., None] + d_t[..., None] * q_atoms_t  # [B, M] via broadcast
    target_z = jnp.broadcast_to(target_z, r_t.shape + (num_atoms,))
    probs_t = jax.nn.softmax(q_logits_t, axis=-1)  # [B, A, M]
    probs_best = jnp.take_along_axis(probs_t, best_a[..., None, None].repeat(num_atoms, -1), axis=-2)[
        ..., 0, :
    ]  # [B, M]
    target = categorical_l2_project(target_z, probs_best, z_q)
    logits_a = jnp.take_along_axis(
        q_logits_tm1, a_tm1[..., None, None].repeat(num_atoms, -1), axis=-2
    )[..., 0, :]
    ce = -jnp.sum(jax.lax.stop_gradient(target) * jax.nn.log_softmax(logits_a, axis=-1), axis=-1)
    return jnp.mean(ce)


def categorical_td_learning(
    v_logits_tm1: Array, v_atoms: Array, r_t: Array, d_t: Array, v_logits_t: Array
) -> Array:
    """Distributional TD: project the bootstrapped value distribution."""
    target_z = r_t[..., None] + d_t[..., None] * v_atoms
    probs_t = jax.nn.softmax(v_logits_t, axis=-1)
    target = categorical_l2_project(target_z, probs_t, v_atoms)
    ce = -jnp.sum(jax.lax.stop_gradient(target) * jax.nn.log_softmax(v_logits_tm1, axis=-1), axis=-1)
    return jnp.mean(ce)


def quantile_regression_loss(
    dist_src: Array, tau_src: Array, dist_target: Array, huber_param: float = 1.0
) -> Array:
    """Quantile-regression (Huber) loss between quantile estimates and targets.

    dist_src: [N] source quantiles; tau_src: [N] quantile midpoints;
    dist_target: [M] target samples. Returns a scalar.
    """
    dist_target = jax.lax.stop_gradient(dist_target)
    delta = dist_target[None, :] - dist_src[:, None]  # [N, M]
    weight = jnp.abs(tau_src[:, None] - (delta < 0.0).astype(dist_src.dtype))
    if huber_param > 0:
        loss = huber_loss(delta, huber_param) * weight
    else:
        loss = jnp.abs(delta) * weight
    return jnp.mean(jnp.sum(jnp.mean(loss, axis=-1), axis=0))


def quantile_q_learning(
    dist_q_tm1: Array,
    tau_q_tm1: Array,
    a_tm1: Array,
    r_t: Array,
    d_t: Array,
    dist_q_t_selector: Array,
    dist_q_t: Array,
    huber_param: float = 1.0,
) -> Array:
    """QR-DQN loss (Dabney et al. 2018), batched.

    dist_q_tm1: [B, N, A]; tau: [B, N]; dist_q_t(_selector): [B, N, A].
    """
    q_t_selector = jnp.mean(dist_q_t_selector, axis=1)  # [B, A]
    best_a = jnp.argmax(q_t_selector, axis=-1)  # [B]
    n = dist_q_tm1.shape[1]
    dist_a_tm1 = jnp.take_along_axis(dist_q_tm1, a_tm1[:, None, None].repeat(n, 1), axis=-1)[..., 0]
    dist_best_t = jnp.take_along_axis(dist_q_t, best_a[:, None, None].repeat(n, 1), axis=-1)[..., 0]
    target = r_t[:, None] + d_t[:, None] * dist_best_t

    return jnp.mean(
        jax.vmap(quantile_regression_loss, in_axes=(0, 0, 0, None))(
            dist_a_tm1, tau_q_tm1, target, huber_param
        )
    )
