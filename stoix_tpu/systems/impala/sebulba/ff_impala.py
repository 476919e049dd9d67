"""Sebulba IMPALA (reference stoix/systems/impala/sebulba/ff_impala.py, 1054 LoC).

Off-policy actor-critic with V-trace corrections (Espeholt et al. 2018): the
actor threads' stored log-probs are the behavior policy; the learner computes
V-trace value targets and policy-gradient advantages
(stoix_tpu.ops.multistep.vtrace_td_error_and_advantage, replacing the
reference's rlax vmap at :426-439) in one pass per rollout. The host loop is
the Sebulba runner's (stoix_tpu/sebulba/runner.py); learner state, networks
and the actors' `act_fn` are the shared actor-critic ones
(stoix_tpu/sebulba/actor_critic.py).
"""

from __future__ import annotations

import sys
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams, PPOTransition
from stoix_tpu.observability import annotate
from stoix_tpu.ops import vtrace_td_error_and_advantage
from stoix_tpu.resilience import guards
from stoix_tpu.sebulba import runner
from stoix_tpu.sebulba.actor_critic import (
    CoreLearnerState,
    actor_critic_system,
    build_networks,
    normalize_trajectory,
    shard_learn_step,
)
from stoix_tpu.sebulba.runner import LAST_RUN_STATS  # noqa: F401 — read through this module
from stoix_tpu.sebulba.sources import OnPolicySource
from stoix_tpu.utils import config as config_lib


def build_impala_loss(actor_apply, critic_apply, config):
    """V-trace actor-critic loss over one [T, E] minibatch — shared by the
    separate-network and shared-torso variants. `actor_params`/`critic_params`
    may alias (shared torso)."""
    gamma = float(config.system.gamma)
    lam = float(config.system.get("vtrace_lambda", 1.0))
    clip_rho = float(config.system.get("clip_rho_threshold", 1.0))
    clip_pg_rho = float(config.system.get("clip_pg_rho_threshold", 1.0))

    def loss_fn(actor_params, critic_params, mb: PPOTransition):
        dist = actor_apply(actor_params, mb.obs)
        online_log_prob = dist.log_prob(mb.action)  # [T, E/m]
        values = critic_apply(critic_params, mb.obs)  # [T, E/m]
        bootstrap = critic_apply(critic_params, mb.next_obs)  # [T, E/m]

        rhos = jnp.exp(jax.lax.stop_gradient(online_log_prob) - mb.log_prob)
        d_t = gamma * (1.0 - mb.done.astype(jnp.float32))
        errors, pg_adv, _ = jax.vmap(
            lambda v, b, r, d, rho: vtrace_td_error_and_advantage(
                v, b, r, d, rho, lam, clip_rho, clip_pg_rho
            ),
            in_axes=1,
            out_axes=1,
        )(
            jax.lax.stop_gradient(values),
            jax.lax.stop_gradient(bootstrap),
            mb.reward,
            d_t,
            rhos,
        )
        pg_loss = -jnp.mean(pg_adv * online_log_prob)
        value_targets = jax.lax.stop_gradient(errors + values)
        value_loss = 0.5 * jnp.mean((values - value_targets) ** 2)
        entropy = dist.entropy().mean()
        total = (
            pg_loss
            + float(config.system.get("vf_coef", 0.5)) * value_loss
            - float(config.system.get("ent_coef", 0.01)) * entropy
        )
        return total, {
            "actor_loss": pg_loss,
            "value_loss": value_loss,
            "entropy": entropy,
            "mean_rho": jnp.mean(rhos),
        }

    return loss_fn


def split_env_minibatches(traj: PPOTransition, num_minibatches: int) -> PPOTransition:
    """[T, E] -> [m, T, E/m], time contiguous so each V-trace sees whole
    trajectories (reference ff_impala.py:525-556)."""
    return jax.tree.map(
        lambda x: jnp.swapaxes(
            x.reshape((x.shape[0], num_minibatches, -1) + x.shape[2:]), 0, 1
        ),
        traj,
    )


def maybe_normalize_rewards(traj: PPOTransition, config) -> PPOTransition:
    """Batch reward normalization option (reference ff_impala.py:385-389).

    Statistics are reduced over the "data" mesh axis so the scaling matches
    the reference's whole-batch normalization regardless of learner device
    count (per-shard stats would make gradients depend on the sharding)."""
    if not bool(config.system.get("normalize_rewards", False)):
        return traj
    r_mean = jax.lax.pmean(jnp.mean(traj.reward), "data")
    r_sq = jax.lax.pmean(jnp.mean(traj.reward**2), "data")
    r_std = jnp.sqrt(jnp.maximum(r_sq - r_mean**2, 0.0))
    scale = float(config.system.get("reward_scale", 1.0))
    eps = float(config.system.get("reward_eps", 1e-8))
    return traj._replace(reward=scale * (traj.reward - r_mean) / (r_std + eps))


def get_impala_learn_step(actor_apply, critic_apply, update_fns, config, mesh: Mesh):
    actor_update, critic_update = update_fns

    normalize_obs = bool(config.system.get("normalize_observations", False))
    num_minibatches = int(config.system.get("num_minibatches", 1))
    guard_mode = guards.resolve_mode(config)
    impala_loss = build_impala_loss(actor_apply, critic_apply, config)

    def per_shard(state: CoreLearnerState, traj: PPOTransition):
        traj, obs_stats = normalize_trajectory(traj, state.obs_stats, normalize_obs)
        traj = maybe_normalize_rewards(traj, config)

        def loss_fn(params: ActorCriticParams, mb: PPOTransition):
            return impala_loss(params.actor_params, params.critic_params, mb)

        @annotate("impala_minibatch")
        def _minibatch(carry, mb: PPOTransition):
            params, opt_states = carry
            # value_and_grad: the guard needs the total loss (DCE'd when the
            # guard is off — jax.grad is a value_and_grad that drops it).
            (total_loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb
            )
            grads, metrics = jax.lax.pmean((grads, metrics), axis_name="data")
            a_updates, a_opt = actor_update(grads.actor_params, opt_states.actor_opt_state)
            c_updates, c_opt = critic_update(grads.critic_params, opt_states.critic_opt_state)
            new_params = ActorCriticParams(
                optax.apply_updates(params.actor_params, a_updates),
                optax.apply_updates(params.critic_params, c_updates),
            )
            # Divergence guard (resilience/guards.py): shard-consistent
            # skip/halt of non-finite updates on the replicated params.
            (params, opt_states), guard_metrics = guards.guard_update(
                guard_mode,
                new=(new_params, ActorCriticOptStates(a_opt, c_opt)),
                old=(params, opt_states),
                loss=total_loss,
                grads=grads,
                opt_state=opt_states,
                axis_names=("data",),
            )
            return (params, opt_states), {**metrics, **guard_metrics}

        (params, opt_states), metrics = jax.lax.scan(
            _minibatch,
            (state.params, state.opt_states),
            split_env_minibatches(traj, num_minibatches),
        )
        # skipped_updates is a COUNT (summed on the host into the registry
        # counter); everything else reports as a per-minibatch mean.
        metrics = {
            k: (jnp.sum(v) if k == "skipped_updates" else jnp.mean(v))
            for k, v in metrics.items()
        }
        return CoreLearnerState(params, opt_states, state.key, obs_stats), metrics

    return shard_learn_step(per_shard, mesh, P(None, "data"))


def run_experiment(config: Any) -> float:
    def networks(config: Any, probe_envs: Any):
        return build_networks(config, probe_envs.num_actions, None, env=probe_envs)

    return runner.run_experiment(
        config, actor_critic_system(networks, get_impala_learn_step, OnPolicySource)
    )


def main() -> float:
    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_impala.yaml",
        sys.argv[1:],
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
