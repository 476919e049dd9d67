"""Sebulba PPO (reference stoix/systems/ppo/sebulba/ff_ppo.py, 1046 LoC).

The PPO update over a learner-device mesh (`get_learn_step`) and its IMPACT
variant (`get_impact_learn_step`, `system.impact.enabled`), handed to the
Sebulba runner (`stoix_tpu/sebulba/runner.py`) with the actor-critic parts
every such system shares (`stoix_tpu/sebulba/actor_critic.py`: learner
state, networks, the actors' `act_fn`, the transition row). The host loop —
actor threads, batch source, parameter server, evaluator, supervision,
preemption — is the runner's.

The learner consumes GLOBAL arrays assembled with
jax.make_array_from_single_device_arrays (no host concat, no
device_put_sharded), and the update itself is jit+shard_map over the learner
mesh rather than pmap (SURVEY.md §7.1.3); `system.update_guard` guards the
gradient step against non-finite losses/grads.
"""

from __future__ import annotations

import functools
import sys
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams, PPOTransition
from stoix_tpu.observability import SCOPES, annotate
from stoix_tpu.ops import (
    losses,
    shuffled_minibatch_epoch,
    truncated_generalized_advantage_estimation,
)
from stoix_tpu.resilience import guards
from stoix_tpu.sebulba import runner
from stoix_tpu.sebulba.actor_critic import (  # noqa: F401 — the system's public names
    CoreLearnerState,
    actor_critic_system,
    build_networks as _build_networks,
    get_act_fn,
    normalize_trajectory,
    shard_learn_step,
)
from stoix_tpu.sebulba.runner import LAST_RUN_STATS  # noqa: F401 — read through this module
from stoix_tpu.sebulba.sources import (
    ImpactSource,
    OnPolicySource,
    impact_settings_from_config,
)
from stoix_tpu.utils import config as config_lib


def _build_learn_step(actor_apply, critic_apply, update_fns, config, mesh: Mesh, rho_clip):
    """jit+shard_map PPO update over the learner mesh; batch arrives as global
    arrays sharded on the env axis.

    With `rho_clip` it is the IMPACT variant (arXiv:1912.00167, docs/DESIGN.md
    §2.12): the update takes a THIRD input — the slow-moving target params
    (replicated; a host-refreshed alias of a recent online version) — and the
    actor objective becomes losses.impact_loss: the PPO clip taken against
    the target policy, importance-weighted by the clipped target/behavior
    ratio. `traj.log_prob` is the BEHAVIOR log-prob recorded by whichever
    (possibly stale) param version collected the trajectory, which is what
    makes re-stepping buffered batches sound. Everything else — GAE on the
    stored values, epoch/minibatch scan, value loss, pmean over "data",
    guards.guard_update — is the on-policy schedule unchanged."""
    actor_update, critic_update = update_fns
    impact = rho_clip is not None
    gamma = float(config.system.gamma)
    normalize_obs = bool(config.system.get("normalize_observations", False))
    guard_mode = guards.resolve_mode(config)

    def update(state: CoreLearnerState, target_params, traj: PPOTransition):
        traj, obs_stats = normalize_trajectory(traj, state.obs_stats, normalize_obs)
        with annotate(SCOPES["gae"]):
            v_t = critic_apply(state.params.critic_params, traj.next_obs)
            d_t = gamma * (1.0 - traj.done.astype(jnp.float32))
            advantages, targets = truncated_generalized_advantage_estimation(
                traj.reward, d_t, float(config.system.gae_lambda),
                v_tm1=traj.value, v_t=v_t,
                truncation_t=traj.truncated.astype(jnp.float32),
                standardize_advantages=bool(
                    config.system.get("standardize_advantages", True)
                ),
                impl=str(config.system.get("multistep_impl", "scan")),
            )

        @annotate("impact_minibatch" if impact else SCOPES["update_minibatch"])
        def _minibatch(carry, batch):
            params, opt_states = carry
            mb_traj, mb_adv, mb_tgt = batch

            def actor_loss_fn(p):
                dist = actor_apply(p, mb_traj.obs)
                log_prob = dist.log_prob(mb_traj.action)
                if impact:
                    # Target policy log-probs on the same (normalized) obs; no
                    # gradient flows into them (target_params is not `p`).
                    target_dist = actor_apply(target_params.actor_params, mb_traj.obs)
                    target_log_prob = target_dist.log_prob(mb_traj.action)
                    loss = losses.impact_loss(
                        log_prob, mb_traj.log_prob, target_log_prob, mb_adv,
                        float(config.system.clip_eps), rho_clip,
                    )
                else:
                    loss = losses.ppo_clip_loss(
                        log_prob, mb_traj.log_prob, mb_adv, float(config.system.clip_eps)
                    )
                entropy = dist.entropy().mean()
                return loss - float(config.system.ent_coef) * entropy, (loss, entropy)

            def critic_loss_fn(p):
                value = critic_apply(p, mb_traj.obs)
                loss = losses.clipped_value_loss(
                    value, mb_traj.value, mb_tgt, float(config.system.clip_eps)
                )
                return float(config.system.vf_coef) * loss, loss

            # value_and_grad: the divergence guard needs the total losses;
            # unused under update_guard=off, so XLA DCEs them (jax.grad is
            # itself a value_and_grad that drops the value).
            (a_total, (a_loss, entropy)), a_grads = jax.value_and_grad(
                actor_loss_fn, has_aux=True
            )(params.actor_params)
            (c_total, v_loss), c_grads = jax.value_and_grad(
                critic_loss_fn, has_aux=True
            )(params.critic_params)
            a_grads, c_grads = jax.lax.pmean((a_grads, c_grads), axis_name="data")
            a_updates, a_opt = actor_update(a_grads, opt_states.actor_opt_state)
            c_updates, c_opt = critic_update(c_grads, opt_states.critic_opt_state)
            new_params = ActorCriticParams(
                optax.apply_updates(params.actor_params, a_updates),
                optax.apply_updates(params.critic_params, c_updates),
            )
            # Divergence guard (resilience/guards.py): the per-shard loss is
            # pmean'ed over "data" inside the guard so every shard makes the
            # same keep/skip decision on the replicated params. It stays wired
            # on the stale-reuse path — a blown-up IS ratio meeting a stale
            # minibatch is exactly the non-finite-update class
            # system.update_guard exists for.
            (params, opt_states), guard_metrics = guards.guard_update(
                guard_mode,
                new=(new_params, ActorCriticOptStates(a_opt, c_opt)),
                old=(params, opt_states),
                loss=a_total + c_total,
                grads=(a_grads, c_grads),
                opt_state=opt_states,
                axis_names=("data",),
            )
            return (params, opt_states), {
                "actor_loss": a_loss, "value_loss": v_loss, "entropy": entropy,
                **guard_metrics,
            }

        minibatch_epoch = shuffled_minibatch_epoch(
            _minibatch,
            (state.params, state.opt_states),
            (traj, advantages, targets),
            config.system.num_minibatches,
        )

        @annotate("impact_epoch" if impact else SCOPES["update_epoch"])
        def _epoch(carry, _):
            params, opt_states, key = carry
            key, shuffle_key = jax.random.split(key)
            (params, opt_states), metrics = minibatch_epoch((params, opt_states), shuffle_key)
            return (params, opt_states, key), metrics

        (params, opt_states, key), metrics = jax.lax.scan(
            _epoch, (state.params, state.opt_states, state.key), None,
            int(config.system.epochs),
        )
        metrics = jax.lax.pmean(metrics, axis_name="data")
        return CoreLearnerState(params, opt_states, key, obs_stats), metrics

    if impact:
        def per_shard(state: CoreLearnerState, target_params, traj: PPOTransition):
            return update(state, target_params, traj)

        return shard_learn_step(per_shard, mesh, P(), P(None, "data"))

    def per_shard(state: CoreLearnerState, traj: PPOTransition):
        return update(state, None, traj)

    return shard_learn_step(per_shard, mesh, P(None, "data"))


def get_learn_step(actor_apply, critic_apply, update_fns, config, mesh: Mesh):
    return _build_learn_step(actor_apply, critic_apply, update_fns, config, mesh, None)


def get_impact_learn_step(
    actor_apply, critic_apply, update_fns, config, mesh: Mesh, rho_clip: float
):
    return _build_learn_step(actor_apply, critic_apply, update_fns, config, mesh, rho_clip)


def _networks(config: Any, probe_envs: Any):
    # `_build_networks` is looked up through the module at every call: the
    # benchmark's driver swaps the attribute to see the networks.
    return _build_networks(config, probe_envs.num_actions, None, env=probe_envs)


def run_experiment(config: Any, learn_step_builder: Callable = None) -> float:
    # IMPACT stale-trajectory reuse (docs/DESIGN.md §2.12): None (the
    # default) hands the runner the UNCHANGED on-policy objects — same
    # OnPolicySource, same get_learn_step trace.
    impact = impact_settings_from_config(config)
    if impact is None:
        builder, make_source = (learn_step_builder or get_learn_step), OnPolicySource
    elif learn_step_builder is not None:
        raise ValueError(
            "system.impact.enabled is incompatible with a custom "
            "learn_step_builder: the IMPACT update takes (state, "
            "target_params, batch), not (state, batch)"
        )
    else:
        builder = functools.partial(get_impact_learn_step, rho_clip=impact.rho_clip)
        make_source = functools.partial(ImpactSource, settings=impact)
    return runner.run_experiment(config, actor_critic_system(_networks, builder, make_source))


def main() -> float:
    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_ppo.yaml",
        sys.argv[1:],
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
