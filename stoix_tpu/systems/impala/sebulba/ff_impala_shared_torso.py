"""Sebulba IMPALA with a shared torso (reference
stoix/systems/impala/sebulba/ff_impala_shared_torso.py, 1018 LoC): ONE network
with a PolicyValueHead serves both the policy and the value function
(reference uses a single net + PolicyValueHead). Implemented as two views over
the same module: the actor view returns the distribution, the critic view the
value; both views share parameters and the combined V-trace loss updates them
once through the actor optimizer (the critic optimizer sees an empty tree).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams, PPOTransition
from stoix_tpu.ops import running_statistics
from stoix_tpu.systems.ppo.sebulba.ff_ppo import CoreLearnerState, run_experiment as _run
from stoix_tpu.utils import config as config_lib


class _SharedView(nn.Module):
    """Callable view over a shared actor-critic module selecting one output."""

    net: nn.Module
    index: int

    @nn.compact
    def __call__(self, observation):
        return self.net(observation)[self.index]


def build_shared_networks(config: Any, num_actions: int, dummy_obs: Any):
    from stoix_tpu.networks.base import FeedForwardActorCritic
    from stoix_tpu.networks.heads import CategoricalHead, PolicyValueHead, ScalarCriticHead

    net_cfg = config.network
    shared = FeedForwardActorCritic(
        shared_head=PolicyValueHead(
            action_head=CategoricalHead(num_actions=num_actions),
            critic_head=ScalarCriticHead(),
        ),
        torso=config_lib.instantiate(net_cfg.actor_network.pre_torso),
        input_layer=config_lib.instantiate(net_cfg.actor_network.input_layer),
    )
    actor_view = _SharedView(net=shared, index=0)
    critic_view = _SharedView(net=shared, index=1)
    return actor_view, critic_view


def get_shared_impala_learn_step(actor_apply, critic_apply, update_fns, config, mesh: Mesh):
    """V-trace update through the shared parameters only (actor slot)."""
    from stoix_tpu.systems.impala.sebulba.ff_impala import (
        build_impala_loss,
        maybe_normalize_rewards,
        split_env_minibatches,
    )

    actor_update, _ = update_fns
    normalize_obs = bool(config.system.get("normalize_observations", False))
    num_minibatches = int(config.system.get("num_minibatches", 1))
    impala_loss = build_impala_loss(actor_apply, critic_apply, config)

    def per_shard(state: CoreLearnerState, traj: PPOTransition):
        # Match the actor path: observations the behavior policy consumed were
        # normalized with these (pre-update) statistics; fold the raw batch in
        # afterwards so the stats keep advancing.
        obs_stats = state.obs_stats
        if normalize_obs:
            raw_obs = traj.obs
            traj = traj._replace(
                obs=running_statistics.normalize_observation(traj.obs, obs_stats),
                next_obs=running_statistics.normalize_observation(traj.next_obs, obs_stats),
            )
            obs_stats = running_statistics.update(
                obs_stats, raw_obs.agent_view, axis_names=("data",),
                std_min_value=5e-4, std_max_value=5e4,
            )

        traj = maybe_normalize_rewards(traj, config)

        def loss_fn(shared_params, mb: PPOTransition):
            return impala_loss(shared_params, shared_params, mb)

        def _minibatch(carry, mb: PPOTransition):
            shared, a_opt = carry
            grads, metrics = jax.grad(loss_fn, has_aux=True)(shared, mb)
            grads, metrics = jax.lax.pmean((grads, metrics), axis_name="data")
            updates, a_opt = actor_update(grads, a_opt)
            return (optax.apply_updates(shared, updates), a_opt), metrics

        (shared, a_opt), metrics = jax.lax.scan(
            _minibatch,
            (state.params.actor_params, state.opt_states.actor_opt_state),
            split_env_minibatches(traj, num_minibatches),
        )
        metrics = jax.tree.map(jnp.mean, metrics)
        # Keep both param slots in sync (the rollout's critic view reads the
        # critic slot).
        params = ActorCriticParams(shared, shared)
        new_opts = ActorCriticOptStates(a_opt, state.opt_states.critic_opt_state)
        return CoreLearnerState(params, new_opts, state.key, obs_stats), metrics

    return jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(CoreLearnerState(P(), P(), P(), P()), P(None, "data")),
            out_specs=(CoreLearnerState(P(), P(), P(), P()), P()),
            # No in-shard vmap axis here, so the varying-manual-axes
            # validator runs (Anakin's pmean-over-vmap-axis limitation
            # does not apply — see systems/anakin.py).
            check_vma=True,
        )
    )


def run_experiment(config: Any) -> float:
    return _run(
        config,
        learn_step_builder=get_shared_impala_learn_step,
        networks_builder=build_shared_networks,
    )


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_impala_shared_torso.yaml",
        sys.argv[1:],
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
