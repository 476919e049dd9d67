"""Sebulba IMPALA with a shared torso (reference
stoix/systems/impala/sebulba/ff_impala_shared_torso.py, 1018 LoC): ONE network
with a PolicyValueHead serves both the policy and the value function
(reference uses a single net + PolicyValueHead). Implemented as two views over
the same module: the actor view returns the distribution, the critic view the
value; both views share parameters and the combined V-trace loss updates them
once through the actor optimizer (the critic optimizer sees an empty tree).
"""

from __future__ import annotations

import sys
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams, PPOTransition
from stoix_tpu.sebulba import runner
from stoix_tpu.sebulba.actor_critic import (
    CoreLearnerState,
    actor_critic_system,
    normalize_trajectory,
    shard_learn_step,
)
from stoix_tpu.sebulba.runner import LAST_RUN_STATS  # noqa: F401 — read through this module
from stoix_tpu.sebulba.sources import OnPolicySource
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
        traj, obs_stats = normalize_trajectory(traj, state.obs_stats, normalize_obs)
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

    return shard_learn_step(per_shard, mesh, P(None, "data"))


def run_experiment(config: Any) -> float:
    def networks(config: Any, probe_envs: Any):
        return build_shared_networks(config, probe_envs.num_actions, None)

    return runner.run_experiment(
        config, actor_critic_system(networks, get_shared_impala_learn_step, OnPolicySource)
    )


def main() -> float:
    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_impala_shared_torso.yaml",
        sys.argv[1:],
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
