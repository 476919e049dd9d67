"""What the Sebulba actor-critic systems share (PPO, IMPACT, both IMPALAs):
the learner state, the networks, the actors' inference program, one
transition row, and the record of them that `sebulba/runner.py` runs. A
system file adds its learn step, and the shared-torso IMPALA its networks."""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams, PPOTransition
from stoix_tpu.observability import SCOPES, annotate
from stoix_tpu.ops import running_statistics
from stoix_tpu.sebulba.runner import Learner, SebulbaSystem
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.training import make_learning_rate


class CoreLearnerState(NamedTuple):
    params: ActorCriticParams
    opt_states: ActorCriticOptStates
    key: jax.Array
    obs_stats: Any  # observation running statistics (updates gated by config)


def build_networks(config: Any, num_actions: int, obs_value: Any, env: Any = None):
    from stoix_tpu.networks.base import FeedForwardActor, FeedForwardCritic

    net_cfg = config.network
    if env is not None:
        # Infer head kwargs from the action space (discrete num_actions or
        # continuous action_dim/minimum/maximum), like the Anakin systems.
        from stoix_tpu.systems.anakin import head_kwargs_for_env

        head_kwargs = head_kwargs_for_env(net_cfg.actor_network.action_head, env)
    else:
        head_kwargs = {"num_actions": num_actions}
    actor = FeedForwardActor(
        action_head=config_lib.instantiate(
            net_cfg.actor_network.action_head, **head_kwargs
        ),
        torso=config_lib.instantiate(net_cfg.actor_network.pre_torso),
        input_layer=config_lib.instantiate(net_cfg.actor_network.input_layer),
    )
    critic = FeedForwardCritic(
        critic_head=config_lib.instantiate(net_cfg.critic_network.critic_head),
        torso=config_lib.instantiate(net_cfg.critic_network.pre_torso),
        input_layer=config_lib.instantiate(net_cfg.critic_network.input_layer),
    )
    return actor, critic


def get_act_fn(actor_apply, critic_apply, normalize_obs: bool):
    """The actors' per-step inference program (`jit_act_fn` in a device
    trace), all of it under the `rollout_policy` scope."""

    @jax.jit
    @annotate(SCOPES["rollout_policy"])
    def act_fn(bundle, observation, key):
        params, obs_stats = bundle
        if normalize_obs:
            observation = running_statistics.normalize_observation(observation, obs_stats)
        dist = actor_apply(params.actor_params, observation)
        value = critic_apply(params.critic_params, observation)
        action = dist.sample(seed=key)
        return action, dist.log_prob(action), value

    return act_fn


def transition(obs: Any, act_out: Any, next_timestep: Any) -> PPOTransition:
    """Row t of a rollout. The operators keep what a host pool returns on the
    host (numpy) and a JAX twin's arrays on their device."""
    action, log_prob, value = act_out
    return PPOTransition(
        done=next_timestep.discount == 0.0,
        truncated=next_timestep.last() & (next_timestep.discount != 0.0),
        action=action,
        value=value,
        reward=next_timestep.reward,
        log_prob=log_prob,
        obs=obs,
        next_obs=next_timestep.extras["next_obs"],
        info=next_timestep.extras["episode_metrics"],
    )


def normalize_trajectory(traj: PPOTransition, obs_stats: Any, normalize_obs: bool):
    """`(traj, obs_stats)` for a learn step's shard. Actors already acted on
    observations normalized with these (pre-update) statistics; normalize the
    stored RAW obs identically, then fold the raw batch into the statistics
    (psum over the mesh axis) so that they keep advancing."""
    if not normalize_obs:
        return traj, obs_stats
    raw_obs = traj.obs
    traj = traj._replace(
        obs=running_statistics.normalize_observation(raw_obs, obs_stats),
        next_obs=running_statistics.normalize_observation(traj.next_obs, obs_stats),
    )
    obs_stats = running_statistics.update(
        obs_stats, raw_obs.agent_view, axis_names=("data",),
        std_min_value=5e-4, std_max_value=5e4,
    )
    return traj, obs_stats


def shard_learn_step(per_shard: Callable, mesh: Any, *operand_specs: P):
    """`per_shard(state, *operands) -> (state, metrics)` as one jitted
    shard_map program over the learner mesh: the `CoreLearnerState` and the
    metrics replicated, each operand placed as its spec says."""
    state_spec = CoreLearnerState(P(), P(), P(), P())
    return jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(state_spec, *operand_specs),
            out_specs=(state_spec, P()),
            # No in-shard vmap axis here, so the varying-manual-axes
            # validator runs (Anakin's pmean-over-vmap-axis limitation
            # does not apply — see systems/anakin.py).
            check_vma=True,
        )
    )


def _adam(learning_rate: float, config: Any) -> optax.GradientTransformation:
    schedule = make_learning_rate(
        learning_rate, config, int(config.system.epochs), int(config.system.num_minibatches)
    )
    return optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(schedule, eps=1e-5),
    )


def actor_critic_system(
    networks_builder: Callable, learn_step_builder: Callable, make_source: Callable
) -> SebulbaSystem:
    """`networks_builder(config, probe_envs)` -> actor and critic modules;
    `learn_step_builder(actor_apply, critic_apply, update_fns, config, mesh)`
    -> the jitted update over `CoreLearnerState`; `make_source(ctx)` -> the
    batch source that feeds it."""

    # Networks and learner state are each the output of ONE jitted program:
    # built op by op they were some 90 eager compilations, none of them long
    # enough for the persistent cache to keep.
    def init_networks(config: Any, probe_envs: Any, key: jax.Array):
        actor, critic = networks_builder(config, probe_envs)
        # On the host: a JAX twin's observation is committed to its CPU device,
        # and the init would follow it there.
        obs0 = jax.tree.map(np.asarray, probe_envs.reset(seed=0).observation)

        @jax.jit
        def init_params(key: jax.Array, obs0: Any):
            key, a_key, c_key = jax.random.split(key, 3)
            return ActorCriticParams(actor.init(a_key, obs0), critic.init(c_key, obs0)), key

        params, key = init_params(key, obs0)
        return (actor, critic, params, obs0), key

    def setup_learner(config: Any, networks: Any, key: jax.Array, learner_mesh: Any):
        actor, critic, params, obs0 = networks
        actor_optim = _adam(float(config.system.actor_lr), config)
        critic_optim = _adam(float(config.system.critic_lr), config)
        # Eager: the key handed back stays where the runner's own splits are.
        key, learn_key = jax.random.split(key)

        def init_state(params: ActorCriticParams, learn_key: jax.Array) -> CoreLearnerState:
            opt_states = ActorCriticOptStates(
                actor_optim.init(params.actor_params), critic_optim.init(params.critic_params)
            )
            obs0_single = jax.tree.map(lambda x: x[0], obs0.agent_view)
            obs_stats = running_statistics.init_state(obs0_single)
            return CoreLearnerState(params, opt_states, learn_key, obs_stats)

        state = jax.jit(init_state, out_shardings=NamedSharding(learner_mesh, P()))(
            params, learn_key
        )
        learn_step = learn_step_builder(
            actor.apply, critic.apply, (actor_optim.update, critic_optim.update),
            config, learner_mesh,
        )
        normalize_obs = bool(config.system.get("normalize_observations", False))

        def eval_apply(payload, observation):
            if normalize_obs:
                p, stats = payload
                observation = running_statistics.normalize_observation(observation, stats)
                return actor.apply(p, observation)
            return actor.apply(payload, observation)

        def eval_params(state: CoreLearnerState):
            if normalize_obs:
                return state.params.actor_params, state.obs_stats
            return state.params.actor_params

        learner = Learner(
            state=state,
            step=learn_step,
            make_source=make_source,
            make_act_fn=functools.partial(get_act_fn, actor.apply, critic.apply, normalize_obs),
            transition=transition,
            actor_params=lambda state: (state.params, state.obs_stats),
            eval_params=eval_params,
            eval_apply=eval_apply,
        )
        return learner, key

    return SebulbaSystem(init_networks, setup_learner)
