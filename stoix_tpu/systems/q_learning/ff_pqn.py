"""Anakin PQN (reference stoix/systems/q_learning/ff_pqn.py, 519 LoC):
buffer-free parallel Q-learning — epsilon-greedy rollouts, Q(lambda) targets
over the fresh trajectory (reference ff_pqn.py:114-118), epoch/minibatch SGD
like PPO. The reference pairs it with a LayerNorm MLP torso.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu import envs
from stoix_tpu.base_types import ExperimentOutput, OnPolicyLearnerState
from stoix_tpu.evaluator import get_distribution_act_fn
from stoix_tpu.ops import pick_along_last, q_lambda
from stoix_tpu.systems import anakin
from stoix_tpu.systems.q_learning.q_family import build_q_network
from stoix_tpu.systems.runner import AnakinSetup, run_anakin_experiment
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.jax_utils import tree_merge_leading_dims
from stoix_tpu.utils.training import make_learning_rate


class PQNStepCount(NamedTuple):
    """Dedicated gradient-step counter state, found by type (not by leaf-name
    pattern matching) so future optimizer-chain changes can't silently change
    the epsilon annealing rate."""

    count: jax.Array


def count_gradient_steps() -> optax.GradientTransformation:
    """Stateful no-op transform appended to the PQN chain: its PQNStepCount
    increments exactly once per gradient step."""

    def init(params):
        del params
        return PQNStepCount(jnp.zeros((), jnp.int32))

    def update(updates, state, params=None):
        del params
        return updates, PQNStepCount(state.count + 1)

    return optax.GradientTransformation(init, update)


def _find_step_count(opt_states) -> jax.Array:
    counts = [
        leaf.count
        for leaf in jax.tree.leaves(
            opt_states, is_leaf=lambda x: isinstance(x, PQNStepCount)
        )
        if isinstance(leaf, PQNStepCount)
    ]
    assert len(counts) == 1, "expected exactly one PQNStepCount in the optimizer chain"
    return counts[0]


def get_learner_fn(env, q_apply, q_update, config):
    gamma = float(config.system.gamma)
    lam = float(config.system.get("q_lambda", 0.65))
    train_eps = float(config.system.training_epsilon)
    # Reference PQN anneals epsilon 1.0 -> training_epsilon over
    # exploration_fraction of training (reference
    # configs/system/q_learning/ff_pqn.yaml decay_epsilon/exploration_fraction).
    # PQN is buffer-free, so progress is read off the dedicated step counter.
    decay = bool(config.system.get("decay_epsilon", False))
    explore_frac = float(config.system.get("exploration_fraction", 0.5))
    grad_steps_per_update = int(config.system.epochs) * int(config.system.num_minibatches)
    decay_updates = max(1.0, explore_frac * int(config.arch.num_updates))

    def _epsilon(opt_states):
        if not decay:
            return train_eps
        count = _find_step_count(opt_states)
        frac = jnp.minimum(
            count.astype(jnp.float32) / grad_steps_per_update / decay_updates, 1.0
        )
        return 1.0 + frac * (train_eps - 1.0)

    def _env_step(learner_state: OnPolicyLearnerState, _):
        params, opt_states, key, env_state, last_timestep = learner_state
        key, act_key = jax.random.split(key)
        dist = q_apply(params, last_timestep.observation, _epsilon(opt_states))
        action = dist.sample(seed=act_key)
        env_state, timestep = env.step(env_state, action)
        data = {
            "obs": last_timestep.observation,
            "action": action,
            "reward": timestep.reward,
            "discount": timestep.discount,
            "truncated": jnp.logical_and(timestep.last(), timestep.discount != 0.0),
            "next_obs": timestep.extras["next_obs"],
            "info": timestep.extras["episode_metrics"],
        }
        return OnPolicyLearnerState(params, opt_states, key, env_state, timestep), data

    def _update_step(learner_state: OnPolicyLearnerState, _):
        learner_state, traj = jax.lax.scan(
            _env_step, learner_state, None, int(config.system.rollout_length)
        )
        params, opt_states, key, env_state, last_timestep = learner_state

        # Q(lambda) targets over the fresh trajectory, time-major. q_next is
        # computed from the TRUE next obs, so forcing lambda_t = 0 at
        # truncations bootstraps from it instead of chaining the return across
        # the auto-reset boundary; terminations are cut by discount = 0.
        q_next = q_apply(params, traj["next_obs"], 0.0).preferences  # [T, E, A]
        lam_t = lam * (1.0 - traj["truncated"].astype(jnp.float32))
        targets = q_lambda(
            traj["reward"], gamma * traj["discount"], q_next, lam_t, batch_major=False
        )

        def _update_epoch(carry, _):
            params, opt_states, key = carry
            key, shuffle_key = jax.random.split(key)
            batch_size = targets.shape[0] * targets.shape[1]
            perm = jax.random.permutation(shuffle_key, batch_size)
            flat = tree_merge_leading_dims((traj["obs"], traj["action"], targets), 2)
            shuffled = jax.tree.map(lambda x: jnp.take(x, perm, axis=0), flat)
            minibatches = jax.tree.map(
                lambda x: x.reshape((int(config.system.num_minibatches), -1) + x.shape[1:]),
                shuffled,
            )

            def _update_minibatch(carry, batch):
                params, opt_states = carry
                obs, action, target = batch

                def loss_fn(p):
                    q = q_apply(p, obs, 0.0).preferences
                    qa = pick_along_last(q, action)
                    loss = 0.5 * jnp.mean((qa - target) ** 2)
                    return loss, {"q_loss": loss, "mean_q": jnp.mean(q)}

                grads, metrics = jax.grad(loss_fn, has_aux=True)(params)
                grads = jax.lax.pmean(grads, axis_name="batch")
                grads = jax.lax.pmean(grads, axis_name="data")
                updates, opt_states = q_update(grads, opt_states)
                params = optax.apply_updates(params, updates)
                return (params, opt_states), metrics

            (params, opt_states), metrics = jax.lax.scan(
                _update_minibatch, (params, opt_states), minibatches
            )
            return (params, opt_states, key), metrics

        (params, opt_states, key), loss_info = jax.lax.scan(
            _update_epoch, (params, opt_states, key), None, int(config.system.epochs)
        )
        learner_state = OnPolicyLearnerState(params, opt_states, key, env_state, last_timestep)
        return learner_state, (traj["info"], loss_info)

    def learner_fn(learner_state: OnPolicyLearnerState) -> ExperimentOutput:
        key = learner_state.key[0]
        state = learner_state._replace(key=key)
        state, (episode_info, loss_info) = jax.lax.scan(
            jax.vmap(_update_step, axis_name="batch"),
            state, None, int(config.arch.num_updates_per_eval),
        )
        state = state._replace(key=state.key[None])
        loss_info = jax.lax.pmean(loss_info, axis_name="data")
        return ExperimentOutput(state, episode_info, loss_info)

    return learner_fn


def learner_setup(env: envs.Environment, config: Any, mesh: Mesh, key: jax.Array) -> AnakinSetup:
    config.system.action_dim = env.num_actions
    q_network = build_q_network(config, env.num_actions)
    q_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.radam(make_learning_rate(float(config.system.q_lr), config,
                                       int(config.system.epochs),
                                       int(config.system.num_minibatches))),
        count_gradient_steps(),
    )

    key, net_key, env_key = jax.random.split(key, 3)
    dummy_obs = jax.tree.map(lambda x: x[None], env.observation_value())
    params = q_network.init(net_key, dummy_obs)
    opt_state = q_optim.init(params)

    update_batch = int(config.arch.get("update_batch_size", 1))
    state_specs = OnPolicyLearnerState(
        params=P(), opt_states=P(), key=P("data"),
        env_state=P(None, "data"), timestep=P(None, "data"),
    )
    env_state, timestep = anakin.reset_envs_for_anakin(env, config, env_key)
    learner_state = OnPolicyLearnerState(
        params=anakin.broadcast_to_update_batch(params, update_batch),
        opt_states=anakin.broadcast_to_update_batch(opt_state, update_batch),
        key=anakin.make_step_keys(key, mesh, config),
        env_state=env_state,
        timestep=timestep,
    )
    learner_state = anakin.place_learner_state(learner_state, mesh, state_specs)

    learn_per_shard = get_learner_fn(env, q_network.apply, q_optim.update, config)
    learn = anakin.shardmap_learner(learn_per_shard, mesh, state_specs)

    return AnakinSetup(
        learn=learn,
        learner_state=learner_state,
        eval_act_fn=get_distribution_act_fn(config, q_network.apply),
        eval_params_fn=lambda s: anakin.unbatch_params(s.params),
    )


def run_experiment(config: Any) -> float:
    return run_anakin_experiment(config, learner_setup)


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_pqn.yaml", sys.argv[1:]
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
