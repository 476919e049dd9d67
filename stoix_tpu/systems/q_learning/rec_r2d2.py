"""Anakin R2D2 (reference stoix/systems/q_learning/rec_r2d2.py, 894 LoC — the
reference's largest Q-system).

Distinctives preserved: prioritised SEQUENCE replay with stored recurrent
states (reference :644), burn-in split to re-warm hidden states before the
training segment (:300-302), double-Q with a target network, transformed
n-step targets with the signed-hyperbolic pair (:18,:346-347),
importance-weighted loss + priority updates with the max/mean mix
eta (:364-374, buffer_set_priorities :413-416).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu import envs
from stoix_tpu.base_types import ExperimentOutput, OnlineAndTarget, RNNOffPolicyLearnerState
from stoix_tpu.buffers import make_prioritised_trajectory_buffer
from stoix_tpu.ops import SIGNED_HYPERBOLIC_PAIR, n_step_bootstrapped_returns, pick_along_last
from stoix_tpu.systems import anakin, off_policy_core as core
from stoix_tpu.systems.off_policy_core import pmean_grads
from stoix_tpu.systems.runner import AnakinSetup
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.training import make_learning_rate


def get_learner_fn(env, q_network, q_update, buffer, config, cell_type, hidden_size):
    from stoix_tpu.networks.base import ScannedRNN

    gamma = float(config.system.gamma)
    tau = float(config.system.tau)
    n_step = int(config.system.get("n_step", 5))
    burn_in = int(config.system.get("burn_in_length", 8))
    train_eps = float(config.system.training_epsilon)
    priority_eta = float(config.system.get("priority_eta", 0.9))
    importance_beta = float(config.system.get("importance_sampling_exponent", 0.6))
    tx = SIGNED_HYPERBOLIC_PAIR

    def _env_step(learner_state: RNNOffPolicyLearnerState, _):
        (params, opt_states, buffer_state, key, env_state, last_timestep,
         done, truncated, hstate) = learner_state
        key, act_key = jax.random.split(key)
        # Hidden state resets on done OR truncation, matching the flags the
        # training replay uses (a mismatch desynchronizes stored hstates).
        reset_flag = jnp.logical_or(done, truncated)
        obs_t = jax.tree.map(lambda x: x[None], last_timestep.observation)
        new_hstate, dist = q_network.apply(
            params.online, hstate, (obs_t, reset_flag[None]), train_eps
        )
        action = dist.sample(seed=act_key)[0]
        env_state, timestep = env.step(env_state, action)
        next_done = timestep.discount == 0.0
        next_trunc = jnp.logical_and(timestep.last(), timestep.discount != 0.0)
        data = {
            "obs": last_timestep.observation,
            "action": action,
            "reward": timestep.reward,
            "discount": timestep.discount,
            "done": reset_flag,  # reset flag ENTERING the step
            "hstate": jax.tree.map(lambda x: x, hstate),  # carry at step start
            "info": timestep.extras["episode_metrics"],
        }
        new_state = RNNOffPolicyLearnerState(
            params, opt_states, buffer_state, key, env_state, timestep,
            next_done, next_trunc, new_hstate,
        )
        return new_state, data

    def _loss_fn(online_params, target_params, seq, probs):
        # seq leaves [B, L, ...]; unroll time-major [L, B, ...].
        tm = lambda x: jnp.swapaxes(x, 0, 1)
        obs = jax.tree.map(tm, seq["obs"])
        dones = tm(seq["done"])
        init_h = jax.tree.map(lambda x: x[:, 0], seq["hstate"])  # [B, H]

        # Burn-in: warm both nets' carries without gradient.
        burn_obs = jax.tree.map(lambda x: x[:burn_in], obs)
        rest_obs = jax.tree.map(lambda x: x[burn_in:], obs)
        burn_dones, rest_dones = dones[:burn_in], dones[burn_in:]
        h_online, _ = q_network.apply(online_params, init_h, (burn_obs, burn_dones), 0.0)
        h_target, _ = q_network.apply(target_params, init_h, (burn_obs, burn_dones), 0.0)
        h_online = jax.lax.stop_gradient(h_online)
        h_target = jax.lax.stop_gradient(h_target)

        _, online_dist = q_network.apply(online_params, h_online, (rest_obs, rest_dones), 0.0)
        _, target_dist = q_network.apply(target_params, h_target, (rest_obs, rest_dones), 0.0)
        q_online = online_dist.preferences  # [L', B, A]
        q_target = target_dist.preferences

        action = tm(seq["action"])[burn_in:]
        reward = tm(seq["reward"])[burn_in:]
        discount = tm(seq["discount"])[burn_in:]

        # Transformed double n-step targets (selector = online argmax).
        selector = jnp.argmax(q_online, axis=-1)
        v_raw = tx.apply_inv(pick_along_last(q_target, selector))
        targets = n_step_bootstrapped_returns(
            reward[:-1].swapaxes(0, 1),
            (gamma * discount[:-1]).swapaxes(0, 1),
            v_raw[1:].swapaxes(0, 1),
            n=n_step,
        ).swapaxes(0, 1)
        targets = tx.apply(targets)

        qa = pick_along_last(q_online, action)[:-1]
        td = jax.lax.stop_gradient(targets) - qa  # [L'-1, B]

        # Sequence priorities: eta * max|td| + (1-eta) * mean|td|.
        abs_td = jnp.abs(td)
        new_priorities = priority_eta * jnp.max(abs_td, axis=0) + (
            1.0 - priority_eta
        ) * jnp.mean(abs_td, axis=0)

        weights = (1.0 / jnp.maximum(probs, 1e-9)) ** importance_beta
        weights = weights / jnp.max(weights)
        loss = jnp.mean(weights[None, :] * 0.5 * td**2)
        return loss, (new_priorities, {"q_loss": loss, "mean_q": jnp.mean(q_online)})

    def _update_epoch(carry, _):
        params, opt_states, buffer_state, key = carry
        key, sample_key = jax.random.split(key)
        sample = buffer.sample(buffer_state, sample_key)
        grads, (new_priorities, loss_info) = jax.grad(_loss_fn, has_aux=True)(
            params.online, params.target, sample.experience, sample.probabilities
        )
        grads = pmean_grads(grads)
        updates, opt_states = q_update(grads, opt_states)
        online = optax.apply_updates(params.online, updates)
        target = optax.incremental_update(online, params.target, tau)
        buffer_state = buffer.set_priorities(buffer_state, sample.indices, new_priorities)
        return (OnlineAndTarget(online, target), opt_states, buffer_state, key), loss_info

    def _update_step(learner_state: RNNOffPolicyLearnerState, _):
        learner_state, traj = jax.lax.scan(
            _env_step, learner_state, None, int(config.system.rollout_length)
        )
        (params, opt_states, buffer_state, key, env_state, timestep,
         done, truncated, hstate) = learner_state
        store = {k: v for k, v in traj.items() if k != "info"}
        buffer_state = buffer.add(
            buffer_state, jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), store)
        )
        (params, opt_states, buffer_state, key), loss_info = jax.lax.scan(
            _update_epoch, (params, opt_states, buffer_state, key), None,
            int(config.system.epochs),
        )
        learner_state = RNNOffPolicyLearnerState(
            params, opt_states, buffer_state, key, env_state, timestep,
            done, truncated, hstate,
        )
        return learner_state, (traj["info"], loss_info)

    def learner_fn(learner_state: RNNOffPolicyLearnerState) -> ExperimentOutput:
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


class RecurrentQNetwork:
    """pre_torso -> ScannedRNN -> epsilon-greedy Q head over sequences."""

    def __init__(self, config, num_actions, hidden_size, cell_type):
        from stoix_tpu.networks.base import RecurrentActor, ScannedRNN
        from stoix_tpu.networks.heads import DiscreteQNetworkHead

        net_cfg = config.network.actor_network
        self.module = RecurrentActor(
            action_head=DiscreteQNetworkHead(
                action_dim=num_actions,
                epsilon=float(config.system.evaluation_epsilon),
            ),
            rnn=ScannedRNN(hidden_size=hidden_size, cell_type=cell_type),
            pre_torso=config_lib.instantiate(net_cfg.pre_torso),
            post_torso=config_lib.instantiate(net_cfg.post_torso),
            input_layer=config_lib.instantiate(net_cfg.input_layer),
        )

    def init(self, key, hstate, inputs):
        return self.module.init(key, hstate, inputs)

    def apply(self, params, hstate, inputs, epsilon=0.0):
        # RecurrentActor passes head kwargs through observation mask path only;
        # epsilon is applied by rebuilding the distribution over preferences.
        hstate, dist = self.module.apply(params, hstate, inputs)
        from stoix_tpu.ops import EpsilonGreedy

        return hstate, EpsilonGreedy(dist.preferences, epsilon)


def learner_setup(env: envs.Environment, config: Any, mesh: Mesh, key: jax.Array) -> AnakinSetup:
    from stoix_tpu.networks.base import ScannedRNN

    config.system.action_dim = env.num_actions
    hidden_size = int(config.network.get("rnn_hidden_size", 128))
    cell_type = str(config.network.get("rnn_cell_type", "gru"))
    q_network = RecurrentQNetwork(config, env.num_actions, hidden_size, cell_type)

    q_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(make_learning_rate(float(config.system.q_lr), config,
                                      int(config.system.epochs)), eps=1e-5),
    )

    key, net_key, env_key = jax.random.split(key, 3)
    dummy_obs = jax.tree.map(lambda x: x[None, None], env.observation_value())
    dummy_done = jnp.zeros((1, 1), bool)
    dummy_h = ScannedRNN.initialize_carry(cell_type, hidden_size, (1,))
    online = q_network.init(net_key, dummy_h, (dummy_obs, dummy_done))
    params = OnlineAndTarget(online, online)
    opt_state = q_optim.init(online)

    n_shards = int(mesh.shape["data"])
    update_batch = int(config.arch.get("update_batch_size", 1))
    envs_axis = int(config.arch.total_num_envs) // update_batch
    seq_len = int(config.system.get("burn_in_length", 8)) + int(
        config.system.get("train_length", 8)
    )
    local_envs, sample_batch, max_length = core.trajectory_buffer_sizing(
        config, mesh, 2 * seq_len
    )
    buffer = make_prioritised_trajectory_buffer(
        add_batch_size=local_envs,
        sample_batch_size=sample_batch,
        sample_sequence_length=seq_len,
        period=int(config.system.get("period", 4)),
        max_length_time_axis=max_length,
        priority_exponent=float(config.system.get("priority_exponent", 0.6)),
    )
    dummy_item = {
        "obs": env.observation_value(),
        "action": jnp.zeros((), jnp.int32),
        "reward": jnp.zeros((), jnp.float32),
        "discount": jnp.zeros((), jnp.float32),
        "done": jnp.zeros((), bool),
        "hstate": jax.tree.map(
            lambda x: x[0], ScannedRNN.initialize_carry(cell_type, hidden_size, (1,))
        ),
    }
    buffer_state = buffer.init(dummy_item)

    state_specs = RNNOffPolicyLearnerState(
        params=P(), opt_states=P(), buffer_state=P("data"), key=P("data"),
        env_state=P(None, "data"), timestep=P(None, "data"),
        done=P(None, "data"), truncated=P(None, "data"), hstates=P(None, "data"),
    )
    env_state, timestep = anakin.reset_envs_for_anakin(env, config, env_key)
    learner_state = RNNOffPolicyLearnerState(
        params=anakin.broadcast_to_update_batch(params, update_batch),
        opt_states=anakin.broadcast_to_update_batch(opt_state, update_batch),
        buffer_state=jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_shards, update_batch) + x.shape), buffer_state
        ),
        key=anakin.make_step_keys(key, mesh, config),
        env_state=env_state,
        timestep=timestep,
        done=jnp.zeros((update_batch, envs_axis), bool),
        truncated=jnp.zeros((update_batch, envs_axis), bool),
        hstates=ScannedRNN.initialize_carry(cell_type, hidden_size, (update_batch, envs_axis)),
    )
    learner_state = anakin.place_learner_state(learner_state, mesh, state_specs)

    learn_per_shard = get_learner_fn(
        env, q_network, q_optim.update, buffer, config, cell_type, hidden_size
    )

    learn = core.wrap_learn(learn_per_shard, mesh, state_specs)

    def rnn_act_fn(params, hstate, observation, done, act_key):
        obs_t = jax.tree.map(lambda x: x[None, None], observation)
        done_t = jnp.asarray(done).reshape(1, 1)
        hstate, dist = q_network.apply(
            params, hstate, (obs_t, done_t), float(config.system.evaluation_epsilon)
        )
        greedy = bool(config.arch.get("evaluation_greedy", False))
        action = dist.mode() if greedy else dist.sample(seed=act_key)
        return hstate, action[0, 0]

    return AnakinSetup(
        learn=learn,
        learner_state=learner_state,
        eval_act_fn=rnn_act_fn,
        eval_params_fn=lambda s: anakin.unbatch_params(s.params.online),
    )


def run_experiment(config: Any) -> float:
    from stoix_tpu.systems.runner import run_rnn_anakin_experiment

    return run_rnn_anakin_experiment(config, learner_setup)


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_rec_r2d2.yaml", sys.argv[1:]
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
