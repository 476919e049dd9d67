"""Anakin Rainbow (reference stoix/systems/q_learning/ff_rainbow.py, 676 LoC).

Distinctives preserved: prioritised trajectory buffer for n-step sequences
(reference ff_rainbow.py:433), noisy dueling distributional network
(reference dueling.py:90) driven by the "noise" rng stream, C51 projection
targets over n-step returns, importance-weighted loss + priority updates.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from stoix_tpu import envs
from stoix_tpu.base_types import ExperimentOutput, OffPolicyLearnerState, OnlineAndTarget
from stoix_tpu.buffers import make_prioritised_trajectory_buffer
from stoix_tpu.evaluator import get_distribution_act_fn
from stoix_tpu.ops import categorical_l2_project
from stoix_tpu.systems import anakin, off_policy_core as core
from stoix_tpu.systems.runner import AnakinSetup, run_anakin_experiment
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.training import make_learning_rate


def get_learner_fn(env, q_network, q_update, buffer, config):
    gamma = float(config.system.gamma)
    tau = float(config.system.tau)
    n_step = int(config.system.get("n_step", 3))
    importance_beta = float(config.system.get("importance_sampling_exponent", 0.6))

    def _env_step(learner_state: OffPolicyLearnerState, _):
        params, opt_states, buffer_state, key, env_state, last_timestep = learner_state
        key, act_key, noise_key = jax.random.split(key, 3)
        dist, _, _ = q_network.apply(
            params.online, last_timestep.observation, rngs={"noise": noise_key}
        )
        action = dist.sample(seed=act_key)
        env_state, timestep = env.step(env_state, action)
        data = {
            "obs": last_timestep.observation,
            "action": action,
            "reward": timestep.reward,
            "discount": timestep.discount,
            "next_obs": timestep.extras["next_obs"],
            "info": timestep.extras["episode_metrics"],
        }
        return (
            OffPolicyLearnerState(params, opt_states, buffer_state, key, env_state, timestep),
            data,
        )

    def _loss_fn(online_params, target_params, seq, probs, key):
        k1, k2, k3 = jax.random.split(key, 3)
        obs_0 = jax.tree.map(lambda x: x[:, 0], seq["obs"])
        action_0 = seq["action"][:, 0]
        # n-step discounted reward and terminal discount over the sequence.
        discounts = gamma * seq["discount"][:, :-1]  # [B, n]
        cum = jnp.cumprod(
            jnp.concatenate([jnp.ones_like(discounts[:, :1]), discounts[:, :-1]], axis=1),
            axis=1,
        )
        r_n = jnp.sum(cum * seq["reward"][:, :-1], axis=1)
        d_n = jnp.prod(discounts, axis=1)
        # Bootstrap state is s_n = obs of the LAST sequence element (rewards
        # and discounts above cover transitions 0..n-1 exactly).
        obs_n = jax.tree.map(lambda x: x[:, -1], seq["obs"])

        _, logits_0, atoms = q_network.apply(online_params, obs_0, rngs={"noise": k1})
        dist_sel, _, _ = q_network.apply(online_params, obs_n, rngs={"noise": k2})
        _, logits_n, _ = q_network.apply(target_params, obs_n, rngs={"noise": k3})
        best_a = jnp.argmax(dist_sel.preferences, axis=-1)

        num_atoms = atoms.shape[0]
        probs_best = jnp.take_along_axis(
            jax.nn.softmax(logits_n, axis=-1),
            best_a[:, None, None].repeat(num_atoms, -1), axis=-2,
        )[:, 0, :]
        target_z = r_n[:, None] + d_n[:, None] * atoms[None, :]
        target = jax.lax.stop_gradient(
            categorical_l2_project(target_z, probs_best, atoms)
        )
        logits_a = jnp.take_along_axis(
            logits_0, action_0[:, None, None].repeat(num_atoms, -1), axis=-2
        )[:, 0, :]
        ce = -jnp.sum(target * jax.nn.log_softmax(logits_a, axis=-1), axis=-1)  # [B]

        # Importance sampling weights (normalized to max 1).
        weights = (1.0 / jnp.maximum(probs, 1e-9)) ** importance_beta
        weights = weights / jnp.max(weights)
        loss = jnp.mean(weights * ce)
        return loss, (ce, {"q_loss": loss})

    def _update_epoch(carry, _):
        params, opt_states, buffer_state, key = carry
        key, sample_key, loss_key = jax.random.split(key, 3)
        sample = buffer.sample(buffer_state, sample_key)
        grads, (ce, loss_info) = jax.grad(_loss_fn, has_aux=True)(
            params.online, params.target, sample.experience, sample.probabilities, loss_key
        )
        grads = core.pmean_grads(grads)
        updates, opt_states = q_update(grads, opt_states)
        online = optax.apply_updates(params.online, updates)
        target = optax.incremental_update(online, params.target, tau)
        buffer_state = buffer.set_priorities(buffer_state, sample.indices, ce)
        return (OnlineAndTarget(online, target), opt_states, buffer_state, key), loss_info

    def _update_step(learner_state: OffPolicyLearnerState, _):
        learner_state, traj = jax.lax.scan(
            _env_step, learner_state, None, int(config.system.rollout_length)
        )
        params, opt_states, buffer_state, key, env_state, timestep = learner_state
        store = {k: v for k, v in traj.items() if k != "info"}
        buffer_state = buffer.add(
            buffer_state, jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), store)
        )
        (params, opt_states, buffer_state, key), loss_info = jax.lax.scan(
            _update_epoch, (params, opt_states, buffer_state, key), None,
            int(config.system.epochs),
        )
        learner_state = OffPolicyLearnerState(
            params, opt_states, buffer_state, key, env_state, timestep
        )
        return learner_state, (traj["info"], loss_info)

    def learner_fn(learner_state: OffPolicyLearnerState) -> ExperimentOutput:
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


def learner_setup(env: envs.Environment, config: Any, mesh: Mesh, key: jax.Array):
    from stoix_tpu.networks.base import FeedForwardActor

    config.system.action_dim = env.num_actions
    net_cfg = config.network.actor_network
    q_network = FeedForwardActor(
        action_head=config_lib.instantiate(
            net_cfg.action_head,
            action_dim=env.num_actions,
            epsilon=float(config.system.evaluation_epsilon),
            num_atoms=int(config.system.get("num_atoms", 51)),
            vmin=float(config.system.get("vmin", -10.0)),
            vmax=float(config.system.get("vmax", 10.0)),
        ),
        torso=config_lib.instantiate(net_cfg.pre_torso),
        input_layer=config_lib.instantiate(net_cfg.input_layer),
    )
    q_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(make_learning_rate(float(config.system.q_lr), config,
                                      int(config.system.epochs)), eps=1e-5),
    )

    key, net_key, env_key = jax.random.split(key, 3)
    dummy_obs = jax.tree.map(lambda x: x[None], env.observation_value())
    online = q_network.init({"params": net_key, "noise": net_key}, dummy_obs)
    params = OnlineAndTarget(online, online)
    opt_state = q_optim.init(online)

    n_step = int(config.system.get("n_step", 3))
    local_envs, sample_batch, max_length = core.trajectory_buffer_sizing(
        config, mesh, 2 * int(config.system.rollout_length)
    )
    buffer = make_prioritised_trajectory_buffer(
        add_batch_size=local_envs,
        sample_batch_size=sample_batch,
        sample_sequence_length=n_step + 1,
        period=1,
        max_length_time_axis=max_length,
        priority_exponent=float(config.system.get("priority_exponent", 0.6)),
    )
    dummy_item = {
        "obs": env.observation_value(),
        "action": jnp.zeros((), jnp.int32),
        "reward": jnp.zeros((), jnp.float32),
        "discount": jnp.zeros((), jnp.float32),
        "next_obs": env.observation_value(),
    }
    buffer_state = buffer.init(dummy_item)

    learn_per_shard = get_learner_fn(env, q_network, q_optim.update, buffer, config)
    learner_state, state_specs = core.assemble_off_policy_state(
        config, mesh, env, params, opt_state, buffer_state, key, env_key
    )

    learn = core.wrap_learn(learn_per_shard, mesh, state_specs)

    # Rainbow's warmup writes trajectory-layout sequences (not flat items).
    def traj_warmup(state):
        def _step(carry, _):
            env_state, timestep, key = carry
            key, act_key = jax.random.split(key)
            n_envs = timestep.reward.shape[0]
            action = jax.random.randint(act_key, (n_envs,), 0, env.num_actions)
            next_env_state, next_timestep = env.step(env_state, action)
            data = {
                "obs": timestep.observation,
                "action": action,
                "reward": next_timestep.reward,
                "discount": next_timestep.discount,
                "next_obs": next_timestep.extras["next_obs"],
            }
            return (next_env_state, next_timestep, key), data

        key, warmup_key = jax.random.split(state.key)
        (env_state, timestep, _), traj = jax.lax.scan(
            _step, (state.env_state, state.timestep, warmup_key), None,
            int(config.system.warmup_steps),
        )
        buffer_state = buffer.add(
            state.buffer_state, jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), traj)
        )
        return state._replace(
            buffer_state=buffer_state, key=key, env_state=env_state, timestep=timestep
        )

    def per_shard_warmup(state):
        squeezed = state._replace(
            buffer_state=jax.tree.map(lambda x: x[0], state.buffer_state),
            key=state.key[0],
        )
        out = jax.vmap(traj_warmup, axis_name="batch")(squeezed)
        return out._replace(
            buffer_state=jax.tree.map(lambda x: x[None], out.buffer_state),
            key=out.key[None],
        )

    warmup = jax.jit(
        jax.shard_map(
            per_shard_warmup, mesh=mesh, in_specs=(state_specs,),
            out_specs=state_specs, check_vma=False,
        )
    )

    def eval_apply(params, obs):
        dist, _, _ = q_network.apply(params, obs)
        return dist

    setup = AnakinSetup(
        learn=learn,
        learner_state=learner_state,
        eval_act_fn=get_distribution_act_fn(config, eval_apply),
        eval_params_fn=lambda s: anakin.unbatch_params(s.params.online),
    )
    return setup, warmup


def run_experiment(config: Any) -> float:
    holder = {}

    def setup_fn(env, cfg, mesh, key):
        setup, warmup = learner_setup(env, cfg, mesh, key)
        holder["warmup"] = warmup
        return setup

    return run_anakin_experiment(config, setup_fn, warmup_fn=lambda s: holder["warmup"](s))


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_rainbow.yaml", sys.argv[1:]
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
