"""Anakin MPO (reference stoix/systems/mpo/ff_mpo.py, 774 LoC / continuous
ff_mpo_continuous.py, 805 LoC).

Maximum a Posteriori Policy Optimization (Abdolmaleki et al. 2018):
  - trajectory replay buffer of sequences (reference ff_mpo.py:539)
  - Q-critic trained with Retrace targets (reference multistep.py:270)
  - E-step: nonparametric improved policy via temperature-weighted Q values
    (sampled actions for continuous; all actions for discrete), with a
    learnable temperature dual
  - M-step: weighted max-likelihood under decoupled KL trust regions with
    learnable alpha duals (reference mpo_types.py:23-31, continuous_loss.py)
  - target actor/critic networks, periodic/polyak updates.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from stoix_tpu import envs
from stoix_tpu.base_types import ExperimentOutput, OffPolicyLearnerState, OnlineAndTarget
from stoix_tpu.buffers import make_trajectory_buffer
from stoix_tpu.evaluator import get_distribution_act_fn
from stoix_tpu.ops import distributions as dists
from stoix_tpu.ops import retrace_continuous
from stoix_tpu.systems import anakin, off_policy_core as core
from stoix_tpu.systems.mpo.ff_vmpo import (
    decomposed_dists,
    decoupled_alpha_losses,
    gaussian_kls_per_dim,
    gaussian_params,
    init_log_duals,
    project_duals,
)
from stoix_tpu.systems.runner import AnakinSetup, run_anakin_experiment
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.jax_utils import tree_merge_leading_dims
from stoix_tpu.utils.training import make_learning_rate


class MPOParams(NamedTuple):
    actor_params: OnlineAndTarget
    q_params: OnlineAndTarget
    log_temperature: jax.Array
    log_alpha: jax.Array  # scalar (discrete) or [2] mean/std (continuous)


class MPOOptStates(NamedTuple):
    actor_opt_state: Any
    q_opt_state: Any
    dual_opt_state: Any


def _softplus(x):
    return jax.nn.softplus(x) + 1e-8


def get_learner_fn(env, networks, update_fns, buffer, config, continuous: bool):
    actor, q_network = networks
    actor_update, q_update, dual_update = update_fns
    gamma = float(config.system.gamma)
    tau = float(config.system.tau)
    num_samples = int(config.system.get("num_samples", 16))
    eps_eta = float(config.system.get("epsilon_eta", 0.1))
    eps_alpha = float(config.system.get("epsilon_alpha", 0.01))
    eps_alpha_mean = float(config.system.get("epsilon_alpha_mean", 0.0075))
    eps_alpha_stddev = float(config.system.get("epsilon_alpha_stddev", 1e-5))

    def _env_step(learner_state: OffPolicyLearnerState, _):
        params, opt_states, buffer_state, key, env_state, last_timestep = learner_state
        key, act_key = jax.random.split(key)
        dist = actor.apply(params.actor_params.online, last_timestep.observation)
        action = dist.sample(seed=act_key)
        log_prob = dist.log_prob(action)
        env_state, timestep = env.step(env_state, action)
        data = {
            "obs": last_timestep.observation,
            "action": action,
            "log_prob": log_prob,
            "reward": timestep.reward,
            "discount": timestep.discount,
            "info": timestep.extras["episode_metrics"],
        }
        return (
            OffPolicyLearnerState(params, opt_states, buffer_state, key, env_state, timestep),
            data,
        )

    def _q_value(q_params, obs, action):
        if continuous:
            return q_network.apply(q_params, obs, action)
        q_all = q_network.apply(q_params, obs, 0.0).preferences
        return dists.pick_along_last(q_all, action)

    def _critic_loss_fn(q_online, params: MPOParams, seq, key):
        # Retrace targets over the sampled sequences [B, L].
        obs = seq["obs"]
        target_dist = actor.apply(params.actor_params.target, obs)
        online_log_prob = target_dist.log_prob(seq["action"])
        log_rhos = online_log_prob - seq["log_prob"]

        # v_t: expected Q under the target policy at each state.
        if continuous:
            sample_keys = jax.random.split(key, num_samples)
            sampled = jax.vmap(lambda k: target_dist.sample(seed=k))(sample_keys)  # [N,B,L,A]
            q_sampled = jax.vmap(
                lambda a: _q_value(params.q_params.target, obs, a)
            )(sampled)  # [N,B,L]
            v_t = jnp.mean(q_sampled, axis=0)
        else:
            q_all = q_network.apply(params.q_params.target, obs, 0.0).preferences
            probs = dists.Categorical(target_dist.logits).probs
            v_t = jnp.sum(probs * q_all, axis=-1)

        q_tm1 = _q_value(q_online, obs, seq["action"])  # [B, L]
        q_t_target = _q_value(params.q_params.target, obs, seq["action"])

        errors = retrace_continuous(
            q_tm1[:, :-1],
            q_t_target[:, 1:-1],
            v_t[:, 1:],
            seq["reward"][:, :-1],
            gamma * seq["discount"][:, :-1],
            log_rhos[:, 1:-1],
            float(config.system.get("retrace_lambda", 0.95)),
        )
        loss = 0.5 * jnp.mean(errors**2)
        return loss, {"q_loss": loss, "mean_q": jnp.mean(q_tm1)}

    def _policy_loss_fn(learnable, params: MPOParams, seq, key):
        actor_online, log_temperature, log_alpha = learnable
        eta = _softplus(log_temperature)
        obs = jax.tree.map(lambda x: tree_merge_leading_dims(x, 2), seq["obs"])

        target_dist = actor.apply(params.actor_params.target, obs)
        online_dist = actor.apply(actor_online, obs)

        if continuous:
            sample_keys = jax.random.split(key, num_samples)
            actions = jax.vmap(lambda k: target_dist.sample(seed=k))(sample_keys)  # [N,B,A]
            q_vals = jax.vmap(lambda a: _q_value(params.q_params.target, obs, a))(actions)
            weights = jax.nn.softmax(q_vals / eta, axis=0)  # over samples
            temperature_loss = eta * eps_eta + eta * jnp.mean(
                jax.nn.logsumexp(q_vals / eta, axis=0) - jnp.log(float(num_samples))
            )
            # Decomposed M-step (reference continuous_loss.py:232-256): the
            # mean learns through a distribution borrowing the TARGET's
            # stddev, the stddev through one borrowing the TARGET's mean —
            # two cross-entropy losses instead of one.
            fixed_std, fixed_mean = decomposed_dists(target_dist, online_dist)
            lp_mean = jax.vmap(fixed_std.log_prob)(actions)  # [N,B]
            lp_std = jax.vmap(fixed_mean.log_prob)(actions)  # [N,B]
            w = jax.lax.stop_gradient(weights)
            policy_loss = -jnp.mean(jnp.sum(w * lp_mean, axis=0)) - jnp.mean(
                jnp.sum(w * lp_std, axis=0)
            )

            b_loc, b_scale = gaussian_params(target_dist)
            o_loc, o_scale = gaussian_params(online_dist)
            # Decoupled per-dimension mean/stddev KLs with per-dimension
            # alpha duals [2, A] (reference continuous_loss.py,
            # per_dim_constraining=True).
            kl_mean, kl_std = gaussian_kls_per_dim(b_loc, b_scale, o_loc, o_scale)
            alpha_loss, kl_loss, kl_metric = decoupled_alpha_losses(
                log_alpha, kl_mean, kl_std, eps_alpha_mean, eps_alpha_stddev
            )
        else:
            q_all = q_network.apply(params.q_params.target, obs, 0.0).preferences  # [B, A]
            prior_logits = dists.Categorical(target_dist.logits).logits
            # Nonparametric posterior weighted by the prior, in log space
            # (prior*exp(q/eta) overflows fp32 once eta shrinks below ~1).
            improved = jax.nn.softmax(q_all / eta + prior_logits, axis=-1)
            temperature_loss = eta * eps_eta + eta * jnp.mean(
                jax.nn.logsumexp(q_all / eta + prior_logits, axis=-1)
            )
            log_probs_all = online_dist.logits
            policy_loss = -jnp.mean(
                jnp.sum(jax.lax.stop_gradient(improved) * log_probs_all, axis=-1)
            )
            kl = jnp.mean(
                dists.Categorical(target_dist.logits).kl_divergence(online_dist)
            )
            alpha = _softplus(log_alpha)
            alpha_loss = jnp.sum(alpha * (eps_alpha - jax.lax.stop_gradient(kl)))
            kl_loss = jnp.sum(jax.lax.stop_gradient(alpha) * kl)
            kl_metric = kl

        total = policy_loss + temperature_loss + alpha_loss + kl_loss
        return total, {"policy_loss": policy_loss, "temperature": eta, "kl": kl_metric}

    def _update_epoch(carry, _):
        params, opt_states, buffer_state, key = carry
        key, sample_key, critic_key, policy_key = jax.random.split(key, 4)
        seq = buffer.sample(buffer_state, sample_key).experience  # [B, L, ...]

        q_grads, q_metrics = jax.grad(_critic_loss_fn, has_aux=True)(
            params.q_params.online, params, seq, critic_key
        )
        learnable = (params.actor_params.online, params.log_temperature, params.log_alpha)
        p_grads, p_metrics = jax.grad(_policy_loss_fn, has_aux=True)(
            learnable, params, seq, policy_key
        )
        q_grads, p_grads = jax.lax.pmean(
            jax.lax.pmean((q_grads, p_grads), axis_name="batch"), axis_name="data"
        )
        actor_grads, temp_grads, alpha_grads = p_grads

        q_updates, q_opt = q_update(q_grads, opt_states.q_opt_state)
        q_online = optax.apply_updates(params.q_params.online, q_updates)
        q_target = optax.incremental_update(q_online, params.q_params.target, tau)

        a_updates, a_opt = actor_update(actor_grads, opt_states.actor_opt_state)
        actor_online = optax.apply_updates(params.actor_params.online, a_updates)
        actor_target = optax.incremental_update(
            actor_online, params.actor_params.target, tau
        )

        d_updates, d_opt = dual_update(
            (temp_grads, alpha_grads), opt_states.dual_opt_state
        )
        log_temperature, log_alpha = optax.apply_updates(
            (params.log_temperature, params.log_alpha), d_updates
        )
        log_temperature, log_alpha = project_duals(log_temperature, log_alpha)

        params = MPOParams(
            OnlineAndTarget(actor_online, actor_target),
            OnlineAndTarget(q_online, q_target),
            log_temperature,
            log_alpha,
        )
        return (params, MPOOptStates(a_opt, q_opt, d_opt), buffer_state, key), {
            **q_metrics, **p_metrics,
        }

    def _update_step(learner_state: OffPolicyLearnerState, _):
        learner_state, traj = jax.lax.scan(
            _env_step, learner_state, None, int(config.system.rollout_length)
        )
        params, opt_states, buffer_state, key, env_state, timestep = learner_state
        store = {k: v for k, v in traj.items() if k != "info"}
        batch = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), store)  # [E, T, ...]
        buffer_state = buffer.add(buffer_state, batch)

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


def learner_setup(env: envs.Environment, config: Any, mesh: Mesh, key: jax.Array) -> AnakinSetup:
    from stoix_tpu.networks.base import FeedForwardActor, FeedForwardCritic

    config.system.action_dim = env.num_actions
    continuous = hasattr(env.action_space(), "low")
    net_cfg = config.network

    actor = FeedForwardActor(
        action_head=config_lib.instantiate(
            net_cfg.actor_network.action_head,
            **anakin.head_kwargs_for_env(net_cfg.actor_network.action_head, env),
        ),
        torso=config_lib.instantiate(net_cfg.actor_network.pre_torso),
        input_layer=config_lib.instantiate(net_cfg.actor_network.input_layer),
    )
    if continuous:
        q_network = FeedForwardCritic(
            critic_head=config_lib.instantiate(net_cfg.critic_network.critic_head),
            torso=config_lib.instantiate(net_cfg.critic_network.pre_torso),
            input_layer=config_lib.instantiate(net_cfg.critic_network.input_layer),
        )
    else:
        from stoix_tpu.networks.heads import DiscreteQNetworkHead

        q_network = FeedForwardActor(
            action_head=DiscreteQNetworkHead(action_dim=env.num_actions, epsilon=0.0),
            torso=config_lib.instantiate(net_cfg.critic_network.pre_torso),
            input_layer=config_lib.instantiate(
                net_cfg.actor_network.input_layer
            ),
        )

    actor_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(make_learning_rate(float(config.system.actor_lr), config,
                                      int(config.system.epochs)), eps=1e-5),
    )
    q_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(make_learning_rate(float(config.system.q_lr), config,
                                      int(config.system.epochs)), eps=1e-5),
    )
    dual_optim = optax.adam(float(config.system.get("dual_lr", 1e-2)))

    key, actor_key, q_key, env_key = jax.random.split(key, 4)
    dummy_obs = jax.tree.map(lambda x: x[None], env.observation_value())
    actor_p = actor.init(actor_key, dummy_obs)
    if continuous:
        dummy_act = jnp.asarray(env.action_value(), jnp.float32)[None]
        q_p = q_network.init(q_key, dummy_obs, dummy_act)
    else:
        q_p = q_network.init(q_key, dummy_obs)
    log_temperature, log_alpha = init_log_duals(config, continuous, int(env.num_actions))
    params = MPOParams(
        OnlineAndTarget(actor_p, actor_p), OnlineAndTarget(q_p, q_p),
        log_temperature, log_alpha,
    )
    opt_states = MPOOptStates(
        actor_optim.init(actor_p), q_optim.init(q_p),
        dual_optim.init((log_temperature, log_alpha)),
    )

    local_envs, sample_batch, max_length = core.trajectory_buffer_sizing(
        config, mesh, 2 * int(config.system.rollout_length)
    )
    buffer = make_trajectory_buffer(
        add_batch_size=local_envs,
        sample_batch_size=sample_batch,
        sample_sequence_length=int(config.system.get("sample_sequence_length", 8)),
        period=int(config.system.get("sample_period", 1)),
        max_length_time_axis=max_length,
    )
    dummy_item = {
        "obs": env.observation_value(),
        "action": jnp.asarray(
            env.action_value(), jnp.float32 if continuous else jnp.int32
        ),
        "log_prob": jnp.zeros((), jnp.float32),
        "reward": jnp.zeros((), jnp.float32),
        "discount": jnp.zeros((), jnp.float32),
    }
    buffer_state = buffer.init(dummy_item)

    learn_per_shard = get_learner_fn(
        env, (actor, q_network),
        (actor_optim.update, q_optim.update, dual_optim.update),
        buffer, config, continuous,
    )
    learner_state, state_specs = core.assemble_off_policy_state(
        config, mesh, env, params, opt_states, buffer_state, key, env_key
    )

    learn = core.wrap_learn(learn_per_shard, mesh, state_specs)

    return AnakinSetup(
        learn=learn,
        learner_state=learner_state,
        eval_act_fn=get_distribution_act_fn(config, actor.apply),
        eval_params_fn=lambda s: anakin.unbatch_params(s.params.actor_params.online),
    )


def run_experiment(config: Any) -> float:
    return run_anakin_experiment(config, learner_setup)


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_mpo.yaml", sys.argv[1:]
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
