"""Anakin Transformer-PPO — PPO with a causal attention context window.

The reference has no attention anywhere (SURVEY.md §5 long-context: RNN-only
sequence memory); this system is a TPU-native addition that makes the
transformer torso (networks/attention.py — Pallas flash attention on TPU) a
first-class policy: each env maintains a sliding window of its last W
observations, the actor/critic attend causally over the window and read the
final position, and the window clears at episode boundaries so attention
never crosses an auto-reset (generalized frame-stacking with attention in
place of concatenation).

Scaffolding (GAE, clip objective, epoch/minibatch scans, shard_map mesh
layout) mirrors the canonical ff_ppo template; transitions store the acting
window so training replays exactly what acting saw.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu import envs
from stoix_tpu.base_types import (
    ActorCriticOptStates,
    ActorCriticParams,
    ExperimentOutput,
)
from stoix_tpu.ops import losses, truncated_generalized_advantage_estimation
from stoix_tpu.systems import anakin
from stoix_tpu.systems.runner import AnakinSetup, run_anakin_experiment
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.jax_utils import tree_merge_leading_dims
from stoix_tpu.utils.training import make_learning_rate


class TransPPOLearnerState(NamedTuple):
    params: Any
    opt_states: Any
    key: jax.Array
    env_state: Any
    timestep: Any
    window: jax.Array  # [E, W, F] past-observation context (zeros = padding)


class TransPPOTransition(NamedTuple):
    done: jax.Array
    truncated: jax.Array
    action: jax.Array
    value: jax.Array
    reward: jax.Array
    log_prob: jax.Array
    window: jax.Array  # [E, W, F] context the policy actually saw
    next_obs: jax.Array  # [E, F] true successor obs (bootstrap; the successor
    # CONTEXT is derived at update time — storing it would duplicate the
    # window tensor)
    info: Any


def _push(window: jax.Array, obs: jax.Array) -> jax.Array:
    """Slide the window one step: drop the oldest frame, append `obs` last."""
    return jnp.concatenate([window[:, 1:], obs[:, None]], axis=1)


def get_learner_fn(
    env: envs.Environment,
    apply_fns: Tuple[Callable, Callable],
    update_fns: Tuple[Callable, Callable],
    config: Any,
) -> Callable[[TransPPOLearnerState], ExperimentOutput]:
    actor_apply, critic_apply = apply_fns
    actor_update, critic_update = update_fns
    gamma = float(config.system.gamma)

    def _flat(view: jax.Array) -> jax.Array:
        return view.reshape((view.shape[0], -1))  # [E, F] (pixels flattened)

    def _env_step(learner_state: TransPPOLearnerState, _: Any):
        params, opt_states, key, env_state, last_timestep, window = learner_state
        key, policy_key = jax.random.split(key)

        ctx = _push(window, _flat(last_timestep.observation.agent_view))  # [E, W, F]
        actor_policy = actor_apply(params.actor_params, ctx)
        value = critic_apply(params.critic_params, ctx)
        action = actor_policy.sample(seed=policy_key)
        log_prob = actor_policy.log_prob(action)

        env_state, timestep = env.step(env_state, action)
        done = timestep.discount == 0.0
        truncated = jnp.logical_and(timestep.last(), timestep.discount != 0.0)

        # Episode boundary: clear the context so attention never spans an
        # auto-reset.
        new_window = jnp.where(timestep.last()[:, None, None], 0.0, ctx)

        transition = TransPPOTransition(
            done=done,
            truncated=truncated,
            action=action,
            value=value,
            reward=timestep.reward,
            log_prob=log_prob,
            window=ctx,
            next_obs=_flat(timestep.extras["next_obs"].agent_view),
            info=timestep.extras["episode_metrics"],
        )
        return (
            TransPPOLearnerState(
                params, opt_states, key, env_state, timestep, new_window
            ),
            transition,
        )

    def _actor_loss_fn(actor_params, window, action, old_log_prob, gae):
        actor_policy = actor_apply(actor_params, window)
        log_prob = actor_policy.log_prob(action)
        loss_actor = losses.ppo_clip_loss(
            log_prob, old_log_prob, gae, float(config.system.clip_eps)
        )
        entropy = actor_policy.entropy().mean()
        total = loss_actor - float(config.system.ent_coef) * entropy
        return total, (loss_actor, entropy)

    def _critic_loss_fn(critic_params, window, targets, old_value):
        value = critic_apply(critic_params, window)
        if config.system.get("clip_value", True):
            value_loss = losses.clipped_value_loss(
                value, old_value, targets, float(config.system.clip_eps)
            )
        else:
            value_loss = jnp.mean((value - targets) ** 2)
        return float(config.system.vf_coef) * value_loss, value_loss

    def _update_minibatch(train_state: Tuple, batch_info: Tuple):
        params, opt_states = train_state
        traj_batch, advantages, targets = batch_info

        actor_grads, (loss_actor, entropy) = jax.grad(_actor_loss_fn, has_aux=True)(
            params.actor_params,
            traj_batch.window,
            traj_batch.action,
            traj_batch.log_prob,
            advantages,
        )
        critic_grads, value_loss = jax.grad(_critic_loss_fn, has_aux=True)(
            params.critic_params, traj_batch.window, targets, traj_batch.value
        )
        actor_grads, critic_grads = jax.lax.pmean(
            jax.lax.pmean((actor_grads, critic_grads), axis_name="batch"),
            axis_name="data",
        )
        actor_updates, actor_opt_state = actor_update(
            actor_grads, opt_states.actor_opt_state
        )
        critic_updates, critic_opt_state = critic_update(
            critic_grads, opt_states.critic_opt_state
        )
        params = ActorCriticParams(
            optax.apply_updates(params.actor_params, actor_updates),
            optax.apply_updates(params.critic_params, critic_updates),
        )
        loss_info = {
            "actor_loss": loss_actor,
            "value_loss": value_loss,
            "entropy": entropy,
        }
        return (params, ActorCriticOptStates(actor_opt_state, critic_opt_state)), loss_info

    def _update_epoch(update_state: Tuple, _: Any):
        params, opt_states, traj_batch, advantages, targets, key = update_state
        key, shuffle_key = jax.random.split(key)
        batch_size = advantages.shape[0] * advantages.shape[1]
        permutation = jax.random.permutation(shuffle_key, batch_size)
        flat = tree_merge_leading_dims((traj_batch, advantages, targets), 2)
        shuffled = jax.tree.map(lambda x: jnp.take(x, permutation, axis=0), flat)
        minibatches = jax.tree.map(
            lambda x: x.reshape(
                (int(config.system.num_minibatches), -1) + x.shape[1:]
            ),
            shuffled,
        )
        (params, opt_states), loss_info = jax.lax.scan(
            _update_minibatch, (params, opt_states), minibatches
        )
        return (params, opt_states, traj_batch, advantages, targets, key), loss_info

    def _update_step(learner_state: TransPPOLearnerState, _: Any):
        learner_state, traj_batch = jax.lax.scan(
            _env_step, learner_state, None, int(config.system.rollout_length)
        )
        params, opt_states, key, env_state, last_timestep, window = learner_state

        # Successor contexts for the bootstrap, derived in one shot from the
        # stored windows (true next obs pushed onto each acting context —
        # valid across truncation; terminal values die via discount 0), then
        # one batched critic apply.
        next_windows = jnp.concatenate(
            [traj_batch.window[:, :, 1:], traj_batch.next_obs[:, :, None]], axis=2
        )
        v_t = critic_apply(params.critic_params, next_windows)
        d_t = gamma * (1.0 - traj_batch.done.astype(jnp.float32))
        advantages, targets = truncated_generalized_advantage_estimation(
            traj_batch.reward,
            d_t,
            float(config.system.gae_lambda),
            v_tm1=traj_batch.value,
            v_t=v_t,
            truncation_t=traj_batch.truncated.astype(jnp.float32),
            standardize_advantages=bool(
                config.system.get("standardize_advantages", True)
            ),
        )

        update_state = (params, opt_states, traj_batch, advantages, targets, key)
        update_state, loss_info = jax.lax.scan(
            _update_epoch, update_state, None, int(config.system.epochs)
        )
        params, opt_states, _, _, _, key = update_state
        learner_state = TransPPOLearnerState(
            params, opt_states, key, env_state, last_timestep, window
        )
        return learner_state, (traj_batch.info, loss_info)

    def learner_fn(learner_state: TransPPOLearnerState) -> ExperimentOutput:
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
    import flax.linen as nn

    from stoix_tpu.networks import heads as heads_lib
    from stoix_tpu.networks.attention import TransformerTorso

    config.system.action_dim = env.num_actions
    num_actions = env.num_actions
    window = int(config.system.get("window_length", 16))
    num_layers = int(config.system.get("num_layers", 2))
    num_heads = int(config.system.get("num_heads", 4))
    head_dim = int(config.system.get("head_dim", 32))
    ffn_dim = int(config.system.get("ffn_dim", 256))

    def make_torso():
        return TransformerTorso(
            num_layers=num_layers,
            num_heads=num_heads,
            head_dim=head_dim,
            ffn_dim=ffn_dim,
            max_timesteps=window,
        )

    class WindowActor(nn.Module):
        @nn.compact
        def __call__(self, ctx):  # [..., W, F]
            x = make_torso()(ctx.reshape((-1,) + ctx.shape[-2:]))
            x = x[:, -1].reshape(ctx.shape[:-2] + (x.shape[-1],))
            return heads_lib.CategoricalHead(num_actions=num_actions)(x)

    class WindowCritic(nn.Module):
        @nn.compact
        def __call__(self, ctx):
            x = make_torso()(ctx.reshape((-1,) + ctx.shape[-2:]))
            x = x[:, -1].reshape(ctx.shape[:-2] + (x.shape[-1],))
            return heads_lib.ScalarCriticHead()(x)

    actor_network, critic_network = WindowActor(), WindowCritic()

    actor_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(make_learning_rate(float(config.system.actor_lr), config,
                                      int(config.system.epochs),
                                      int(config.system.num_minibatches)), eps=1e-5),
    )
    critic_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(make_learning_rate(float(config.system.critic_lr), config,
                                      int(config.system.epochs),
                                      int(config.system.num_minibatches)), eps=1e-5),
    )

    key, actor_key, critic_key, env_key = jax.random.split(key, 4)
    feat = int(env.observation_value().agent_view.reshape(-1).shape[0])
    dummy_ctx = jnp.zeros((1, window, feat))
    actor_params = actor_network.init(actor_key, dummy_ctx)
    critic_params = critic_network.init(critic_key, dummy_ctx)
    params = ActorCriticParams(actor_params, critic_params)
    opt_states = ActorCriticOptStates(
        actor_optim.init(actor_params), critic_optim.init(critic_params)
    )

    update_batch = int(config.arch.get("update_batch_size", 1))
    state_specs = TransPPOLearnerState(
        params=P(), opt_states=P(), key=P("data"),
        env_state=P(None, "data"), timestep=P(None, "data"),
        window=P(None, "data"),
    )
    env_state, timestep = anakin.reset_envs_for_anakin(env, config, env_key)
    envs_total = timestep.reward.shape[1]
    learner_state = TransPPOLearnerState(
        params=anakin.broadcast_to_update_batch(params, update_batch),
        opt_states=anakin.broadcast_to_update_batch(opt_states, update_batch),
        key=anakin.make_step_keys(key, mesh, config),
        env_state=env_state,
        timestep=timestep,
        window=jnp.zeros((update_batch, envs_total, window, feat)),
    )
    learner_state = anakin.place_learner_state(learner_state, mesh, state_specs)

    learn_per_shard = get_learner_fn(
        env, (actor_network.apply, critic_network.apply),
        (actor_optim.update, critic_optim.update), config,
    )
    learn = anakin.shardmap_learner(learn_per_shard, mesh, state_specs)

    # Evaluator: the context window plays the RNN evaluator's hidden-state
    # role — carried across eval steps, cleared on done (rnn_act_fn
    # signature, one episode at a time: run_experiment wires the carry evaluator).
    def window_act_fn(p, ctx_state, observation, done, act_key):
        flat = observation.agent_view.reshape(-1)[None]  # [1, F]
        ctx_state = jnp.where(jnp.asarray(done), 0.0, ctx_state)
        ctx_state = _push(ctx_state, flat)  # [1, W, F]
        dist = actor_network.apply(p, ctx_state)
        greedy = bool(config.arch.get("evaluation_greedy", False))
        action = dist.mode() if greedy else dist.sample(seed=act_key)
        return ctx_state, action[0]

    return AnakinSetup(
        learn=learn,
        learner_state=learner_state,
        eval_act_fn=window_act_fn,
        eval_params_fn=lambda s: anakin.unbatch_params(s.params.actor_params),
    )


def run_experiment(config: Any) -> float:
    from stoix_tpu.evaluator import per_episode_evaluator_setup

    window = int(config.system.get("window_length", 16))

    def evaluator_setup(eval_env, act_fn, cfg, mesh):
        feat = int(eval_env.observation_value().agent_view.reshape(-1).shape[0])
        setup = per_episode_evaluator_setup(lambda: jnp.zeros((1, window, feat)))
        return setup(eval_env, act_fn, cfg, mesh)

    return run_anakin_experiment(config, learner_setup, evaluator_setup_fn=evaluator_setup)


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/anakin/default_ff_trans_ppo.yaml",
        sys.argv[1:],
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
