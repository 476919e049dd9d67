"""Anakin PPO (discrete) — THE canonical system template.

Behavioral parity: reference stoix/systems/ppo/anakin/ff_ppo.py (731 LoC) —
single-file layout with get_learner_fn / learner_setup / run_experiment /
entry point, truncation-aware GAE from per-step bootstrap values
(reference ff_ppo.py:96-179), epoch/minibatch SGD scans (:296-334), optional
observation normalization (:90-94,145-162).

TPU-native redesign (SURVEY.md §7.1):
  - ONE global `jax.sharding.Mesh` ("data" axis) replaces
    pmap(axis="device") + replicate/unreplicate. The learner step is written
    per-shard and wrapped in `jax.shard_map`; gradient sync is an explicit
    `lax.pmean` over ("batch", "data") riding ICI/DCN.
  - `arch.update_batch_size` (U) is an in-shard vmap with axis_name "batch"
    (reference's nested vmap, ff_ppo.py:361), params carrying a leading [U]
    axis that stays replicated across the mesh.
  - Bootstrap values for extras["next_obs"] are computed in ONE batched
    critic apply over the whole [T, E] rollout after the scan instead of per
    step — bigger matmuls for the MXU, identical math.
  - Learner state lives as global sharded arrays; checkpointing saves them
    directly; there is no unreplicate dance.

Layout (S = data shards, U = update batch, E = envs per (shard, batch)):
  params/opt_states:      [U, ...]        P()        (replicated)
  key:                    [S, U, 2]       P("data")
  env_state / timestep:   [U, S*E, ...]   P(None, "data")
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
    PPOTransition,
)
from stoix_tpu.evaluator import get_distribution_act_fn
from stoix_tpu.observability import SCOPES, annotate, get_logger, span
from stoix_tpu.ops import (
    losses,
    running_statistics,
    shuffled_minibatch_epoch,
    truncated_generalized_advantage_estimation,
)
from stoix_tpu.parallel import is_coordinator
from stoix_tpu.resilience import guards
from stoix_tpu.utils import compilecache, config as config_lib
from stoix_tpu.utils.jax_utils import count_parameters
from stoix_tpu.systems.runner import AnakinSetup, run_anakin_experiment
from stoix_tpu.utils.training import make_learning_rate


class PPOLearnerState(NamedTuple):
    """OnPolicyLearnerState + observation running statistics (the reference
    injects this field dynamically, ff_ppo.py:90-94; here it is explicit).

    `kl_beta` is the KL-penalty coefficient as TRAINED STATE: constant unless
    `system.adaptive_kl_beta` is set (PPO-penalty's adaptive-KL variant,
    Schulman et al. 2017 §4), in which case it doubles/halves around
    `system.kl_target` after every update step. Unused (zero) for clip/DPO."""

    params: Any
    opt_states: Any
    key: jax.Array
    env_state: Any
    timestep: Any
    obs_stats: Any
    kl_beta: Any


def get_learner_fn(
    env: envs.Environment,
    apply_fns: Tuple[Callable, Callable],
    update_fns: Tuple[Callable, Callable],
    config: Any,
    policy_loss_fn: Callable = None,
    hparams: Any = None,
) -> Callable[[PPOLearnerState], ExperimentOutput]:
    """Build the PER-SHARD learner function (wrapped in shard_map by setup).

    policy_loss_fn(dist, action, old_log_prob, gae, config, behavior_dist=...)
        -> (loss, entropy); behavior_dist is the pre-epoch policy re-applied
        on the same observations (analytic-KL penalties anchor to it)
    overrides the PPO clip objective (penalty/DPO variants).

    `hparams` (stoix_tpu/population, docs/DESIGN.md §2.11): a mapping of
    hyperparameter name -> scalar that OVERRIDES the config float. The plain
    path passes None and every value stays a trace-time Python float —
    byte-identical jaxprs. The population runner calls get_learner_fn inside
    its vmapped member function with per-member TRACED scalars, so one
    compiled program trains P members with different lr/ent_coef/gamma/...
    When `actor_lr`/`critic_lr` are present, `update_fns` must be built
    WITHOUT a learning rate (clip + scale_by_adam); the lr multiply happens
    here as `u * (-lr)` — bitwise the same multiply optax's scale(-lr) does.
    """

    actor_apply, critic_apply = apply_fns
    actor_update, critic_update = update_fns
    adaptive_kl = bool(config.system.get("adaptive_kl_beta", False))
    if adaptive_kl and not getattr(policy_loss_fn, "uses_kl_beta", False):
        # Fail fast: adapting beta for a loss that discards it (clip, DPO)
        # would log a "working" kl_beta while changing nothing.
        raise ValueError(
            "system.adaptive_kl_beta=true requires a policy loss that consumes "
            "kl_beta (the PPO-penalty loss); the configured loss does not."
        )
    hp = dict(hparams or {})
    gamma = hp.get("gamma", float(config.system.gamma))
    reward_scale = hp.get("reward_scale", float(config.system.get("reward_scale", 1.0)))
    gae_lambda = hp.get("gae_lambda", float(config.system.gae_lambda))
    clip_eps = hp.get("clip_eps", float(config.system.clip_eps))
    ent_coef = hp.get("ent_coef", float(config.system.ent_coef))
    vf_coef = hp.get("vf_coef", float(config.system.vf_coef))
    actor_lr = hp.get("actor_lr")  # None = update_fns already bake the lr
    critic_lr = hp.get("critic_lr")
    normalize_obs = bool(config.system.get("normalize_observations", False))
    guard_mode = guards.resolve_mode(config)
    # Hot-path compute knobs (docs/DESIGN.md §2.7): which scan kernel
    # evaluates the GAE recurrence, and whether actor+critic loss/grad/pmean
    # ride ONE fused gradient pass (2 collectives instead of 4) or the
    # reference's two independent passes (the bit-identical default).
    multistep_impl = str(config.system.get("multistep_impl", "scan"))
    fused_update = bool(config.system.get("fused_update", False))

    def _maybe_normalize(observation, obs_stats):
        if not normalize_obs:
            return observation
        return running_statistics.normalize_observation(observation, obs_stats)

    @annotate(SCOPES["rollout"])
    def _env_step(learner_state: PPOLearnerState, _: Any):
        params, opt_states, key = (
            learner_state.params, learner_state.opt_states, learner_state.key,
        )
        env_state, last_timestep = learner_state.env_state, learner_state.timestep
        obs_stats = learner_state.obs_stats
        key, policy_key = jax.random.split(key)

        with annotate(SCOPES["rollout_policy"]):
            observation = _maybe_normalize(last_timestep.observation, obs_stats)
            actor_policy = actor_apply(params.actor_params, observation)
            value = critic_apply(params.critic_params, observation)
            action = actor_policy.sample(seed=policy_key)
            log_prob = actor_policy.log_prob(action)

        with annotate(SCOPES["rollout_env"]):
            env_state, timestep = env.step(env_state, action)

        done = timestep.discount == 0.0
        truncated = jnp.logical_and(timestep.last(), timestep.discount != 0.0)
        transition = PPOTransition(
            done=done,
            truncated=truncated,
            action=action,
            value=value,
            reward=timestep.reward,
            log_prob=log_prob,
            obs=last_timestep.observation,  # RAW; normalized at use
            next_obs=timestep.extras["next_obs"],  # RAW; normalized at use
            info=timestep.extras["episode_metrics"],
        )
        return (
            learner_state._replace(key=key, env_state=env_state, timestep=timestep),
            transition,
        )

    def _actor_loss_fn(
        actor_params, behavior_actor_params, obs, action, old_log_prob, gae, kl_beta
    ):
        actor_policy = actor_apply(actor_params, obs)
        if policy_loss_fn is not None:
            # The behavior distribution (pre-epoch params on the SAME
            # normalized observations) backs analytic-KL penalties — the
            # reference's PPO-penalty recomputes it exactly this way
            # (reference ff_ppo_penalty.py:158).
            behavior_policy = actor_apply(behavior_actor_params, obs)
            loss_actor, entropy = policy_loss_fn(
                actor_policy, action, old_log_prob, gae, config,
                behavior_dist=behavior_policy, beta=kl_beta,
            )
        else:
            log_prob = actor_policy.log_prob(action)
            loss_actor = losses.ppo_clip_loss(log_prob, old_log_prob, gae, clip_eps)
            entropy = actor_policy.entropy().mean()
        total = loss_actor - ent_coef * entropy
        return total, (loss_actor, entropy)

    def _critic_loss_fn(critic_params, obs, targets, old_value):
        value = critic_apply(critic_params, obs)
        if config.system.get("clip_value", True):
            value_loss = losses.clipped_value_loss(value, old_value, targets, clip_eps)
        else:
            value_loss = jnp.mean((value - targets) ** 2)
        return vf_coef * value_loss, value_loss

    def _fused_loss_fn(
        joint_params, behavior_actor_params, obs, action, old_log_prob, gae,
        kl_beta, targets, old_value,
    ):
        """Joint actor+critic objective for the fused update: the two losses
        share no parameters, so d(total)/d(actor) == the actor grad and
        d(total)/d(critic) == the critic grad — the SAME gradients as the
        two-pass path, computed in one backward pass over one params tree."""
        actor_total, (loss_actor, entropy) = _actor_loss_fn(
            joint_params.actor_params, behavior_actor_params, obs, action,
            old_log_prob, gae, kl_beta,
        )
        critic_total, value_loss = _critic_loss_fn(
            joint_params.critic_params, obs, targets, old_value
        )
        return actor_total + critic_total, (loss_actor, entropy, value_loss)

    @annotate(SCOPES["update_minibatch"])
    def _update_minibatch(train_state: Tuple, batch_info: Tuple):
        params, opt_states, behavior_actor_params, kl_beta = train_state
        traj_batch, advantages, targets = batch_info

        if fused_update:
            # ONE backward pass + ONE pmean pair over the joint grads tree:
            # XLA sees a single all-reduce per axis for actor+critic together
            # instead of two, and the actor/critic backward graphs fuse.
            joint_grads, (loss_actor, entropy, value_loss) = jax.grad(
                _fused_loss_fn, has_aux=True
            )(
                params,
                behavior_actor_params,
                traj_batch.obs,
                traj_batch.action,
                traj_batch.log_prob,
                advantages,
                kl_beta,
                targets,
                traj_batch.value,
            )
            joint_grads = jax.lax.pmean(joint_grads, axis_name="batch")
            joint_grads = jax.lax.pmean(joint_grads, axis_name="data")
            actor_grads = joint_grads.actor_params
            critic_grads = joint_grads.critic_params
        else:
            actor_grad_fn = jax.grad(_actor_loss_fn, has_aux=True)
            actor_grads, (loss_actor, entropy) = actor_grad_fn(
                params.actor_params,
                behavior_actor_params,
                traj_batch.obs,
                traj_batch.action,
                traj_batch.log_prob,
                advantages,
                kl_beta,
            )
            critic_grad_fn = jax.grad(_critic_loss_fn, has_aux=True)
            critic_grads, value_loss = critic_grad_fn(
                params.critic_params, traj_batch.obs, targets, traj_batch.value
            )

            # Gradient sync: mean over the in-shard update-batch vmap axis,
            # then the mesh data axis (the latter rides ICI/DCN).
            actor_grads = jax.lax.pmean(actor_grads, axis_name="batch")
            actor_grads = jax.lax.pmean(actor_grads, axis_name="data")
            critic_grads = jax.lax.pmean(critic_grads, axis_name="batch")
            critic_grads = jax.lax.pmean(critic_grads, axis_name="data")

        actor_updates, actor_opt_state = actor_update(
            actor_grads, opt_states.actor_opt_state
        )
        if actor_lr is not None:
            # Threaded lr (population path): update_fns end at scale_by_adam,
            # so the update IS the adam direction; `u * (-lr)` is bitwise the
            # multiply optax's scale(-lr) performs inside adam(lr).
            actor_updates = jax.tree.map(lambda u: u * (-actor_lr), actor_updates)
        actor_params = optax.apply_updates(params.actor_params, actor_updates)
        critic_updates, critic_opt_state = critic_update(
            critic_grads, opt_states.critic_opt_state
        )
        if critic_lr is not None:
            critic_updates = jax.tree.map(lambda u: u * (-critic_lr), critic_updates)
        critic_params = optax.apply_updates(params.critic_params, critic_updates)

        # Divergence guard (resilience/guards.py): select the pre-update
        # (params, opt_states) when loss/grad-norm is non-finite. Zero added
        # ops and no extra metrics under the default update_guard=off.
        # Grads sync over BOTH ("batch", "data") above, so the [U] replicas
        # are bit-identical and the guard verdict must be too — a per-replica
        # decision would silently desync the replicated params forever.
        (params, opt_states), guard_metrics = guards.guard_update(
            guard_mode,
            new=(
                ActorCriticParams(actor_params, critic_params),
                ActorCriticOptStates(actor_opt_state, critic_opt_state),
            ),
            old=(params, opt_states),
            loss=loss_actor + value_loss,
            grads=(actor_grads, critic_grads),
            opt_state=opt_states,
            axis_names=("batch", "data"),
            metric_axes=("batch",),
        )

        loss_info = {
            "total_loss": loss_actor + value_loss,
            "actor_loss": loss_actor,
            "value_loss": value_loss,
            "entropy": entropy,
            **guard_metrics,
        }
        return (
            params,
            opt_states,
            behavior_actor_params,
            kl_beta,
        ), loss_info

    def _update_step(learner_state: PPOLearnerState, _: Any):
        learner_state, traj_batch = jax.lax.scan(
            _env_step, learner_state, None, int(config.system.rollout_length)
        )
        params, opt_states, key = (
            learner_state.params, learner_state.opt_states, learner_state.key,
        )
        env_state, last_timestep = learner_state.env_state, learner_state.timestep
        obs_stats, kl_beta = learner_state.obs_stats, learner_state.kl_beta

        # Trajectory obs are stored RAW; normalize them with the PRE-update
        # statistics (identical to what the rollout's log_probs/values used),
        # THEN fold the raw policy-consumed observations into the statistics
        # (psummed over the vmap + mesh axes so every replica stays in sync —
        # reference ff_ppo.py:145-162).
        raw_obs = traj_batch.obs
        traj_batch = traj_batch._replace(
            obs=_maybe_normalize(raw_obs, obs_stats),
            next_obs=_maybe_normalize(traj_batch.next_obs, obs_stats),
        )
        if normalize_obs:
            obs_stats = running_statistics.update(
                obs_stats,
                raw_obs.agent_view,
                axis_names=("batch", "data"),
                std_min_value=5e-4,
                std_max_value=5e4,
            )

        with annotate(SCOPES["gae"]):
            # ONE batched critic apply for all bootstrap values [T, E].
            v_t = critic_apply(params.critic_params, traj_batch.next_obs)

            d_t = gamma * (1.0 - traj_batch.done.astype(jnp.float32))
            advantages, targets = truncated_generalized_advantage_estimation(
                traj_batch.reward * reward_scale,
                d_t,
                gae_lambda,
                v_tm1=traj_batch.value,
                v_t=v_t,
                truncation_t=traj_batch.truncated.astype(jnp.float32),
                standardize_advantages=bool(
                    config.system.get("standardize_advantages", True)
                ),
                impl=multistep_impl,
            )

        # Behavior params (the rollout's) stay FIXED across all epochs: KL
        # penalties anchor to them, matching the reference's
        # behaviour_actor_params capture (reference ff_ppo_penalty.py:128).
        train_state = (params, opt_states, params.actor_params, kl_beta)
        # Shuffle across both time and envs: [T, E] -> T*E samples, packed
        # once here, permuted anew every epoch (ops/minibatch.py).
        minibatch_epoch = shuffled_minibatch_epoch(
            _update_minibatch,
            train_state,
            (traj_batch, advantages, targets),
            config.system.num_minibatches,
        )

        @annotate(SCOPES["update_epoch"])
        def _update_epoch(update_state: Tuple, _: Any):
            train_state, key = update_state
            key, shuffle_key = jax.random.split(key)
            train_state, loss_info = minibatch_epoch(train_state, shuffle_key)
            return (train_state, key), loss_info

        (train_state, key), loss_info = jax.lax.scan(
            _update_epoch, (train_state, key), None, int(config.system.epochs)
        )
        params, opt_states, behavior_actor_params, kl_beta = train_state

        if adaptive_kl:
            # Adaptive-KL PPO (Schulman et al. 2017 §4): after the full
            # update, measure the analytic KL(behavior ‖ new policy) over the
            # rollout batch and double/halve beta around `kl_target`. The KL
            # is pmeaned over the update-batch and mesh axes FIRST so the
            # replicated beta state stays bit-identical on every replica.
            kl_target = float(config.system.get("kl_target", 0.01))
            new_dist = actor_apply(params.actor_params, traj_batch.obs)
            behavior_dist = actor_apply(behavior_actor_params, traj_batch.obs)
            try:
                measured_kl = jnp.mean(behavior_dist.kl_divergence(new_dist))
            except NotImplementedError:
                log_ratio = jnp.clip(
                    new_dist.log_prob(traj_batch.action) - traj_batch.log_prob,
                    -losses._LOG_RATIO_CLAMP, losses._LOG_RATIO_CLAMP,
                )
                measured_kl = jnp.mean(jnp.exp(log_ratio) - 1.0 - log_ratio)
            measured_kl = jax.lax.pmean(measured_kl, axis_name="batch")
            measured_kl = jax.lax.pmean(measured_kl, axis_name="data")
            kl_beta = jnp.where(measured_kl > 1.5 * kl_target, kl_beta * 2.0, kl_beta)
            kl_beta = jnp.where(measured_kl < kl_target / 1.5, kl_beta / 2.0, kl_beta)
            kl_beta = jnp.clip(kl_beta, 1e-3, 1e3)
            loss_info = {**loss_info, "measured_kl": measured_kl, "kl_beta": kl_beta}

        learner_state = PPOLearnerState(
            params, opt_states, key, env_state, last_timestep, obs_stats, kl_beta
        )
        return learner_state, (traj_batch.info, loss_info)

    def learner_fn(learner_state: PPOLearnerState) -> ExperimentOutput:
        """Per-shard learner: scans vmapped update steps for one eval period."""
        key = learner_state.key[0]  # [S=1 slice, U, 2] -> [U, 2]
        state = learner_state._replace(key=key)

        batched_update_step = jax.vmap(_update_step, axis_name="batch")
        state, (episode_info, loss_info) = jax.lax.scan(
            batched_update_step, state, None, int(config.arch.num_updates_per_eval)
        )

        state = state._replace(key=state.key[None])  # restore [1, U, 2]
        # Losses are identical across shards post-pmean of grads only in
        # expectation; reduce them globally so P() outputs are truly replicated.
        loss_info = jax.lax.pmean(loss_info, axis_name="data")
        return ExperimentOutput(
            learner_state=state,
            episode_metrics=episode_info,
            train_metrics=loss_info,
        )

    return learner_fn


def build_networks(env: envs.Environment, config: Any):
    """Actor/critic network construction from the network config — shared by
    learner_setup and the population setup (stoix_tpu/population), which
    builds ONE network pair for all P members."""
    from stoix_tpu.systems import anakin
    from stoix_tpu.networks.base import FeedForwardActor, FeedForwardCritic

    net_cfg = config.network
    actor_network = FeedForwardActor(
        action_head=config_lib.instantiate(
            net_cfg.actor_network.action_head,
            **anakin.head_kwargs_for_env(net_cfg.actor_network.action_head, env),
        ),
        torso=config_lib.instantiate(net_cfg.actor_network.pre_torso),
        input_layer=config_lib.instantiate(net_cfg.actor_network.input_layer),
    )
    critic_network = FeedForwardCritic(
        critic_head=config_lib.instantiate(net_cfg.critic_network.critic_head),
        torso=config_lib.instantiate(net_cfg.critic_network.pre_torso),
        input_layer=config_lib.instantiate(net_cfg.critic_network.input_layer),
    )
    return actor_network, critic_network


def learner_setup(
    env: envs.Environment, config: Any, mesh: Mesh, keys: jax.Array,
    policy_loss_fn: Callable = None,
) -> AnakinSetup:
    """Instantiate networks/optimizers, build the shard_mapped learner, and
    initialise the (globally sharded) learner state."""

    from stoix_tpu.systems import anakin

    if "group" in mesh.axis_names:
        # ("group", "data") mesh: G gossip-averaged learner groups
        # (parallel/gossip.py, docs/DESIGN.md §2.12).
        return grouped_learner_setup(env, config, mesh, keys, policy_loss_fn)

    num_actions = env.num_actions
    config.system.action_dim = num_actions

    actor_network, critic_network = build_networks(env, config)

    actor_lr = make_learning_rate(
        float(config.system.actor_lr), config, int(config.system.epochs),
        int(config.system.num_minibatches),
    )
    critic_lr = make_learning_rate(
        float(config.system.critic_lr), config, int(config.system.epochs),
        int(config.system.num_minibatches),
    )
    actor_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(actor_lr, eps=1e-5),
    )
    critic_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(critic_lr, eps=1e-5),
    )

    apply_fns = (actor_network.apply, critic_network.apply)
    update_fns = (actor_optim.update, critic_optim.update)
    learn_per_shard = get_learner_fn(env, apply_fns, update_fns, config, policy_loss_fn)

    # ---- Global learner-state construction (shared anakin conventions) -----
    update_batch = int(config.arch.get("update_batch_size", 1))
    state_specs = PPOLearnerState(
        params=P(),
        opt_states=P(),
        key=P("data"),
        env_state=P(None, "data"),
        timestep=P(None, "data"),
        obs_stats=P(),
        kl_beta=P(),
    )

    def init_state(key: jax.Array) -> PPOLearnerState:
        """key -> the whole initial learner state, as one traceable function."""
        key, actor_key, critic_key, env_key = jax.random.split(key, 4)
        dummy_obs = jax.tree.map(lambda x: x[None], env.observation_value())
        actor_params = actor_network.init(actor_key, dummy_obs)
        critic_params = critic_network.init(critic_key, dummy_obs)
        env_state, timestep = anakin.reset_envs_for_anakin(env, config, env_key)
        obs_stats = running_statistics.init_state(env.observation_value().agent_view)
        return PPOLearnerState(
            params=anakin.broadcast_to_update_batch(
                ActorCriticParams(actor_params, critic_params), update_batch
            ),
            opt_states=anakin.broadcast_to_update_batch(
                ActorCriticOptStates(
                    actor_optim.init(actor_params), critic_optim.init(critic_params)
                ),
                update_batch,
            ),
            key=anakin.make_step_keys(key, mesh, config),
            env_state=env_state,
            timestep=timestep,
            obs_stats=anakin.broadcast_to_update_batch(obs_stats, update_batch),
            kl_beta=anakin.broadcast_to_update_batch(
                # 3.0 matches the penalty loss's historical default so a config
                # omitting kl_beta keeps the KL penalty ACTIVE (0.0 would
                # silently disable it). Unused state for clip/DPO losses.
                jnp.asarray(float(config.system.get("kl_beta", 3.0))), update_batch
            ),
        )

    # The runner times this whole function as set-up's `learner_setup`; the
    # span marks the state's build in a profiler trace. ONE jitted program
    # makes the state on its shardings: built op by op it was some 180 eager
    # compilations (14 s of the Ant cell's set-up on a v5e), none of them long
    # enough for the persistent cache to keep.
    with span("network_init"):
        learner_state = anakin.build_learner_state(init_state, keys, mesh, state_specs)
    learn = anakin.shardmap_learner(learn_per_shard, mesh, state_specs)

    if is_coordinator():
        programs, compilations = compilecache.compile_counts()
        get_logger("stoix_tpu.setup").info(
            "[setup] %s parameters | mesh %s | %s global envs | %d programs, %d compilations "
            "so far",
            f"{count_parameters(learner_state.params) // update_batch:,}", dict(mesh.shape),
            config.arch.total_num_envs, programs, compilations,
        )

    normalize_obs = bool(config.system.get("normalize_observations", False))
    if normalize_obs:
        # Eval params bundle the actor params with the current statistics.
        def eval_apply(bundle, observation):
            params, stats = bundle
            observation = running_statistics.normalize_observation(observation, stats)
            return actor_network.apply(params, observation)

        eval_act_fn = get_distribution_act_fn(config, eval_apply)
        eval_params_fn = lambda s: (
            jax.tree.map(lambda x: x[0], s.params.actor_params),
            jax.tree.map(lambda x: x[0], s.obs_stats),
        )
    else:
        eval_act_fn = get_distribution_act_fn(config, actor_network.apply)
        eval_params_fn = lambda s: jax.tree.map(lambda x: x[0], s.params.actor_params)

    setup = AnakinSetup(
        learn=learn,
        learner_state=learner_state,
        eval_act_fn=eval_act_fn,
        eval_params_fn=eval_params_fn,
    )
    return setup


def grouped_learner_setup(
    env: envs.Environment, config: Any, mesh: Mesh, keys: jax.Array,
    policy_loss_fn: Callable = None,
) -> AnakinSetup:
    """G gossip-averaged learner groups on a ("group", "data") mesh
    (parallel/gossip.py, docs/DESIGN.md §2.12; arxiv 1906.04585).

    Each group is the UNCHANGED per-shard learner: inside shard_map its
    `pmean(axis_name="data")` reduces within the group's data slice only, so
    the dense gradient all-reduce never crosses a group boundary. Groups all
    start from group 0's params/opt state (gossip-SGD averages replicas of
    ONE model — unlike population members, which are independent agents) but
    roll out on fold_in-separated env/step key streams, and the runner mixes
    the per-group parameter stacks with the jitted gossip step every
    `arch.gossip.interval` windows. Env counts are PER GROUP."""

    import os

    from stoix_tpu.parallel import gossip as gossip_lib
    from stoix_tpu.systems import anakin

    gossip_lib.validate_grouped_config(config, mesh)
    num_groups = int(mesh.shape["group"])

    num_actions = env.num_actions
    config.system.action_dim = num_actions

    actor_network, critic_network = build_networks(env, config)

    actor_lr = make_learning_rate(
        float(config.system.actor_lr), config, int(config.system.epochs),
        int(config.system.num_minibatches),
    )
    critic_lr = make_learning_rate(
        float(config.system.critic_lr), config, int(config.system.epochs),
        int(config.system.num_minibatches),
    )
    actor_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(actor_lr, eps=1e-5),
    )
    critic_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(critic_lr, eps=1e-5),
    )
    apply_fns = (actor_network.apply, critic_network.apply)
    update_fns = (actor_optim.update, critic_optim.update)

    update_batch = int(config.arch.get("update_batch_size", 1))
    dummy_obs = jax.tree.map(lambda x: x[None], env.observation_value())
    obs_stats0 = running_statistics.init_state(env.observation_value().agent_view)
    kl_beta0 = jnp.asarray(float(config.system.get("kl_beta", 3.0)))

    # Group 0's key path is EXACTLY learner_setup's (the single-group
    # bit-identity pin rides on it); groups g>0 fold_in(g) for their env and
    # step streams but share group 0's network init.
    shared_params = None
    shared_opt = None
    member_states = []
    for g in range(num_groups):
        member_key = keys if g == 0 else jax.random.fold_in(keys, g)
        key_g, actor_key, critic_key, env_key = jax.random.split(member_key, 4)
        if g == 0:
            actor_params = actor_network.init(actor_key, dummy_obs)
            critic_params = critic_network.init(critic_key, dummy_obs)
            shared_params = ActorCriticParams(actor_params, critic_params)
            shared_opt = ActorCriticOptStates(
                actor_optim.init(actor_params), critic_optim.init(critic_params)
            )
        env_state, timestep = anakin.reset_envs_for_anakin(env, config, env_key)
        member_states.append(
            PPOLearnerState(
                params=anakin.broadcast_to_update_batch(shared_params, update_batch),
                opt_states=anakin.broadcast_to_update_batch(shared_opt, update_batch),
                key=anakin.make_step_keys(key_g, mesh, config),
                env_state=env_state,
                timestep=timestep,
                obs_stats=anakin.broadcast_to_update_batch(obs_stats0, update_batch),
                kl_beta=anakin.broadcast_to_update_batch(kl_beta0, update_batch),
            )
        )
    grouped_state = jax.tree.map(lambda *xs: jnp.stack(xs), *member_states)

    grouped_specs = PPOLearnerState(
        params=P("group"),
        opt_states=P("group"),
        key=P("group", "data"),
        env_state=P("group", None, "data"),
        timestep=P("group", None, "data"),
        obs_stats=P("group"),
        kl_beta=P("group"),
    )
    grouped_state = anakin.place_learner_state(grouped_state, mesh, grouped_specs)

    learn_member = get_learner_fn(env, apply_fns, update_fns, config, policy_loss_fn)

    def per_shard_learn(state: PPOLearnerState) -> ExperimentOutput:
        # The stacked [G] axis is sharded 1:1 over the mesh's group axis, so
        # the local slice is always ONE group: squeeze -> the unchanged
        # ff_ppo learner -> unsqueeze. Reshapes only, which is why a single
        # group trains BIT-identically to plain ff_ppo.
        local = jax.tree.map(lambda x: x[0], state)
        out = learn_member(local)
        return jax.tree.map(lambda x: x[None], out)

    learn_sm = jax.shard_map(
        per_shard_learn,
        mesh=mesh,
        in_specs=(grouped_specs,),
        out_specs=ExperimentOutput(
            learner_state=grouped_specs,
            episode_metrics=P("group", None, None, None, "data"),
            train_metrics=P("group"),
        ),
        # Same Anakin opt-out as systems/anakin.py shardmap_learner: the
        # in-shard update-batch vmap's pmean trips check_vma's
        # varying-manual-axes assert.
        check_vma=False,
    )
    donate = {} if os.environ.get("STOIX_TPU_NO_DONATE") else {"donate_argnums": (0,)}
    learn = jax.jit(learn_sm, **donate)

    gossip_plan = gossip_lib.build_gossip_plan(config, mesh, state_specs=grouped_specs)

    if is_coordinator():
        n_params = count_parameters(shared_params.actor_params) + count_parameters(
            shared_params.critic_params
        )
        get_logger("stoix_tpu.setup").info(
            "[setup] %s parameters | mesh %s | %s envs/group | %d groups (%s, "
            "interval %s)",
            f"{n_params:,}", dict(mesh.shape), config.arch.total_num_envs,
            num_groups,
            gossip_plan.topology if gossip_plan else "lockstep",
            gossip_plan.interval if gossip_plan else "-",
        )

    # Evaluation serves group 0's replica-0 slice — the same values the
    # lockstep path's `x[0]` serves (post-gossip, group 0 already carries its
    # mixed parameters: the snapshot is taken AFTER the gossip dispatch).
    normalize_obs = bool(config.system.get("normalize_observations", False))
    if normalize_obs:

        def eval_apply(bundle, observation):
            params, stats = bundle
            observation = running_statistics.normalize_observation(observation, stats)
            return actor_network.apply(params, observation)

        eval_act_fn = get_distribution_act_fn(config, eval_apply)
        eval_params_fn = lambda s: (
            jax.tree.map(lambda x: x[0, 0], s.params.actor_params),
            jax.tree.map(lambda x: x[0, 0], s.obs_stats),
        )
    else:
        eval_act_fn = get_distribution_act_fn(config, actor_network.apply)
        eval_params_fn = lambda s: jax.tree.map(lambda x: x[0, 0], s.params.actor_params)

    return AnakinSetup(
        learn=learn,
        learner_state=grouped_state,
        eval_act_fn=eval_act_fn,
        eval_params_fn=eval_params_fn,
        gossip=gossip_plan,
    )


def run_experiment(config: Any) -> float:
    """Train Anakin PPO; returns the final evaluation episode-return mean."""
    return run_anakin_experiment(config, learner_setup)


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/anakin/default_ff_ppo.yaml",
        sys.argv[1:],
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
