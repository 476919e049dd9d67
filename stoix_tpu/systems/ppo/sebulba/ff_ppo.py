"""Sebulba PPO (reference stoix/systems/ppo/sebulba/ff_ppo.py, 1046 LoC).

Actor/learner disaggregation for non-pure-JAX environments: actor THREADS pin
jitted inference to actor devices and step stateful envs (EnvPool/C++/JAX
adapters behind the EnvFactory seam); trajectories flow through bounded queues
(OnPolicyPipeline) to a learner thread running the PPO update over a learner-
device mesh; fresh params return via the ParameterServer; evaluation runs
asynchronously on its own device.

TPU-native differences from the reference (SURVEY.md §7.1.3):
  - the learner consumes GLOBAL arrays assembled with
    jax.make_array_from_single_device_arrays (no host concat, no
    device_put_sharded), and the update itself is jit+shard_map over the
    learner mesh rather than pmap.
  - actor->learner backpressure (queue maxsize=1) and the skip-fetch-on-first-
    rollout pipelining (reference :202-214) are preserved.

Fault tolerance (stoix_tpu/resilience, docs/DESIGN.md §2.3): actor threads
are owned by an ActorSupervisor (crash -> bounded-backoff restart with a
fresh env and re-primed params; budget exhausted or heartbeat wedge -> typed
ComponentFailure poison-pill so the learner fails fast), SIGTERM/SIGINT stop
the learner loop at the next update boundary, and `system.update_guard`
guards the gradient step against non-finite losses/grads.
"""

from __future__ import annotations

import contextlib
import os
import queue
import sys
import threading
import time
from typing import Any, Callable, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams, PPOTransition
from stoix_tpu.envs.factory import make_factory
from stoix_tpu.evaluator import get_distribution_act_fn, get_ff_evaluator_fn
from stoix_tpu.observability import (
    SCOPES,
    RunStats,
    SetupClock,
    annotate,
    flightrec,
    get_health_monitor,
    get_logger,
    get_registry,
    get_status_board,
    goodput,
    span,
)
from stoix_tpu.ops import (
    losses,
    running_statistics,
    scan_kernels,
    shuffled_minibatch_epoch,
    truncated_generalized_advantage_estimation,
)
from stoix_tpu.parallel import MeshRoles, assemble_global_array
from stoix_tpu.resilience import (
    PreemptionHandler,
    faultinject,
    fleet,
    guards,
    integrity,
    preflight,
    supervisor_from_config,
)
from stoix_tpu.resilience.errors import ComponentFailure, EvaluatorStallError
from stoix_tpu.sebulba.core import (
    AsyncEvaluator,
    OffPolicyPipeline,
    OnPolicyPipeline,
    ParameterServer,
    ThreadLifetime,
)
from stoix_tpu.sebulba.rollout_storage import RolloutStorage, host_copy
from stoix_tpu.utils import compilecache
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.logger import LogEvent, StoixLogger
from stoix_tpu.utils.timing import StepAccumulator, TimingTracker
from stoix_tpu.utils.training import make_learning_rate

# Throughput stats of the most recent run_experiment call in this process
# (steady-state window: after the first eval block, i.e. post-compile).
# Read by bench.py --sebulba; dict-compatible (RunStats) so callers can
# ignore it entirely. The underlying series live in the metrics registry
# (stoix_tpu_sebulba_*).
LAST_RUN_STATS = RunStats()


class CoreLearnerState(NamedTuple):
    params: ActorCriticParams
    opt_states: ActorCriticOptStates
    key: jax.Array
    obs_stats: Any  # observation running statistics (updates gated by config)


class ImpactSettings(NamedTuple):
    """Validated `system.impact` knobs (IMPACT stale-trajectory reuse,
    arXiv:1912.00167; docs/DESIGN.md §2.12)."""

    target_update_interval: int
    rho_clip: float
    max_staleness: int
    max_reuse: int
    buffer_size: int


def impact_settings_from_config(config: Any) -> "ImpactSettings | None":
    """None unless system.impact.enabled — the disabled path constructs the
    unchanged on-policy objects (OnPolicyPipeline + get_learn_step)."""
    raw = dict(config.system.get("impact") or {})
    if not bool(raw.get("enabled", False)):
        return None
    settings = ImpactSettings(
        target_update_interval=int(raw.get("target_update_interval", 4)),
        rho_clip=float(raw.get("rho_clip", 2.0)),
        max_staleness=int(raw.get("max_staleness", 4)),
        max_reuse=int(raw.get("max_reuse", 2)),
        buffer_size=int(raw.get("buffer_size", 4)),
    )
    if settings.target_update_interval < 1:
        raise ValueError(
            "system.impact.target_update_interval must be >= 1 "
            f"(got {settings.target_update_interval})"
        )
    if settings.rho_clip < 1.0:
        raise ValueError(
            "system.impact.rho_clip must be >= 1.0 — clipping the IS ratio "
            f"below 1 would down-weight FRESH data (got {settings.rho_clip})"
        )
    if settings.max_staleness < 1 or settings.max_reuse < 0 or settings.buffer_size < 1:
        raise ValueError(
            "system.impact: max_staleness/buffer_size must be >= 1 and "
            f"max_reuse >= 0 (got {settings})"
        )
    return settings


class ImpactBatch(NamedTuple):
    """One learner step's worth of data on the IMPACT path."""

    batch: Any  # assembled global-array trajectory batch
    behavior_version: int  # oldest param version that collected it
    fresh: bool  # False when re-stepping a buffered stale batch


class ImpactIngest:
    """Host-side fresh/stale scheduling for the IMPACT learner
    (docs/DESIGN.md §2.12).

    The learner prefers a FULL set of fresh payloads (`need` of them — any
    actor mix, shapes are identical, so one compiled learn step serves both
    paths). When fresh data is late it re-steps the newest eligible buffered
    batch instead of blocking in collect; only with an empty buffer does it
    block in wait_for_data (warmup, or reuse budget exhausted). Buffered
    entries retire on a reuse budget and are dropped once their version lag
    exceeds max_staleness."""

    def __init__(self, pipeline: OffPolicyPipeline, need: int, settings: ImpactSettings):
        import collections

        self._pipeline = pipeline
        self._need = need
        self._settings = settings
        self._pending: List[Any] = []  # (behavior_version, payload) FIFO
        # [behavior_version, batch, reuse_left]; bounded — an append past
        # capacity retires the OLDEST (stalest) entry.
        self._buffer = collections.deque(maxlen=settings.buffer_size)
        registry = get_registry()
        self._reused = registry.counter(
            "stoix_tpu_impact_reused_batches_total",
            "Learner updates that re-stepped a buffered stale batch because "
            "fresh rollouts were late",
        )
        self._dropped = registry.counter(
            "stoix_tpu_impact_dropped_batches_total",
            "Buffered batches retired for exceeding system.impact.max_staleness",
        )

    def _ingest(self, items: List[Any]) -> None:
        for _actor_id, (version, payload) in items:
            self._pending.append((version, payload))

    def _pop_reusable(self, current_version: int) -> "ImpactBatch | None":
        max_lag = self._settings.max_staleness
        while self._buffer:
            # Newest entry first: it has the smallest lag, so if IT is too
            # stale everything behind it is too.
            version, batch, reuse_left = self._buffer[-1]
            if current_version - version > max_lag:
                self._dropped.inc(len(self._buffer))
                self._buffer.clear()
                return None
            if reuse_left <= 0:
                self._buffer.pop()
                continue
            self._buffer[-1][2] = reuse_left - 1
            self._reused.inc()
            return ImpactBatch(batch, version, fresh=False)
        return None

    def next_batch(
        self, assemble: Callable[[List[Any]], Any], current_version: int,
        timeout: float = 180.0,
    ) -> ImpactBatch:
        """One update's batch: fresh when a full payload set is available (or
        arrives while the buffer is empty), else a buffered stale batch."""
        self._ingest(self._pipeline.poll(max_items=4 * self._need, timeout=0.0))
        if len(self._pending) < self._need:
            reusable = self._pop_reusable(current_version)
            if reusable is not None:
                return reusable
            while len(self._pending) < self._need:
                self._ingest(self._pipeline.wait_for_data(timeout=timeout))
        take, self._pending = self._pending[: self._need], self._pending[self._need:]
        version = min(v for v, _ in take)
        batch = assemble([p for _, p in take])
        if self._settings.max_reuse > 0:
            self._buffer.append([version, batch, self._settings.max_reuse])
        return ImpactBatch(batch, version, fresh=True)


def _build_networks(config: Any, num_actions: int, obs_value: Any, env: Any = None):
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


def get_learn_step(actor_apply, critic_apply, update_fns, config, mesh: Mesh):
    """jit+shard_map PPO update over the learner mesh; batch arrives as global
    arrays sharded on the env axis."""
    actor_update, critic_update = update_fns
    gamma = float(config.system.gamma)
    normalize_obs = bool(config.system.get("normalize_observations", False))
    guard_mode = guards.resolve_mode(config)

    def _maybe_normalize(observation, obs_stats):
        if not normalize_obs:
            return observation
        return running_statistics.normalize_observation(observation, obs_stats)

    def per_shard(state: CoreLearnerState, traj: PPOTransition):
        # Actors already acted on observations normalized with these (pre-
        # update) statistics; normalize the stored RAW obs identically, then
        # fold the raw batch into the statistics (psum over the mesh axis).
        obs_stats = state.obs_stats
        raw_obs = traj.obs
        traj = traj._replace(
            obs=_maybe_normalize(raw_obs, obs_stats),
            next_obs=_maybe_normalize(traj.next_obs, obs_stats),
        )
        if normalize_obs:
            obs_stats = running_statistics.update(
                obs_stats, raw_obs.agent_view, axis_names=("data",),
                std_min_value=5e-4, std_max_value=5e4,
            )
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

        @annotate(SCOPES["update_minibatch"])
        def _minibatch(carry, batch):
            params, opt_states = carry
            mb_traj, mb_adv, mb_tgt = batch

            def actor_loss_fn(p):
                dist = actor_apply(p, mb_traj.obs)
                log_prob = dist.log_prob(mb_traj.action)
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
            # same keep/skip decision on the replicated params.
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

        @annotate(SCOPES["update_epoch"])
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


def get_impact_learn_step(
    actor_apply, critic_apply, update_fns, config, mesh: Mesh, rho_clip: float
):
    """IMPACT variant of get_learn_step (arXiv:1912.00167, docs/DESIGN.md
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
    gamma = float(config.system.gamma)
    normalize_obs = bool(config.system.get("normalize_observations", False))
    guard_mode = guards.resolve_mode(config)

    def _maybe_normalize(observation, obs_stats):
        if not normalize_obs:
            return observation
        return running_statistics.normalize_observation(observation, obs_stats)

    def per_shard(state: CoreLearnerState, target_params, traj: PPOTransition):
        obs_stats = state.obs_stats
        raw_obs = traj.obs
        traj = traj._replace(
            obs=_maybe_normalize(raw_obs, obs_stats),
            next_obs=_maybe_normalize(traj.next_obs, obs_stats),
        )
        if normalize_obs:
            obs_stats = running_statistics.update(
                obs_stats, raw_obs.agent_view, axis_names=("data",),
                std_min_value=5e-4, std_max_value=5e4,
            )
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

        @annotate("impact_minibatch")
        def _minibatch(carry, batch):
            params, opt_states = carry
            mb_traj, mb_adv, mb_tgt = batch

            def actor_loss_fn(p):
                dist = actor_apply(p, mb_traj.obs)
                log_prob = dist.log_prob(mb_traj.action)
                # Target policy log-probs on the same (normalized) obs; no
                # gradient flows into them (target_params is not `p`).
                target_dist = actor_apply(target_params.actor_params, mb_traj.obs)
                target_log_prob = target_dist.log_prob(mb_traj.action)
                loss = losses.impact_loss(
                    log_prob, mb_traj.log_prob, target_log_prob, mb_adv,
                    float(config.system.clip_eps), rho_clip,
                )
                entropy = dist.entropy().mean()
                return loss - float(config.system.ent_coef) * entropy, (loss, entropy)

            def critic_loss_fn(p):
                value = critic_apply(p, mb_traj.obs)
                loss = losses.clipped_value_loss(
                    value, mb_traj.value, mb_tgt, float(config.system.clip_eps)
                )
                return float(config.system.vf_coef) * loss, loss

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
            # Divergence guard stays wired on the stale-reuse path — a
            # blown-up IS ratio meeting a stale minibatch is exactly the
            # non-finite-update class system.update_guard exists for.
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

        @annotate("impact_epoch")
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

    return jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(CoreLearnerState(P(), P(), P(), P()), P(), P(None, "data")),
            out_specs=(CoreLearnerState(P(), P(), P(), P()), P()),
            check_vma=True,
        )
    )


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


def rollout_thread(
    actor_id: int,
    actor_device: jax.Device,
    env_factory,
    actor_apply,
    critic_apply,
    config: Any,
    pipeline: OnPolicyPipeline,
    param_server: ParameterServer,
    learner_devices: List[jax.Device],
    learner_mesh: Mesh,
    lifetime: ThreadLifetime,
    seed: int,
    metrics_sink: "queue.Queue",
    supervisor: Any = None,
) -> None:
    envs_per_actor = int(config.arch.actor.envs_per_actor)
    rollout_length = int(config.system.rollout_length)
    timer = TimingTracker()

    try:
        _rollout_body(
            actor_id, actor_device, env_factory, actor_apply, critic_apply,
            config, pipeline, param_server, learner_devices, learner_mesh,
            lifetime, seed, metrics_sink, envs_per_actor, rollout_length, timer,
        )
    except Exception as exc:
        import traceback

        get_registry().counter(
            "stoix_tpu_sebulba_actor_crashes_total",
            "Actor threads that died with an exception",
        ).inc(labels={"actor": str(actor_id)})
        get_logger("stoix_tpu.sebulba").error(
            "[actor-%d] CRASHED:\n%s", actor_id, traceback.format_exc()
        )
        if supervisor is not None:
            # Supervised: restart with backoff, or propagate a typed
            # ComponentFailure poison-pill (resilience/supervisor.py).
            supervisor.report_crash(actor_id, exc)
        else:
            lifetime.stop()


def _rollout_body(
    actor_id, actor_device, env_factory, actor_apply, critic_apply, config,
    pipeline, param_server, learner_devices, learner_mesh, lifetime, seed,
    metrics_sink, envs_per_actor, rollout_length, timer,
):
    envs = env_factory(envs_per_actor)
    timestep = envs.reset(seed=seed)
    # A host pool (C++/EnvPool/Gymnasium) reads the action on the host; a
    # pure-JAX twin takes the device array as it is.
    host_pool = bool(getattr(envs, "takes_host_actions", False))

    normalize_obs = bool(config.system.get("normalize_observations", False))
    # Every pushed trajectory is tagged with the version of the params that
    # collected it: the learner gauges policy lag from it (its newest version
    # minus this one), and on the IMPACT path (docs/DESIGN.md §2.12)
    # computes per-batch staleness.
    impact_on = impact_settings_from_config(config) is not None

    act_fn = get_act_fn(actor_apply, critic_apply, normalize_obs)
    step_seconds = StepAccumulator()
    storage = RolloutStorage(rollout_length, learner_devices)

    with jax.default_device(actor_device):
        key = jax.random.PRNGKey(seed)
        versioned = param_server.get_params_versioned(actor_id)
        if versioned is None:
            return
        behavior_version, params = versioned
        rollout_idx = 0
        while not lifetime.should_stop():
            # Chaos injection points (no-ops unless STOIX_TPU_FAULT armed):
            # a deterministic crash exercises supervised restart, a
            # deterministic wedge exercises heartbeat wedge detection.
            faultinject.maybe_crash_actor(actor_id, rollout_idx)
            faultinject.maybe_stall_queue(
                actor_id, rollout_idx, should_abort=lifetime.should_stop
            )
            # Pipelining: skip the param fetch on the second rollout so actors
            # run ahead while the learner computes (reference :202-214).
            if rollout_idx > 1:
                with timer.time("get_params"):
                    fetched = param_server.get_params_versioned(actor_id)
                    if fetched is None:
                        break
                    behavior_version, params = fetched
            with span("actor_rollout", clock=timer, phase="rollout",
                      actor=actor_id, idx=rollout_idx):
                for _ in range(rollout_length):
                    with span("actor_inference", clock=step_seconds, phase="inference"):
                        key, act_key = jax.random.split(key)
                        # Envs may live on a different device (e.g. CPU for
                        # C++/EnvPool backends); stage observations onto the
                        # actor device for inference.
                        obs_local = jax.device_put(timestep.observation, actor_device)
                        action, log_prob, value = act_fn(params, obs_local, act_key)
                        # `inference` ends when the action is where the env
                        # reads it. For a host pool that is the host: the
                        # device-to-host copy its step() would make, made
                        # here (moved, not added), so `env_step` times the
                        # pool alone and not the wait for the device.
                        env_action = np.asarray(action) if host_pool else action
                    with span("actor_env_step", clock=step_seconds, phase="env_step"):
                        next_timestep = envs.step(env_action)
                    # Row t of the rollout, outside both spans. The operators
                    # keep what a host pool returns on the host (numpy) and a
                    # JAX twin's arrays on their device.
                    storage.add(
                        PPOTransition(
                            done=next_timestep.discount == 0.0,
                            truncated=next_timestep.last() & (next_timestep.discount != 0.0),
                            action=action,
                            value=value,
                            reward=next_timestep.reward,
                            log_prob=log_prob,
                            obs=obs_local,
                            next_obs=next_timestep.extras["next_obs"],
                            info=next_timestep.extras["episode_metrics"],
                        )
                    )
                    timestep = next_timestep
            # Mean seconds a step over this rollout, into the rolling means
            # logged as actor<i>_inference_time / actor<i>_env_step_time.
            step_seconds.flush(timer, rollout_length)

            with span("actor_prepare_data", clock=timer, phase="prepare_data",
                      actor=actor_id):
                # Per leaf, the learner devices' [T, E/n] slices of the env
                # axis, as single-device shards for global-array assembly:
                # one transfer a host leaf, one program for the device leaves.
                payload, stored = storage.finish()
            with timer.time("queue_put"):
                try:
                    tagged = (behavior_version, payload)
                    if impact_on:
                        pipeline.push(actor_id, tagged, timeout=60.0)
                    else:
                        pipeline.send_rollout(actor_id, tagged, timeout=60.0)
                except queue.Full:
                    if lifetime.should_stop():
                        break
                    raise
            metrics_sink.put(
                {
                    "episode_metrics": host_copy(stored.info),
                    "timings": {
                        **timer.all_means(prefix=f"actor{actor_id}_"),
                        **timer.all_percentiles(prefix=f"actor{actor_id}_"),
                    },
                }
            )
            rollout_idx += 1


def run_experiment(
    config: Any,
    learn_step_builder: Callable = None,
    networks_builder: Callable = None,
) -> float:
    LAST_RUN_STATS.clear()
    # Resilience (docs/DESIGN.md §2.3): arm the chaos plan before anything is
    # traced (the in-jit nan_loss fault binds at trace time) and resolve the
    # divergence-guard mode for the learner loop's host-side checks.
    faultinject.configure(config.arch.get("fault_spec"))
    guard_mode = guards.resolve_mode(config)
    # Compile economy (docs/DESIGN.md §2.7): persistent XLA cache knobs must
    # land before the first compile, and the multistep scan-kernel default
    # before the learner is traced.
    compilecache.configure(config)
    scan_kernels.configure_from_config(config)
    # Set-up phases -> stoix_tpu_setup_phase_seconds{phase}, as in the
    # Anakin runner (host memory only).
    setup_phases = SetupClock()
    # Launch hardening (docs/DESIGN.md §2.4, arch.preflight): subprocess
    # backend probe + config cross-validation before any device work — the
    # actor/learner device-id split below is exactly the class of config this
    # catches (ids out of range, envs not divisible by actors).
    pf = preflight.settings_from_config(config)
    if pf.enabled:
        probe = preflight.probe_backend(
            timeout_s=pf.probe_timeout_s,
            attempts=pf.probe_attempts,
            backoff_base_s=pf.probe_backoff_base_s,
            backoff_max_s=pf.probe_backoff_max_s,
        )
        preflight.validate_config(config, device_count=probe.device_count)
    # Device assignment through the unified mesh-role abstraction
    # (parallel/roles.py, docs/DESIGN.md §2.11): the actor/learner/evaluator
    # split — historically resolved ad hoc from arch.actor.device_ids /
    # arch.learner.device_ids / arch.evaluator_device_id — now arrives as one
    # validated MeshRoles object (the same object the Anakin runner, serve,
    # and the population runner consume).
    roles = MeshRoles.from_config(config)
    actor_devices = roles.role_devices("act")
    learner_devices = roles.role_devices("learn")
    evaluator_device = roles.device("evaluate")
    learner_mesh = roles.learn_mesh()
    eval_mesh = roles.role_mesh("evaluate")

    actors_per_device = int(config.arch.actor.actor_per_device)
    num_actors = len(actor_devices) * actors_per_device
    config.arch.actor.envs_per_actor = int(config.arch.total_num_envs) // num_actors

    # Budget accounting (reference total_timestep_checker sebulba branch).
    steps_per_update = int(config.system.rollout_length) * int(config.arch.total_num_envs)
    if config.arch.get("num_updates") in (None, "~"):
        config.arch.num_updates = max(
            1, int(float(config.arch.total_timesteps)) // steps_per_update
        )
    config.arch.total_timesteps = int(config.arch.num_updates) * steps_per_update
    num_evaluation = max(1, int(config.arch.get("num_evaluation", 1)))
    config.arch.num_updates_per_eval = max(1, int(config.arch.num_updates) // num_evaluation)
    config.logger.system_name = config.system.system_name

    with span("env_build", clock=setup_phases, phase="env_build"):
        # The C++ pool's first build (g++, once a checkout) is in here.
        env_factory = make_factory(config)
        probe_envs = env_factory(1)
        num_actions = probe_envs.num_actions
        config.system.action_dim = num_actions
        dummy_obs = jax.tree.map(
            lambda x: np.asarray(x)[None], probe_envs.observation_space().generate_value()
            if hasattr(probe_envs.observation_space(), "generate_value")
            else probe_envs.reset(seed=0).observation,
        )

    build = networks_builder or (
        lambda cfg, n, obs: _build_networks(cfg, n, obs, env=probe_envs)
    )
    with span("network_init", clock=setup_phases, phase="network_init"):
        actor, critic = build(config, num_actions, dummy_obs)
        key = jax.random.PRNGKey(int(config.arch.seed))
        key, a_key, c_key = jax.random.split(key, 3)
        obs0 = jax.tree.map(lambda x: jnp.asarray(x), probe_envs.reset(seed=0).observation)
        actor_params = actor.init(a_key, obs0)
        critic_params = critic.init(c_key, obs0)

    with span("learner_setup", clock=setup_phases, phase="learner_setup"):
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
        params = ActorCriticParams(actor_params, critic_params)
        opt_states = ActorCriticOptStates(
            actor_optim.init(actor_params), critic_optim.init(critic_params)
        )
        key, learn_key = jax.random.split(key)
        obs0_single = jax.tree.map(lambda x: jnp.asarray(x)[0], obs0.agent_view)
        obs_stats = running_statistics.init_state(obs0_single)
        learner_state = jax.device_put(
            CoreLearnerState(params, opt_states, learn_key, obs_stats),
            NamedSharding(learner_mesh, P()),
        )

        # IMPACT stale-trajectory reuse (docs/DESIGN.md §2.12): None (the
        # default) constructs the UNCHANGED on-policy objects below — same
        # OnPolicyPipeline, same get_learn_step trace.
        impact = impact_settings_from_config(config)
        if impact is not None and learn_step_builder is not None:
            raise ValueError(
                "system.impact.enabled is incompatible with a custom "
                "learn_step_builder: the IMPACT update takes (state, "
                "target_params, batch), not (state, batch)"
            )
        if impact is not None:
            learn_step = get_impact_learn_step(
                actor.apply, critic.apply, (actor_optim.update, critic_optim.update),
                config, learner_mesh, rho_clip=impact.rho_clip,
            )
        else:
            builder = learn_step_builder or get_learn_step
            learn_step = builder(
                actor.apply, critic.apply, (actor_optim.update, critic_optim.update),
                config, learner_mesh,
            )

        # State-integrity sentinel (docs/DESIGN.md §2.9, arch.integrity): Sebulba
        # has no coalesced fetch to piggyback fingerprints on, so the learner
        # loop checks the replicated learner state synchronously at each eval
        # boundary (the vector is [num_learner_devices] uint32 — tiny). Off (the
        # default) = None = unchanged loop.
        sentinel = integrity.sentinel_from_config(config)
        if sentinel is not None:
            sentinel.bind(learner_mesh, learner_state)
            sentinel.install_excepthook()

    normalize_obs = bool(config.system.get("normalize_observations", False))

    def eval_apply(payload, observation):
        if normalize_obs:
            p, stats = payload
            observation = running_statistics.normalize_observation(observation, stats)
            return actor.apply(p, observation)
        return actor.apply(payload, observation)

    # Evaluation on the dedicated device via the standard sharded evaluator
    # when the scenario has a JAX env (registry/suites); stateful backends
    # with no JAX twin (EnvPool Atari ids) evaluate on a factory pool instead
    # (reference: Sebulba evaluates EnvPool envs on factory envs).
    from stoix_tpu.envs.registry import make_single
    from stoix_tpu.envs.wrappers import RecordEpisodeMetrics
    from stoix_tpu.evaluator import get_stateful_evaluator_fn

    from stoix_tpu.envs import suites
    from stoix_tpu.envs.registry import ENV_REGISTRY

    scenario = (
        config.env.scenario.name
        if hasattr(config.env.scenario, "name")
        else config.env.scenario
    )
    suite = getattr(config.env, "env_name", None)
    has_jax_twin = scenario in ENV_REGISTRY or suite in suites.SUITE_MAKERS
    with span("evaluator_setup", clock=setup_phases, phase="evaluator_setup"):
        if has_jax_twin:
            # Genuine construction errors must surface — only the known
            # no-JAX-twin case (EnvPool/Gymnasium task ids) falls back.
            eval_env = RecordEpisodeMetrics(
                make_single(
                    scenario, suite=suite, **dict(config.env.get("kwargs", {}) or {})
                )
            )
            eval_fn = get_ff_evaluator_fn(
                eval_env, get_distribution_act_fn(config, eval_apply), config, eval_mesh
            )
        else:
            eval_fn = get_stateful_evaluator_fn(
                env_factory, get_distribution_act_fn(config, eval_apply), config
            )

    logger = StoixLogger(config)
    # Ops plane (docs/DESIGN.md §2.13): StoixLogger's configure() just reset
    # the health monitor and flight recorder — and started the ops HTTP
    # server if `logger.telemetry.http.enabled` — so register THIS run's
    # identity, goodput ledger, and heartbeat board on the fresh instances.
    http_cfg = dict(dict(config.logger.get("telemetry") or {}).get("http") or {})
    ledger = goodput.GoodputLedger().start()
    goodput.set_active(ledger)
    recorder = flightrec.get_flight_recorder()
    recorder.set_context(
        architecture="sebulba",
        system=str(config.system.system_name),
        seed=int(config.arch.seed),
    )
    status = get_status_board()
    status.update(
        {
            "run_id": f"{config.system.system_name}_seed{config.arch.seed}",
            "architecture": "sebulba",
            "system": str(config.system.system_name),
            "step": 0,
        }
    )
    lifetime = ThreadLifetime()
    # Fleet coordination (docs/DESIGN.md §2.6, arch.fleet): in a multi-host
    # Sebulba deployment the learner loop exchanges window-indexed stop votes
    # through the jax.distributed KV store (there is no coalesced device
    # fetch to piggyback on here), publishes heartbeats, and fails collects
    # fast on a declared partition. Off (default) = None = unchanged loop.
    fleet_coord = fleet.fleet_from_config(config)
    if fleet_coord is not None:
        fleet_coord.start()
    if impact is None:
        pipeline = OnPolicyPipeline(num_actors, fleet=fleet_coord)
    else:
        # Push/poll ingestion: a slow actor no longer gates every update —
        # the learner re-steps buffered stale batches instead (ImpactIngest).
        pipeline = OffPolicyPipeline(num_actors, fleet=fleet_coord)
    # One heartbeat board for the whole run: actor beats come from the
    # pipeline, param-server and evaluator beats land on the same board so
    # the stall detector sees every component's age — and /healthz reads the
    # same board through the process-wide health monitor.
    monitor = get_health_monitor()
    monitor.register_board(
        "sebulba-pipeline",
        pipeline.heartbeats,
        stale_after_s=float(http_cfg.get("stale_after_s", 60.0) or 60.0),
    )
    param_server = ParameterServer(
        actor_devices, actors_per_device, heartbeats=pipeline.heartbeats
    )
    metrics_sink: "queue.Queue" = queue.Queue()

    eval_results: List[float] = []

    def on_eval_result(metrics, params_used, t):
        logger.log(metrics, t, len(eval_results), LogEvent.EVAL)
        eval_results.append(float(jnp.mean(metrics["episode_return"])))

    # Set-up's last phase: from the first thread started to the first
    # completed learner update (the actors' first rollouts and every first
    # compile — act_fn, the learn step — are in it).
    first_tick = contextlib.ExitStack()
    first_tick.enter_context(span("first_tick", clock=setup_phases, phase="first_tick"))
    async_evaluator = AsyncEvaluator(
        eval_fn, lifetime, on_eval_result, heartbeats=pipeline.heartbeats
    )
    async_evaluator.thread.start()

    param_server.distribute_params((params, obs_stats))

    # Actor threads are owned by the supervisor (arch.supervision, on by
    # default): a crashed actor is respawned from its factory — fresh thread,
    # fresh env instance, re-primed params — with bounded backoff; past the
    # restart budget (or on a heartbeat wedge) a ComponentFailure poison-pill
    # makes the learner fail fast instead of burning the collect timeout.
    supervisor = supervisor_from_config(config, lifetime, pipeline, param_server)
    actor_threads: List[threading.Thread] = []

    def _actor_factory(actor_id: int, device) -> Callable[[], threading.Thread]:
        def make() -> threading.Thread:
            return threading.Thread(
                target=rollout_thread,
                args=(
                    actor_id, device, env_factory, actor.apply, critic.apply,
                    config, pipeline, param_server, learner_devices, learner_mesh,
                    lifetime, int(config.arch.seed) + 7919 * actor_id, metrics_sink,
                    supervisor,
                ),
                name=f"actor-{actor_id}",
                daemon=True,
            )

        return make

    for d_idx, device in enumerate(actor_devices):
        for a_idx in range(actors_per_device):
            actor_id = d_idx * actors_per_device + a_idx
            factory = _actor_factory(actor_id, device)
            if supervisor is not None:
                supervisor.register(actor_id, factory)
            else:
                t = factory()
                t.start()
                actor_threads.append(t)
    if supervisor is not None:
        supervisor.start_watchdog(pipeline.heartbeats)

    # Graceful preemption: SIGTERM/SIGINT stop the learner loop at the next
    # update boundary and run the orderly shutdown path (lifetime stop, queue
    # drain, evaluator drain) instead of dying mid-handoff.
    preempt = PreemptionHandler().install()

    timer = TimingTracker()

    def _assemble_batch(payloads):
        # Per learner device: concat all payloads' shards, then build one
        # global array per leaf. The shards are [T, E/n] slices of the ENV
        # axis, so they tile array_axis=1 — assembling on the leading axis
        # would stack trajectories along TIME and let GAE bootstrap across
        # the device seam. (IMPACT note: any num_actors payloads tile to the
        # same global shape, so fresh and reused batches share one compile.)
        def to_global(*leaves):
            per_device = []
            for d in range(len(learner_devices)):
                shards = [leaf[d] for leaf in leaves]
                with jax.default_device(learner_devices[d]):
                    per_device.append(jnp.concatenate(shards, axis=1))
            return assemble_global_array(
                per_device, learner_mesh, axis="data", array_axis=1
            ) if len(per_device) > 1 else per_device[0]

        # leaves are lists of per-device arrays; traverse manually.
        flat_payloads = [jax.tree.flatten(p, is_leaf=lambda x: isinstance(x, list))
                         for p in payloads]
        treedef = flat_payloads[0][1]
        merged_leaves = [
            to_global(*(fp[0][i] for fp in flat_payloads))
            for i in range(len(flat_payloads[0][0]))
        ]
        return jax.tree.unflatten(treedef, merged_leaves)

    impact_ingest = None
    impact_stats = None
    target_params = None
    if impact is not None:
        impact_ingest = ImpactIngest(pipeline, num_actors, impact)
        # Target network = device-side alias of a recent online version,
        # refreshed on the host every target_update_interval updates.
        target_params = learner_state.params
        impact_staleness_gauge = get_registry().gauge(
            "stoix_tpu_impact_batch_staleness",
            "Param-version lag (learner version minus behavior version) of "
            "the batch consumed by the most recent IMPACT update",
        )
        impact_refreshes = get_registry().counter(
            "stoix_tpu_impact_target_refreshes_total",
            "IMPACT target-network refreshes from the online params",
        )
        impact_stats = {
            "updates": 0, "fresh_updates": 0, "reused_updates": 0,
            "staleness_sum": 0, "max_staleness_seen": 0, "target_refreshes": 0,
        }

    t_steps = 0
    skipped_base = guards.skipped_counter().value()
    steady_start_time = None  # set after the first eval block (post-compile)
    steady_start_steps = 0
    run_start_time = time.perf_counter()  # whole-run FPS denominator (incl.
    # first-rollout compile — the number a fleet scheduler actually gets)
    fleet_window_started = time.perf_counter()
    # STOIX_TPU_PROFILE_DIR=<dir>: a jax.profiler trace around ONE
    # steady-state learner update, as the Anakin runner wraps one eval
    # window: the update that follows the SECOND eval block (the first
    # block's evaluation compiles), opened just before that block is logged
    # and its evaluation submitted, so the evaluator's `async_eval` is whole
    # inside it. Every thread's spans are TraceAnnotations, so the trace
    # holds the actor, learner and evaluator threads on separate host lines
    # and the device ops, all on one clock.
    profile_dir = os.environ.get("STOIX_TPU_PROFILE_DIR")
    profile_update, profiling = -1, False
    if profile_dir:
        profile_update = min(
            2 * int(config.arch.num_updates_per_eval), int(config.arch.num_updates) - 1
        )
    try:
        for update_idx in range(int(config.arch.num_updates)):
            fresh = True
            if impact_ingest is None:
                with span("learner_rollout_wait", clock=timer, phase="rollout_get",
                          update=update_idx):
                    tagged = pipeline.collect_rollouts()
                ledger.note(
                    goodput.SEBULBA_PHASE_MAP["rollout_get"],
                    timer.latest("rollout_get"),
                )
                with span("learner_assemble", clock=timer, phase="assemble",
                          update=update_idx):
                    # Policy lag of every rollout consumed: the learner's
                    # newest version minus the one the actor acted with.
                    for behavior_version, _ in tagged:
                        param_server.observe_policy_lag(behavior_version)
                    batch = _assemble_batch([payload for _, payload in tagged])
                ledger.note(
                    goodput.SEBULBA_PHASE_MAP["assemble"], timer.latest("assemble")
                )
            else:
                with span("impact_next_batch", clock=timer, phase="rollout_get",
                          update=update_idx):
                    got = impact_ingest.next_batch(
                        _assemble_batch, param_server.version
                    )
                ledger.note(
                    goodput.SEBULBA_PHASE_MAP["rollout_get"],
                    timer.latest("rollout_get"),
                )
                batch, fresh = got.batch, got.fresh
                # First-class staleness: the learner's current version (=
                # completed distributes, i.e. the params it just trained)
                # minus the OLDEST behavior version in the batch; grows on
                # every re-step of the same buffered batch.
                staleness = param_server.version - got.behavior_version
                impact_staleness_gauge.set(staleness)
                impact_stats["updates"] += 1
                impact_stats["fresh_updates" if fresh else "reused_updates"] += 1
                impact_stats["staleness_sum"] += staleness
                impact_stats["max_staleness_seen"] = max(
                    impact_stats["max_staleness_seen"], staleness
                )

            with span("learner_update", clock=timer, phase="learn", update=update_idx):
                if impact_ingest is None:
                    learner_state, train_metrics = learn_step(learner_state, batch)
                else:
                    learner_state, train_metrics = learn_step(
                        learner_state, target_params, batch
                    )
                jax.block_until_ready(train_metrics)
            ledger.note(goodput.SEBULBA_PHASE_MAP["learn"], timer.latest("learn"))
            param_server.distribute_params(
                (learner_state.params, learner_state.obs_stats)
            )
            if update_idx == 0:
                first_tick.close()
            if profiling:
                profiling = False
                try:
                    jax.profiler.stop_trace()
                except Exception:  # noqa: BLE001 — profiling must never kill a run
                    pass
            elif update_idx + 1 == profile_update:
                try:
                    jax.profiler.start_trace(profile_dir)
                    profiling = True
                except Exception:  # noqa: BLE001
                    pass
            if impact_ingest is not None:
                if impact_stats["updates"] % impact.target_update_interval == 0:
                    target_params = learner_state.params
                    impact_stats["target_refreshes"] += 1
                    impact_refreshes.inc()
            if fresh:
                # Re-stepping a buffered batch consumes no NEW env frames:
                # t_steps stays an env-frame count (fps denominators, eval
                # t axis) rather than a gradient-step count.
                t_steps += steps_per_update
            # Divergence guard, host half: count skipped updates; halt mode
            # raises DivergenceError here (metrics are already materialized
            # by the block_until_ready above — no extra sync).
            guards.publish_guard_metrics(guard_mode, train_metrics, t_steps)
            if fleet_coord is None:
                if preempt.stop_requested():
                    preempt.acknowledge(t_steps)
                    break
            else:
                # Fleet mode: never stop alone. The local preemption flag
                # becomes this host's vote at the next eval-window boundary
                # (below), so every host drains at the SAME window; a peer
                # partition declared by the monitor raises the typed error
                # here instead of wedging a future collective.
                fleet_coord.check_partition()
                if preempt.stop_requested():
                    fleet_coord.request_stop(
                        fleet.FLAG_PREEMPT,
                        note=f"{preempt.signal_name} at update {update_idx}",
                    )

            if (update_idx + 1) % int(config.arch.num_updates_per_eval) == 0:
                with span("learner_log", update=update_idx):
                    # Drain actor metrics and log.
                    ep_returns, timings = [], {}
                    while not metrics_sink.empty():
                        m = metrics_sink.get_nowait()
                        em = m["episode_metrics"]
                        mask = em["is_terminal_step"].reshape(-1)
                        if mask.any():
                            ep_returns.extend(
                                em["episode_return"].reshape(-1)[mask].tolist()
                            )
                        timings.update(m["timings"])
                    if ep_returns:
                        logger.log({"episode_return": np.asarray(ep_returns)}, t_steps,
                                   update_idx, LogEvent.ACT)
                    logger.log(jax.tree.map(lambda x: jnp.mean(x), train_metrics),
                               t_steps, update_idx, LogEvent.TRAIN)
                    logger.log(
                        {
                            **timings,
                            **timer.all_means(prefix="learner_"),
                            **timer.all_percentiles(prefix="learner_"),
                        },
                        t_steps, update_idx, LogEvent.MISC,
                    )
                    key, ek = jax.random.split(key)
                    if normalize_obs:
                        eval_payload = (
                            learner_state.params.actor_params, learner_state.obs_stats
                        )
                    else:
                        eval_payload = learner_state.params.actor_params
                    eval_params = jax.device_put(
                        jax.tree.map(np.asarray, eval_payload), evaluator_device
                    )
                    async_evaluator.submit(eval_params, ek, t_steps)
                if steady_start_time is None:
                    # Steady-state SPS window opens once compile/warmup has
                    # been paid (end of the first eval block).
                    steady_start_time = time.perf_counter()
                    steady_start_steps = t_steps
                window_idx = (update_idx + 1) // int(config.arch.num_updates_per_eval)
                status.update({"window": window_idx, "step": t_steps})
                recorder.record(
                    "window", window=window_idx, step=t_steps,
                    updates=update_idx + 1,
                    queue_wait_s=round(timer.mean("rollout_get"), 6),
                    learn_s=round(timer.mean("learn"), 6),
                )
                corruption = None
                if sentinel is not None:
                    # Integrity check at the eval boundary (docs/DESIGN.md
                    # §2.9): synchronous fingerprint + compare of the
                    # replicated learner state. A verdict becomes this
                    # host's FLAG_CORRUPT on the window's fleet vote (so the
                    # stop reason is agreed and visible fleet-wide) and is
                    # raised below — never swallowed by the agreed break.
                    corruption = sentinel.check_state(
                        learner_state, window_idx, t_steps
                    )
                    if corruption is not None and fleet_coord is not None:
                        fleet_coord.request_stop(
                            fleet.FLAG_CORRUPT, note=str(corruption)
                        )
                if fleet_coord is not None:
                    # Window-boundary agreement: exchange stop votes for THIS
                    # window through the KV store — identical decision on
                    # every host, so all drain together — and swap straggler
                    # wall-times for the skew gauges.
                    now = time.perf_counter()
                    fleet_coord.observe_window_wall(
                        window_idx, now - fleet_window_started
                    )
                    fleet_window_started = now
                    decision = fleet_coord.agree_at_window(window_idx)
                    if decision.stop:
                        if corruption is not None:
                            raise corruption
                        if preempt.stop_requested():
                            preempt.acknowledge(t_steps)
                        else:
                            get_logger("stoix_tpu.sebulba").warning(
                                "[fleet] %s — stopping at window %d in "
                                "lockstep with the fleet",
                                decision.describe(), window_idx,
                            )
                        break
                if corruption is not None:
                    raise corruption
        # Close the window BEFORE shutdown: thread joins / evaluator drain in
        # the finally block below can take tens of seconds and must not
        # deflate the steady-state number.
        steady_end_time = time.perf_counter()
    except KeyboardInterrupt:
        # The fleet monitor interrupts the main thread when a peer dies (it
        # may be blocked in collect_rollouts' bounded get). Convert its
        # interrupt into the typed error — the excepthook then translates it
        # to EXIT_CODE_FLEET_PARTITION for the supervising launcher, exactly
        # as in the Anakin runner. A genuine operator ^C re-raises untouched.
        if fleet_coord is not None and fleet_coord.partition_event.is_set():
            raise fleet_coord.partition_error from None
        raise
    finally:
        first_tick.close()  # a run that never completed an update
        preempt.uninstall()
        goodput.set_active(None)
        monitor.unregister("sebulba-pipeline")
        if sentinel is not None:
            # BEFORE fleet stop: the excepthook chain unwinds in reverse
            # install order. Keeps the hook across a propagating corruption
            # verdict (it must still translate to exit code 88).
            sentinel.deactivate()
        if fleet_coord is not None:
            fleet_coord.stop()
        lifetime.stop()
        param_server.shutdown()
        # Unblock actors waiting to enqueue (uninstrumented: drain gets are
        # teardown artifacts and must not pollute the queue-wait series).
        for _ in range(2):
            if pipeline.drain(timeout=0.5) == 0:
                break
        if supervisor is not None:
            supervisor.join_all(timeout=10.0)
        for t in actor_threads:
            t.join(timeout=10.0)
        # Capture BEFORE our own try: inside the except block sys.exc_info()
        # would report the stall error itself, not the failure (if any) that
        # brought us into this finally.
        failure_propagating = sys.exc_info()[0] is not None
        try:
            async_evaluator.wait_until_idle(timeout=120.0)
        except (EvaluatorStallError, ComponentFailure) as exc:
            # Raising from a finally would REPLACE the failure that brought
            # us here (actor ComponentFailure, learner divergence); surface
            # a stalled or failed evaluator as the primary error only on the
            # clean-exit path.
            if not failure_propagating:
                raise
            get_logger("stoix_tpu.sebulba").error(
                "[shutdown] evaluator did not finish cleanly while handling "
                "another failure (%s) — dropping its work", exc,
            )

    if steady_start_time is not None and t_steps > steady_start_steps:
        steady = (t_steps - steady_start_steps) / (
            steady_end_time - steady_start_time
        )
        get_registry().gauge(
            "stoix_tpu_sebulba_steps_per_sec_steady",
            "Post-compile steady-state env-steps/sec of the most recent run",
        ).set(steady)
        LAST_RUN_STATS["steps_per_sec_steady"] = steady
        LAST_RUN_STATS["steady_window_steps"] = t_steps - steady_start_steps
    if t_steps > 0:
        # Whole-run env frames per second (ROADMAP item-1 leftover): total
        # env steps over the full learner-loop wall INCLUDING first-rollout
        # compile — the steady number above excludes it by design; this one
        # is what a scheduler provisioning actor fleets observes. First-class
        # in the bench --sebulba payload as `fps` (+ rep dispersion).
        fps = t_steps / max(steady_end_time - run_start_time, 1e-9)
        get_registry().gauge(
            "stoix_tpu_sebulba_fps",
            "Whole-run env-steps/sec (incl. compile) of the most recent run",
        ).set(fps)
        LAST_RUN_STATS["fps"] = fps
        LAST_RUN_STATS["total_env_steps"] = t_steps
    # Goodput close-out (docs/DESIGN.md §2.13): queue_wait/compute were noted
    # per update; finalize() attributes the residual learner-loop wall (host
    # work concurrent with actor rollouts, teardown joins) to compute per the
    # pipelined-residual rule, so the fractions sum to 1.
    LAST_RUN_STATS["goodput"] = ledger.finalize()
    LAST_RUN_STATS["setup_phases"] = {
        k: round(v, 6) for k, v in setup_phases.seconds().items()
    }
    # None when disabled (the pin tests/test_impact.py asserts): the default
    # config must report the untouched on-policy path, not a zeroed dict.
    LAST_RUN_STATS["impact"] = None if impact is None else {
        "rho_clip": impact.rho_clip,
        "target_update_interval": impact.target_update_interval,
        "max_staleness": impact.max_staleness,
        "max_reuse": impact.max_reuse,
        "updates": impact_stats["updates"],
        "fresh_updates": impact_stats["fresh_updates"],
        "reused_updates": impact_stats["reused_updates"],
        "mean_staleness": (
            impact_stats["staleness_sum"] / max(1, impact_stats["updates"])
        ),
        "max_staleness_seen": impact_stats["max_staleness_seen"],
        "target_refreshes": impact_stats["target_refreshes"],
    }
    LAST_RUN_STATS["resilience"] = {
        "update_guard": guard_mode,
        "skipped_updates": guards.skipped_counter().value() - skipped_base,
        "actor_restarts": supervisor.restart_count() if supervisor is not None else 0,
        "preempted": preempt.stop_requested(),
        # Sebulba has no checkpoint path yet: a preemption stops cleanly but
        # cannot resume mid-run.
        "resume_capable": False,
        "fleet": fleet_coord is not None,
    }
    LAST_RUN_STATS["integrity"] = (
        sentinel.stats() if sentinel is not None else integrity.disabled_stats()
    )

    logger.close()
    return eval_results[-1] if eval_results else 0.0


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_ppo.yaml",
        sys.argv[1:],
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
