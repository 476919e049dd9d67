"""Sebulba DQN — the off-policy ingestion path (docs/DESIGN.md §2.10).

Actor devices run epsilon-greedy inference against stateful envs and PUSH
transition shards through the OffPolicyPipeline whenever a rollout chunk is
ready; learner devices own a device-resident sharded replay service
(stoix_tpu/replay) and SAMPLE it independently — no lockstep collect, so a
slow or supervisor-restarting actor never stalls the learner (Podracer's
actor/learner core split, arxiv 2104.06272, applied to the DQN family).

Data path per ingest: actors flatten a [T, E] rollout chunk to [T*E]
transitions, split it across learner devices, and device_put the shards
directly onto their owning devices; the learner assembles each payload into
ONE global array via parallel.assemble_global_array (no host concat) and
hands it to `service.add` — raw experience lands on its shard and never
moves again. The learn step is one jitted shard_map program embedding the
replay core's cross-shard sampler: sample (a psum of the drawn minibatch is
the only experience bytes on the interconnect) -> Q-learning update ->
polyak target sync, with optional prioritized replay (per-TD-error
priorities scattered back through global indices, importance weights from
the GLOBAL sampling probabilities).

Supervision/heartbeats are the standard Sebulba set: actor threads are
owned by the ActorSupervisor (crash -> bounded-backoff restart with a fresh
env + re-primed params; budget exhausted -> typed ComponentFailure through
the pipeline), every push beats the HeartbeatBoard, and a starved learner
raises ActorStarvationError naming the stalest actor.
"""

from __future__ import annotations

import queue
import sys
import threading
import time
from typing import Any, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stoix_tpu.base_types import OnlineAndTarget, Transition
from stoix_tpu.envs.factory import make_factory
from stoix_tpu.evaluator import get_distribution_act_fn, get_ff_evaluator_fn
from stoix_tpu.observability import (
    RunStats,
    flightrec,
    get_health_monitor,
    get_logger,
    get_registry,
    get_status_board,
    goodput,
    span,
)
from stoix_tpu.parallel import MeshRoles, assemble_global_array
from stoix_tpu.replay import ShardedReplayService, service_from_config
from stoix_tpu.resilience import (
    PreemptionHandler,
    faultinject,
    guards,
    supervisor_from_config,
)
from stoix_tpu.resilience.errors import ComponentFailure, EvaluatorStallError
from stoix_tpu.sebulba.core import (
    AsyncEvaluator,
    OffPolicyPipeline,
    ParameterServer,
    ThreadLifetime,
)
from stoix_tpu.systems.q_learning.q_family import act_dist, build_q_network
from stoix_tpu.utils import compilecache
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.logger import LogEvent, StoixLogger
from stoix_tpu.utils.timing import TimingTracker
from stoix_tpu.utils.training import make_learning_rate

# Stats of the most recent run_experiment call in this process (read by
# bench.py --replay / tests); registry series are the source of truth.
LAST_RUN_STATS = RunStats()


class DQNLearnerState(NamedTuple):
    params: OnlineAndTarget
    opt_state: Any
    key: jax.Array


def get_dqn_learn_step(
    q_apply, q_update, config: Any, mesh: Mesh, service: ShardedReplayService
):
    """One jitted shard_map program per update: sample the sharded replay
    where the data lives, Q-learning step, polyak target sync. The replay
    state threads through (donated — the ring is the device's largest
    allocation) so prioritized runs scatter fresh priorities in-program."""
    core = service.core
    gamma = float(config.system.gamma)
    tau = float(config.system.tau)
    epochs = int(config.system.epochs)
    replay_cfg = dict(config.system.get("replay") or {})
    prioritized = bool(replay_cfg.get("prioritized", False))
    beta = float(replay_cfg.get("importance_beta", 0.4))
    guard_mode = guards.resolve_mode(config)

    def per_shard(state: DQNLearnerState, replay_state):
        rstate = jax.tree.map(lambda x: x[0], replay_state)

        def _epoch(carry, _):
            state, rstate = carry
            key, sample_key = jax.random.split(state.key)
            # state.key is replicated (in_specs P()), so every shard draws
            # the same uniforms — the core's ownership-partition contract.
            drawn = core.sample(rstate, sample_key)
            batch: Transition = drawn.experience

            if prioritized:
                # PER importance weights from the GLOBAL sampling
                # probabilities (the psum'd normalization), so the
                # correction is exact however mass is spread over shards.
                # A zero-probability row (zeroed priority resampled before
                # its slot was overwritten) contributes NOTHING — the
                # (N*p)^-beta form would instead hand it the batch's
                # LARGEST weight and flatten every real row to ~0 through
                # the max-normalization.
                n_global = jax.lax.psum(core.occupancy(rstate), "data")
                w = jnp.where(
                    drawn.probabilities > 0,
                    jnp.power(
                        jnp.maximum(n_global.astype(jnp.float32), 1.0)
                        * jnp.maximum(drawn.probabilities, 1e-9),
                        -beta,
                    ),
                    0.0,
                )
                w = w / jnp.maximum(jax.lax.pmax(jnp.max(w), "data"), 1e-9)
            else:
                w = jnp.ones_like(batch.reward)

            def loss_fn(online):
                q_tm1 = q_apply(online, batch.obs, 0.0).preferences
                q_t = q_apply(state.params.target, batch.next_obs, 0.0).preferences
                d_t = gamma * (1.0 - batch.done.astype(jnp.float32))
                target = batch.reward + d_t * jnp.max(q_t, axis=-1)
                qa = jnp.take_along_axis(
                    q_tm1, batch.action.astype(jnp.int32)[:, None], axis=-1
                )[:, 0]
                td = jax.lax.stop_gradient(target) - qa
                loss = 0.5 * jnp.mean(w * jnp.square(td))
                return loss, (td, jnp.mean(q_tm1))

            (loss, (td, mean_q)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params.online
            )
            grads = jax.lax.pmean(grads, axis_name="data")
            updates, opt_state = q_update(grads, state.opt_state)
            online = optax.apply_updates(state.params.online, updates)
            target = optax.incremental_update(online, state.params.target, tau)
            (params, opt_state), guard_metrics = guards.guard_update(
                guard_mode,
                new=(OnlineAndTarget(online, target), opt_state),
                old=(state.params, state.opt_state),
                loss=loss,
                grads=grads,
                opt_state=state.opt_state,
                axis_names=("data",),
            )
            if prioritized:
                rstate = core.set_priorities(rstate, drawn.indices, jnp.abs(td))
            metrics = {"q_loss": loss, "mean_q": mean_q, **guard_metrics}
            return (DQNLearnerState(params, opt_state, key), rstate), metrics

        (state, rstate), metrics = jax.lax.scan(
            _epoch, (state, rstate), None, epochs
        )
        metrics = jax.lax.pmean(metrics, axis_name="data")
        return state, jax.tree.map(lambda x: x[None], rstate), metrics

    return jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(), P("data")),
            out_specs=(P(), P("data"), P()),
            check_vma=False,
        ),
        donate_argnums=(1,),
    )


def rollout_thread(
    actor_id: int,
    actor_device: jax.Device,
    env_factory,
    q_apply,
    config: Any,
    pipeline: OffPolicyPipeline,
    param_server: ParameterServer,
    learner_devices: List[jax.Device],
    lifetime: ThreadLifetime,
    seed: int,
    metrics_sink: "queue.Queue",
    supervisor: Any = None,
) -> None:
    try:
        _rollout_body(
            actor_id, actor_device, env_factory, q_apply, config, pipeline,
            param_server, learner_devices, lifetime, seed, metrics_sink,
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
            supervisor.report_crash(actor_id, exc)
        else:
            lifetime.stop()


def _rollout_body(
    actor_id, actor_device, env_factory, q_apply, config, pipeline,
    param_server, learner_devices, lifetime, seed, metrics_sink,
):
    envs_per_actor = int(config.arch.actor.envs_per_actor)
    rollout_length = int(config.system.rollout_length)
    train_eps = float(config.system.training_epsilon)
    timer = TimingTracker()
    envs = env_factory(envs_per_actor)
    timestep = envs.reset(seed=seed)

    @jax.jit
    def act_fn(params, observation, key):
        dist = act_dist(q_apply(params, observation, train_eps))
        return dist.sample(seed=key)

    with jax.default_device(actor_device):
        key = jax.random.PRNGKey(seed)
        params = param_server.get_params(actor_id)
        n_learners = len(learner_devices)
        rollout_idx = 0
        while not lifetime.should_stop():
            faultinject.maybe_crash_actor(actor_id, rollout_idx)
            faultinject.maybe_stall_queue(
                actor_id, rollout_idx, should_abort=lifetime.should_stop
            )
            if rollout_idx > 0:
                # Off-policy actors NEVER wait for params: grab a fresh
                # version when one is queued, otherwise keep acting on the
                # current one (staleness is the architecture's contract).
                try:
                    fetched = param_server.get_params(actor_id, timeout=0.0)
                    if fetched is None:
                        break
                    params = fetched
                except queue.Empty:
                    pass
            traj: List[Transition] = []
            ep_infos: List[Any] = []
            with span("actor_rollout", actor=actor_id, idx=rollout_idx), \
                    timer.time("rollout"):
                for _ in range(rollout_length):
                    key, act_key = jax.random.split(key)
                    with timer.time("inference"):
                        obs_local = jax.device_put(timestep.observation, actor_device)
                        action = act_fn(params, obs_local, act_key)
                    with timer.time("env_step"):
                        next_timestep = envs.step(action)
                    traj.append(
                        Transition(
                            obs=obs_local,
                            action=action,
                            reward=next_timestep.reward,
                            done=next_timestep.discount == 0.0,
                            next_obs=next_timestep.extras["next_obs"],
                            # Episode metrics travel via metrics_sink, not
                            # through replay HBM.
                            info={},
                        )
                    )
                    ep_infos.append(next_timestep.extras["episode_metrics"])
                    timestep = next_timestep

            with span("actor_prepare_data", actor=actor_id), timer.time("prepare_data"):
                # [T, E] -> [T*E] transitions -> one shard per learner
                # device, placed directly on its owner for global-array
                # assembly (leading-axis sharding, no host concat).
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *traj)
                flat = jax.tree.map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), stacked
                )
                payload = jax.tree.map(
                    lambda x: [
                        jax.device_put(s, d)
                        for s, d in zip(jnp.split(x, n_learners, axis=0), learner_devices)
                    ],
                    flat,
                )
            with timer.time("queue_put"):
                try:
                    pipeline.push(actor_id, payload, timeout=60.0)
                except queue.Full:
                    if lifetime.should_stop():
                        break
                    raise
            metrics_sink.put(
                {
                    "episode_metrics": jax.tree.map(
                        lambda *xs: np.stack([np.asarray(x) for x in xs]), *ep_infos
                    ),
                    "timings": {
                        **timer.all_means(prefix=f"actor{actor_id}_"),
                        **timer.all_percentiles(prefix=f"actor{actor_id}_"),
                    },
                }
            )
            rollout_idx += 1


def run_experiment(config: Any) -> float:
    LAST_RUN_STATS.clear()
    faultinject.configure(config.arch.get("fault_spec"))
    guard_mode = guards.resolve_mode(config)
    compilecache.configure(config)

    # One validated MeshRoles object replaces the ad-hoc device-id split
    # (parallel/roles.py, docs/DESIGN.md §2.11); the learn mesh it yields is
    # also what the sharded replay service's data axis lives on below.
    roles = MeshRoles.from_config(config)
    actor_devices = roles.role_devices("act")
    learner_devices = roles.role_devices("learn")
    evaluator_device = roles.device("evaluate")
    learner_mesh = roles.learn_mesh()
    eval_mesh = roles.role_mesh("evaluate")

    actors_per_device = int(config.arch.actor.actor_per_device)
    num_actors = len(actor_devices) * actors_per_device
    config.arch.actor.envs_per_actor = int(config.arch.total_num_envs) // num_actors
    chunk = int(config.arch.actor.envs_per_actor) * int(config.system.rollout_length)
    if chunk % len(learner_devices) != 0:
        raise ValueError(
            f"envs_per_actor * rollout_length ({chunk}) must divide over "
            f"{len(learner_devices)} learner device(s) for shard-wise ingestion"
        )

    steps_per_update = int(config.system.rollout_length) * int(config.arch.total_num_envs)
    if config.arch.get("num_updates") in (None, "~"):
        config.arch.num_updates = max(
            1, int(float(config.arch.total_timesteps)) // steps_per_update
        )
    config.arch.total_timesteps = int(config.arch.num_updates) * steps_per_update
    num_evaluation = max(1, int(config.arch.get("num_evaluation", 1)))
    config.arch.num_updates_per_eval = max(1, int(config.arch.num_updates) // num_evaluation)
    config.logger.system_name = config.system.system_name

    env_factory = make_factory(config)
    probe_envs = env_factory(1)
    num_actions = probe_envs.num_actions
    config.system.action_dim = num_actions

    q_network = build_q_network(config, num_actions)
    q_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(
            make_learning_rate(float(config.system.q_lr), config, int(config.system.epochs)),
            eps=1e-5,
        ),
    )
    key = jax.random.PRNGKey(int(config.arch.seed))
    key, net_key, learn_key = jax.random.split(key, 3)
    obs0 = jax.tree.map(lambda x: jnp.asarray(x), probe_envs.reset(seed=0).observation)
    online_params = q_network.init(net_key, obs0)
    params = OnlineAndTarget(online_params, online_params)
    opt_state = q_optim.init(online_params)
    learner_state = jax.device_put(
        DQNLearnerState(params, opt_state, learn_key),
        NamedSharding(learner_mesh, P()),
    )

    # Replay service: buffer state sharded across learner HBM. The item
    # prototype is one UNBATCHED transition from the probe env.
    obs_single = jax.tree.map(lambda x: x[0], obs0)
    item = Transition(
        obs=obs_single,
        action=jnp.zeros((), jnp.int32),
        reward=jnp.zeros((), jnp.float32),
        done=jnp.zeros((), bool),
        next_obs=obs_single,
        info={},
    )
    service = service_from_config(learner_mesh, item, config)
    if service is None:
        raise ValueError(
            "Sebulba ff_dqn ingests through the sharded replay service: set "
            "system.replay.impl=sharded (the local item buffer lives inside "
            "Anakin's jitted learner and has no ingestion seam)"
        )
    replay_base = service.stats()

    learn_step = get_dqn_learn_step(
        q_network.apply, q_optim.update, config, learner_mesh, service
    )

    eval_eps = float(config.system.evaluation_epsilon)

    def eval_apply(p, observation):
        return act_dist(q_network.apply(p, observation, eval_eps))

    from stoix_tpu.envs import suites
    from stoix_tpu.envs.registry import ENV_REGISTRY, make_single
    from stoix_tpu.envs.wrappers import RecordEpisodeMetrics
    from stoix_tpu.evaluator import get_stateful_evaluator_fn

    scenario = (
        config.env.scenario.name
        if hasattr(config.env.scenario, "name")
        else config.env.scenario
    )
    suite = getattr(config.env, "env_name", None)
    if scenario in ENV_REGISTRY or suite in suites.SUITE_MAKERS:
        eval_env = RecordEpisodeMetrics(
            make_single(scenario, suite=suite, **dict(config.env.get("kwargs", {}) or {}))
        )
        eval_fn = get_ff_evaluator_fn(
            eval_env, get_distribution_act_fn(config, eval_apply), config, eval_mesh
        )
    else:
        eval_fn = get_stateful_evaluator_fn(
            env_factory, get_distribution_act_fn(config, eval_apply), config
        )

    logger = StoixLogger(config)
    # Ops plane (docs/DESIGN.md §2.13): register this run's identity, goodput
    # ledger, and heartbeat board on the instances configure() just reset.
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
    pipeline = OffPolicyPipeline(num_actors)
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

    async_evaluator = AsyncEvaluator(
        eval_fn, lifetime, on_eval_result, heartbeats=pipeline.heartbeats
    )
    async_evaluator.thread.start()
    param_server.distribute_params(params.online)

    supervisor = supervisor_from_config(config, lifetime, pipeline, param_server)
    actor_threads: List[threading.Thread] = []

    def _actor_factory(actor_id: int, device):
        def make() -> threading.Thread:
            return threading.Thread(
                target=rollout_thread,
                args=(
                    actor_id, device, env_factory, q_network.apply, config,
                    pipeline, param_server, learner_devices, lifetime,
                    int(config.arch.seed) + 7919 * actor_id, metrics_sink,
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

    def _ingest(payloads) -> None:
        """Assemble each pushed payload into ONE global array per leaf
        (shards already sit on their owning learner devices) and add."""
        for _actor_id, payload in payloads:
            flat, treedef = jax.tree.flatten(
                payload, is_leaf=lambda x: isinstance(x, list)
            )
            merged = [
                assemble_global_array(leaf, learner_mesh, axis="data")
                if len(leaf) > 1
                else leaf[0]
                for leaf in flat
            ]
            service.add(jax.tree.unflatten(treedef, merged))

    preempt = PreemptionHandler().install()
    timer = TimingTracker()
    param_sync = max(1, int(dict(config.system.get("replay") or {}).get(
        "param_sync_interval", 1
    )))
    skipped_base = guards.skipped_counter().value()
    steady_start_time = None
    steady_start_items = 0
    steady_end_time = None
    preempted = False

    def ingested_items() -> int:
        return service.stats()["added_items"] - replay_base["added_items"]

    # Host-side episode-metric accumulation: drained from the sink EVERY
    # update (the sink is unbounded — letting rollout chunks pile up for a
    # whole inter-eval window grows host memory with run length), logged
    # and cleared at eval boundaries.
    pending_returns: List[float] = []
    pending_timings: dict = {}

    def _drain_metrics() -> None:
        while not metrics_sink.empty():
            m = metrics_sink.get_nowait()
            em = m["episode_metrics"]
            mask = em["is_terminal_step"].reshape(-1)
            if mask.any():
                pending_returns.extend(
                    em["episode_return"].reshape(-1)[mask].tolist()
                )
            pending_timings.update(m["timings"])

    replay_warmed = False
    try:
        for update_idx in range(int(config.arch.num_updates)):
            with timer.time("ingest"):
                _ingest(pipeline.poll(timeout=0.0))
                # can_sample is monotonic (fill only grows), so the jitted
                # psum + host fetch runs only until the first True.
                while not replay_warmed and not service.can_sample():
                    # Warmup/starvation path: block for more experience (a
                    # dead actor fleet raises typed starvation here).
                    _ingest(pipeline.wait_for_data(timeout=180.0))
                replay_warmed = True
            ledger.note(
                goodput.SEBULBA_PHASE_MAP["ingest"], timer.latest("ingest")
            )
            with span("learner_update", update=update_idx), timer.time("learn"):
                learner_state, new_replay, train_metrics = learn_step(
                    learner_state, service.state
                )
                service.commit(new_replay)
                service.note_embedded_samples(int(config.system.epochs))
                jax.block_until_ready(train_metrics)
            ledger.note(goodput.SEBULBA_PHASE_MAP["learn"], timer.latest("learn"))
            if (update_idx + 1) % param_sync == 0:
                param_server.distribute_params(learner_state.params.online)
            t_steps = ingested_items()
            guards.publish_guard_metrics(guard_mode, train_metrics, t_steps)
            _drain_metrics()
            if preempt.stop_requested():
                preempt.acknowledge(t_steps)
                preempted = True
                break

            if (update_idx + 1) % int(config.arch.num_updates_per_eval) == 0:
                ep_returns, timings = pending_returns, pending_timings
                pending_returns, pending_timings = [], {}
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
                        **{f"replay_{k}": v for k, v in service.observe().items()
                           if not isinstance(v, list)},
                    },
                    t_steps, update_idx, LogEvent.MISC,
                )
                key, ek = jax.random.split(key)
                eval_params = jax.device_put(
                    jax.tree.map(np.asarray, learner_state.params.online),
                    evaluator_device,
                )
                async_evaluator.submit(eval_params, ek, t_steps)
                window_idx = (update_idx + 1) // int(config.arch.num_updates_per_eval)
                status.update({"window": window_idx, "step": t_steps})
                recorder.record(
                    "window", window=window_idx, step=t_steps,
                    updates=update_idx + 1,
                    queue_wait_s=round(timer.mean("ingest"), 6),
                    learn_s=round(timer.mean("learn"), 6),
                )
                if steady_start_time is None:
                    steady_start_time = time.perf_counter()
                    steady_start_items = ingested_items()
        steady_end_time = time.perf_counter()
    finally:
        preempt.uninstall()
        goodput.set_active(None)
        monitor.unregister("sebulba-pipeline")
        lifetime.stop()
        param_server.shutdown()
        for _ in range(2):
            if pipeline.drain(timeout=0.5) == 0:
                break
        if supervisor is not None:
            supervisor.join_all(timeout=10.0)
        for t in actor_threads:
            t.join(timeout=10.0)
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

    final_items = ingested_items()
    if (
        steady_start_time is not None
        and steady_end_time is not None
        and final_items > steady_start_items
        and steady_end_time > steady_start_time
    ):
        steady = (final_items - steady_start_items) / (
            steady_end_time - steady_start_time
        )
        get_registry().gauge(
            "stoix_tpu_sebulba_steps_per_sec_steady",
            "Post-compile steady-state env-steps/sec of the most recent run",
        ).set(steady)
        LAST_RUN_STATS["steps_per_sec_steady"] = steady
    replay_stats = service.stats()
    LAST_RUN_STATS["replay"] = {
        k: replay_stats[k] - replay_base[k] for k in replay_stats
    }
    LAST_RUN_STATS["goodput"] = ledger.finalize()
    LAST_RUN_STATS["resilience"] = {
        "update_guard": guard_mode,
        "skipped_updates": guards.skipped_counter().value() - skipped_base,
        "actor_restarts": supervisor.restart_count() if supervisor is not None else 0,
        "preempted": preempted,
        "resume_capable": False,
        "fleet": False,
    }
    logger.close()
    return eval_results[-1] if eval_results else 0.0


def main() -> float:
    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_dqn.yaml",
        sys.argv[1:],
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
