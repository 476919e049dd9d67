"""The Sebulba runner: one host loop for every Sebulba system (reference
stoix/systems/ppo/sebulba/ff_ppo.py, 1046 LoC; docs/DESIGN.md §3).

Actor/learner disaggregation for non-pure-JAX environments: actor THREADS pin
jitted inference to actor devices and step stateful envs (EnvPool/C++/JAX
adapters behind the EnvFactory seam); their rollouts reach the learner loop,
on the main thread, through a batch source (`sebulba/sources.py`); the update
runs over a learner-device mesh; fresh params return via the ParameterServer;
evaluation runs asynchronously on its own device. A system file
(`systems/*/sebulba/*.py`) hands `run_experiment` a `SebulbaSystem`: how to
build its networks and its learner. Everything else is here, once: the actor
thread, and `_Run`'s `set_up`, `learn` (the loop), `shut_down` and `close_out`.
What a run opens and closes on the host beside them — ledger, set-up clock,
fault plan, compile cache, preflight, fleet, sentinel, ops plane, stop
handling — is the run host's (`stoix_tpu/run_host.py`, docs/DESIGN.md §2.16).

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

import os
import queue
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, NamedTuple

_IMPORTS_BEGAN = time.perf_counter()  # set-up's phase `imports`: this block's seconds

import jax
import jax.numpy as jnp
import numpy as np

from stoix_tpu.envs.factory import make_factory
from stoix_tpu.evaluator import (
    get_distribution_act_fn,
    get_ff_evaluator_fn,
    get_stateful_evaluator_fn,
)
from stoix_tpu.observability import RunStats, get_logger, get_registry, goodput, span
from stoix_tpu.observability.trace import LAUNCH as setup_launch
from stoix_tpu.parallel import MeshRoles
from stoix_tpu.resilience import faultinject, fleet, guards, supervisor_from_config
from stoix_tpu.resilience.errors import ComponentFailure, EvaluatorStallError
from stoix_tpu.run_host import RunHost
from stoix_tpu.sebulba.core import AsyncEvaluator, ParameterServer, ThreadLifetime
from stoix_tpu.sebulba.sources import SourceContext
from stoix_tpu.utils.logger import LogEvent, StoixLogger
from stoix_tpu.utils.timing import StepAccumulator, TimingTracker

setup_launch.note_imports(_IMPORTS_BEGAN, time.perf_counter())

# Throughput stats of the most recent run_experiment call in this process
# (steady-state window: after the first eval block, i.e. post-compile). The
# system modules bind this object under their own name (the benchmark's driver,
# chip_smoke.py and tests read `<system>.LAST_RUN_STATS`); dict-compatible
# (RunStats) so callers can ignore it entirely. The underlying series live in
# the metrics registry (stoix_tpu_sebulba_*).
LAST_RUN_STATS = RunStats()


class Learner(NamedTuple):
    """What a system's `setup_learner` returns."""

    state: Any  # the initial learner state, replicated over the learner mesh
    step: Callable  # the jitted learn step, as its batch source calls it
    make_source: Callable  # (SourceContext) -> the batch source (sebulba/sources.py)
    make_act_fn: Callable  # () -> jitted (params, observation, key) -> (action, ...)
    transition: Callable  # (observation, act_fn's outputs, next timestep) -> a row with `.info`
    actor_params: Callable  # learner state -> what the actors' act_fn takes
    eval_params: Callable  # learner state -> what `eval_apply` takes
    eval_apply: Callable  # (eval params, observation) -> action distribution


class SebulbaSystem(NamedTuple):
    """What a system file hands `run_experiment`. Both are called inside the
    runner's set-up spans and pass the run's PRNG key along."""

    init_networks: Callable  # (config, probe_envs, key) -> (networks, key)
    setup_learner: Callable  # (config, networks, key, learner_mesh) -> (Learner, key)


def _rollout_body(
    actor_id, actor_device, env_factory, make_act_fn, transition, source, param_server,
    learner_devices, lifetime, seed, metrics_sink, envs_per_actor, rollout_length, timer,
):
    envs = env_factory(envs_per_actor)
    timestep = envs.reset(seed=seed)
    # A host pool (C++/EnvPool/Gymnasium) reads the action on the host; a
    # pure-JAX twin takes the device array as it is.
    host_pool = bool(getattr(envs, "takes_host_actions", False))

    act_fn = make_act_fn()
    step_seconds = StepAccumulator()
    storage = source.storage(rollout_length, learner_devices)

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
            # Whether an actor waits for parameters, and from which rollout
            # on, is its source's to say.
            if rollout_idx >= source.actor_fetch_from:
                with timer.time("get_params"):
                    try:
                        fetched = param_server.get_params_versioned(
                            actor_id, timeout=source.actor_fetch_timeout
                        )
                    except queue.Empty:
                        pass  # none queued: keep acting on the current ones
                    else:
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
                        act_out = act_fn(params, obs_local, act_key)
                        # `inference` ends when the action is where the env
                        # reads it. For a host pool that is the host: the
                        # device-to-host copy its step() would make, made
                        # here (moved, not added), so `env_step` times the
                        # pool alone and not the wait for the device.
                        env_action = np.asarray(act_out[0]) if host_pool else act_out[0]
                    with span("actor_env_step", clock=step_seconds, phase="env_step"):
                        next_timestep = envs.step(env_action)
                    # Row t of the rollout, outside both spans.
                    storage.add(transition(obs_local, act_out, next_timestep))
                    timestep = next_timestep
            # Mean seconds a step over this rollout, into the rolling means
            # logged as actor<i>_inference_time / actor<i>_env_step_time.
            step_seconds.flush(timer, rollout_length)

            with span("actor_prepare_data", clock=timer, phase="prepare_data",
                      actor=actor_id):
                # Per leaf, the learner devices' slices as single-device
                # shards for global-array assembly: one transfer a host leaf,
                # one program for the device leaves.
                payload, stored = storage.finish()
            with timer.time("queue_put"):
                try:
                    source.push(actor_id, behavior_version, payload, timeout=60.0)
                except queue.Full:
                    if lifetime.should_stop():
                        break
                    raise
            metrics_sink.put(
                {
                    "episode_metrics": storage.host_copy(stored.info),
                    "timings": {
                        **timer.all_means(prefix=f"actor{actor_id}_"),
                        **timer.all_percentiles(prefix=f"actor{actor_id}_"),
                    },
                }
            )
            rollout_idx += 1


class _ActorMetrics:
    """The learner's side of the actors' metrics sink: episode returns and
    the newest timings, drained EVERY update (the sink is unbounded — letting
    rollouts pile up for a whole inter-eval window grows host memory with its
    length), taken and cleared at eval boundaries."""

    def __init__(self) -> None:
        self.sink: "queue.Queue" = queue.Queue()
        self._returns: List[float] = []
        self._timings: Dict[str, float] = {}

    def drain(self) -> None:
        while not self.sink.empty():
            m = self.sink.get_nowait()
            em = m["episode_metrics"]
            mask = em["is_terminal_step"].reshape(-1)
            if mask.any():
                self._returns.extend(em["episode_return"].reshape(-1)[mask].tolist())
            self._timings.update(m["timings"])

    def take(self) -> tuple:
        self.drain()
        taken = self._returns, self._timings
        self._returns, self._timings = [], {}
        return taken


class _ProfileWindow:
    """STOIX_TPU_PROFILE_DIR=<dir>: a jax.profiler trace around ONE
    steady-state learner update, as the Anakin runner wraps one eval
    window: the update that follows the SECOND eval block (the first
    block's evaluation compiles), opened just before that block is logged
    and its evaluation submitted, so the evaluator's `async_eval` is whole
    inside it. Every thread's spans are TraceAnnotations, so the trace
    holds the actor, learner and evaluator threads on separate host lines
    and the device ops, all on one clock."""

    def __init__(self, config: Any) -> None:
        self._dir = os.environ.get("STOIX_TPU_PROFILE_DIR")
        self._update, self._on = -1, False
        if self._dir:
            self._update = min(
                2 * int(config.arch.num_updates_per_eval), int(config.arch.num_updates) - 1
            )

    def after_update(self, update_idx: int) -> None:
        if self._on:
            self._on = False
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001 — profiling must never kill a run
                pass
        elif update_idx + 1 == self._update:
            try:
                jax.profiler.start_trace(self._dir)
                self._on = True
            except Exception:  # noqa: BLE001
                pass


def _resolve_budget(config: Any, num_actors: int) -> int:
    """Budget accounting (reference total_timestep_checker sebulba branch);
    returns the env steps one update consumes."""
    config.arch.actor.envs_per_actor = int(config.arch.total_num_envs) // num_actors
    steps_per_update = int(config.system.rollout_length) * int(config.arch.total_num_envs)
    if config.arch.get("num_updates") in (None, "~"):
        config.arch.num_updates = max(
            1, int(float(config.arch.total_timesteps)) // steps_per_update
        )
    config.arch.total_timesteps = int(config.arch.num_updates) * steps_per_update
    num_evaluation = max(1, int(config.arch.get("num_evaluation", 1)))
    config.arch.num_updates_per_eval = max(1, int(config.arch.num_updates) // num_evaluation)
    config.logger.system_name = config.system.system_name
    return steps_per_update


def _evaluator_fn(config: Any, env_factory: Callable, eval_apply: Callable, eval_mesh: Any):
    """Evaluation on the dedicated device via the standard sharded evaluator
    when the scenario has a JAX env (registry/suites); stateful backends
    with no JAX twin (EnvPool Atari ids) evaluate on a factory pool instead
    (reference: Sebulba evaluates EnvPool envs on factory envs)."""
    from stoix_tpu.envs import suites
    from stoix_tpu.envs.registry import ENV_REGISTRY, make_single
    from stoix_tpu.envs.wrappers import RecordEpisodeMetrics

    scenario = (
        config.env.scenario.name
        if hasattr(config.env.scenario, "name")
        else config.env.scenario
    )
    suite = getattr(config.env, "env_name", None)
    act_fn = get_distribution_act_fn(config, eval_apply)
    if scenario in ENV_REGISTRY or suite in suites.SUITE_MAKERS:
        # Genuine construction errors must surface — only the known
        # no-JAX-twin case (EnvPool/Gymnasium task ids) falls back.
        eval_env = RecordEpisodeMetrics(
            make_single(scenario, suite=suite, **dict(config.env.get("kwargs", {}) or {}))
        )
        return get_ff_evaluator_fn(eval_env, act_fn, config, eval_mesh)
    return get_stateful_evaluator_fn(env_factory, act_fn, config)


class _Run:
    """One run of a system: `set_up`, then `learn`, `shut_down` (whatever
    happened, however far set-up got) and `close_out`."""

    def __init__(self, config: Any, system: SebulbaSystem) -> None:
        self.host = RunHost(config, "sebulba", "sebulba-pipeline")
        self.config, self.system = config, system
        # What `shut_down` stops and closes, once `set_up` has built them.
        self.logger = self.async_evaluator = self.supervisor = None
        self.actor_threads: List[threading.Thread] = []

    def set_up(self) -> None:
        host, config, system = self.host, self.config, self.system
        setup_phases = host.setup_phases
        # Device assignment through the unified mesh-role abstraction
        # (parallel/roles.py, docs/DESIGN.md §2.11): the actor/learner/evaluator
        # split arrives as one validated MeshRoles object (the same object the
        # Anakin runner, serve, and the population runner consume). The
        # program's first touch of the devices on an operator's path.
        with span("mesh_build", clock=setup_phases, phase="mesh_build"):
            roles = MeshRoles.from_config(config)
            actor_devices = roles.role_devices("act")
            learner_devices = roles.role_devices("learn")
            learner_mesh = roles.learn_mesh()
            # In a multi-host deployment the learner loop exchanges
            # window-indexed stop votes through the jax.distributed KV store
            # (there is no coalesced device fetch to piggyback on here) and
            # fails collects fast on a declared partition.
            host.open_fleet()

            actors_per_device = int(config.arch.actor.actor_per_device)
            num_actors = len(actor_devices) * actors_per_device
            steps_per_update = _resolve_budget(config, num_actors)

        with span("env_build", clock=setup_phases, phase="env_build"):
            # The C++ pool's first build (g++, once a checkout) is in here.
            env_factory = make_factory(config)
            probe_envs = env_factory(1)
            config.system.action_dim = probe_envs.num_actions

        with span("network_init", clock=setup_phases, phase="network_init"):
            key = jax.random.PRNGKey(int(config.arch.seed))
            networks, key = system.init_networks(config, probe_envs, key)

        with span("learner_setup", clock=setup_phases, phase="learner_setup"):
            learner, self.key = system.setup_learner(config, networks, key, learner_mesh)
            self.learner, self.learner_state = learner, learner.state
            if host.sentinel is not None:
                # Sebulba has no coalesced fetch to piggyback fingerprints on, so
                # the learner loop checks the replicated learner state
                # synchronously at each eval boundary (the vector is
                # [num_learner_devices] uint32 — tiny; docs/DESIGN.md §2.9).
                host.sentinel.bind(learner_mesh, learner.state)
                host.sentinel.install_excepthook()

        with span("evaluator_setup", clock=setup_phases, phase="evaluator_setup"):
            eval_fn = _evaluator_fn(
                config, env_factory, learner.eval_apply, roles.role_mesh("evaluate")
            )

        with span("logger_build", clock=setup_phases, phase="logger_build"):
            self.logger = logger = StoixLogger(config)
            self.lifetime = lifetime = ThreadLifetime()
            self.timer = timer = TimingTracker()
            self.source = source = learner.make_source(
                SourceContext(
                    num_actors, learner_devices, learner_mesh, host.fleet, timer, host.ledger,
                    steps_per_update,
                )
            )
            pipeline = source.pipeline
            # One heartbeat board for the whole run: actor beats come from the
            # pipeline, param-server and evaluator beats land on the same board so
            # the stall detector sees every component's age — and /healthz reads the
            # same board through the process-wide health monitor.
            host.open_ops_plane(pipeline.heartbeats, step=0)
            self.param_server = param_server = ParameterServer(
                actor_devices, actors_per_device, heartbeats=pipeline.heartbeats
            )
            self.actor_metrics = actor_metrics = _ActorMetrics()
            self.eval_results = eval_results = []

            def on_eval_result(metrics, params_used, t):
                logger.log(metrics, t, len(eval_results), LogEvent.EVAL)
                eval_results.append(float(jnp.mean(metrics["episode_return"])))

        # Set-up's last phase: from the first thread started to the first
        # completed learner update (the actors' first rollouts and every first
        # compile — act_fn, the learn step — are in it).
        host.first_tick = setup_phases.open_first_tick()
        self.async_evaluator = async_evaluator = AsyncEvaluator(
            eval_fn, lifetime, on_eval_result, heartbeats=pipeline.heartbeats
        )
        async_evaluator.thread.start()

        param_server.distribute_params(learner.actor_params(learner.state))

        # Actor threads are owned by the supervisor (arch.supervision, on by
        # default): a crashed actor is respawned from its factory — fresh thread,
        # fresh env instance, re-primed params — with bounded backoff; past the
        # restart budget (or on a heartbeat wedge) a ComponentFailure poison-pill
        # makes the learner fail fast instead of burning the collect timeout.
        self.supervisor = supervisor = supervisor_from_config(
            config, lifetime, pipeline, param_server
        )
        actor_threads = self.actor_threads

        def _actor_factory(actor_id: int, device) -> Callable[[], threading.Thread]:
            return lambda: threading.Thread(
                target=self._actor_thread, args=(actor_id, device, env_factory, learner_devices),
                name=f"actor-{actor_id}", daemon=True,
            )

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
        host.watch_for_stop()
        self.evaluator_device = roles.device("evaluate")
        # Written by `learn`.
        self.t_steps = 0
        self.run_start_time = 0.0  # whole-run FPS denominator (incl.
        # first-rollout compile — the number a fleet scheduler actually gets)
        self.steady_start_time = None  # set after the first eval block (post-compile)
        self.steady_start_steps = 0
        self.steady_end_time = 0.0

    def _actor_thread(self, actor_id, device, env_factory, learner_devices) -> None:
        config, learner = self.config, self.learner
        try:
            _rollout_body(
                actor_id, device, env_factory, learner.make_act_fn, learner.transition,
                self.source, self.param_server, learner_devices, self.lifetime,
                int(config.arch.seed) + 7919 * actor_id, self.actor_metrics.sink,
                int(config.arch.actor.envs_per_actor), int(config.system.rollout_length),
                TimingTracker(),
            )
        except Exception as exc:
            get_registry().counter(
                "stoix_tpu_sebulba_actor_crashes_total",
                "Actor threads that died with an exception",
            ).inc(labels={"actor": str(actor_id)})
            get_logger("stoix_tpu.sebulba").error(
                "[actor-%d] CRASHED:\n%s", actor_id, traceback.format_exc()
            )
            if self.supervisor is not None:
                # Supervised: restart with backoff, or propagate a typed
                # ComponentFailure poison-pill (resilience/supervisor.py).
                self.supervisor.report_crash(actor_id, exc)
            else:
                self.lifetime.stop()

    def learn(self) -> None:
        """The learner loop: wait for a batch, update, push parameters; at every
        `num_updates_per_eval`-th update log, submit an evaluation and hold the
        window's votes. Nothing in it asks which system or which source runs."""
        config, source, learner, timer = self.config, self.source, self.learner, self.timer
        host, param_server = self.host, self.param_server
        preempt, fleet_coord, sentinel = host.preempt, host.fleet, host.sentinel
        updates_per_eval = int(config.arch.num_updates_per_eval)
        profile = _ProfileWindow(config)
        self.run_start_time = fleet_window_started = time.perf_counter()
        for update_idx in range(int(config.arch.num_updates)):
            batch = source.next_batch(update_idx, param_server)
            with span("learner_update", clock=timer, phase="learn", update=update_idx):
                self.learner_state, train_metrics = source.step(
                    learner.step, self.learner_state, batch
                )
                jax.block_until_ready(train_metrics)
            host.ledger.note(goodput.SEBULBA_PHASE_MAP["learn"], timer.latest("learn"))
            if (update_idx + 1) % source.param_sync_interval == 0:
                param_server.distribute_params(learner.actor_params(self.learner_state))
            if update_idx == 0:
                host.first_tick.close()
            profile.after_update(update_idx)
            source.after_update(self.learner_state)
            self.t_steps += batch.env_steps
            t_steps = self.t_steps
            # Divergence guard, host half: count skipped updates; halt mode
            # raises DivergenceError here (metrics are already materialized
            # by the block_until_ready above — no extra sync).
            guards.publish_guard_metrics(host.guard_mode, train_metrics, t_steps)
            self.actor_metrics.drain()
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
                host.vote_to_stop(f"at update {update_idx}")

            if (update_idx + 1) % updates_per_eval != 0:
                continue
            with span("learner_log", update=update_idx):
                ep_returns, timings = self.actor_metrics.take()
                if ep_returns:
                    self.logger.log({"episode_return": np.asarray(ep_returns)}, t_steps,
                                   update_idx, LogEvent.ACT)
                self.logger.log(jax.tree.map(lambda x: jnp.mean(x), train_metrics),
                               t_steps, update_idx, LogEvent.TRAIN)
                self.logger.log(
                    {
                        **timings,
                        **timer.all_means(prefix="learner_"),
                        **timer.all_percentiles(prefix="learner_"),
                        **source.observe(),
                    },
                    t_steps, update_idx, LogEvent.MISC,
                )
                self.key, ek = jax.random.split(self.key)
                eval_params = jax.device_put(
                    jax.tree.map(np.asarray, learner.eval_params(self.learner_state)),
                    self.evaluator_device,
                )
                self.async_evaluator.submit(eval_params, ek, t_steps)
            if self.steady_start_time is None:
                # Steady-state SPS window opens once compile/warmup has
                # been paid (end of the first eval block).
                self.steady_start_time = time.perf_counter()
                self.steady_start_steps = t_steps
            window_idx = (update_idx + 1) // updates_per_eval
            host.status.update({"window": window_idx, "step": t_steps})
            host.recorder.record(
                "window", window=window_idx, step=t_steps,
                updates=update_idx + 1,
                queue_wait_s=round(timer.mean(source.wait_phase), 6),
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
                corruption = sentinel.check_state(self.learner_state, window_idx, t_steps)
                if corruption is not None and fleet_coord is not None:
                    fleet_coord.request_stop(fleet.FLAG_CORRUPT, note=str(corruption))
            if fleet_coord is not None:
                # Window-boundary agreement: exchange stop votes for THIS
                # window through the KV store — identical decision on
                # every host, so all drain together — and swap straggler
                # wall-times for the skew gauges.
                now = time.perf_counter()
                fleet_coord.observe_window_wall(window_idx, now - fleet_window_started)
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
        # `shut_down` can take tens of seconds and must not deflate the
        # steady-state number.
        self.steady_end_time = time.perf_counter()

    def shut_down(self) -> None:
        """Runs in `run_experiment`'s `finally`, a failure possibly
        propagating, set-up possibly unfinished."""
        self.host.close()
        if self.async_evaluator is not None:  # the first thread `set_up` starts
            self._stop_threads()
        if self.logger is not None:
            self.logger.close()

    def _stop_threads(self) -> None:
        self.lifetime.stop()
        self.param_server.shutdown()
        # Unblock actors waiting to enqueue (uninstrumented: drain gets are
        # teardown artifacts and must not pollute the queue-wait series).
        for _ in range(2):
            if self.source.pipeline.drain(timeout=0.5) == 0:
                break
        if self.supervisor is not None:
            self.supervisor.join_all(timeout=10.0)
        for t in self.actor_threads:
            t.join(timeout=10.0)
        # Capture BEFORE our own try: inside the except block sys.exc_info()
        # would report the stall error itself, not the failure (if any) that
        # brought us into this finally.
        failure_propagating = sys.exc_info()[0] is not None
        try:
            self.async_evaluator.wait_until_idle(timeout=120.0)
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

    def close_out(self) -> float:
        """`LAST_RUN_STATS` and the last evaluation's return."""
        t_steps = self.t_steps
        if self.steady_start_time is not None and t_steps > self.steady_start_steps:
            steady = (t_steps - self.steady_start_steps) / (
                self.steady_end_time - self.steady_start_time
            )
            get_registry().gauge(
                "stoix_tpu_sebulba_steps_per_sec_steady",
                "Post-compile steady-state env-steps/sec of the most recent run",
            ).set(steady)
            LAST_RUN_STATS["steps_per_sec_steady"] = steady
            LAST_RUN_STATS["steady_window_steps"] = t_steps - self.steady_start_steps
        if t_steps > 0:
            # Whole-run env frames per second (ROADMAP item-1 leftover): total
            # env steps over the full learner-loop wall INCLUDING first-rollout
            # compile — the steady number above excludes it by design; this one
            # is what a scheduler provisioning actor fleets observes. First-class
            # in the bench --sebulba payload as `fps` (+ rep dispersion).
            fps = t_steps / max(self.steady_end_time - self.run_start_time, 1e-9)
            get_registry().gauge(
                "stoix_tpu_sebulba_fps",
                "Whole-run env-steps/sec (incl. compile) of the most recent run",
            ).set(fps)
            LAST_RUN_STATS["fps"] = fps
            LAST_RUN_STATS["total_env_steps"] = t_steps
        # queue_wait/compute were noted per update; the residual learner-loop wall
        # (host work concurrent with actor rollouts, teardown joins) goes to
        # compute, so the goodput fractions sum to 1.
        supervisor = self.supervisor
        LAST_RUN_STATS.update(
            self.host.run_stats(
                self.host.preempt.stop_requested(),
                actor_restarts=supervisor.restart_count() if supervisor is not None else 0,
                # Sebulba has no checkpoint path yet: a preemption stops cleanly
                # but cannot resume mid-run.
                resume_capable=False,
            )
        )
        LAST_RUN_STATS.update(self.source.run_stats())
        return self.eval_results[-1] if self.eval_results else 0.0


def run_experiment(config: Any, system: SebulbaSystem) -> float:
    LAST_RUN_STATS.clear()
    run = _Run(config, system)
    try:
        run.set_up()
        with run.host.interrupt_as_partition():
            run.learn()
    finally:
        run.shut_down()
    return run.close_out()
