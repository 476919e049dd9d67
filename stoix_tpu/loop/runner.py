"""Closed-loop runner: train → serve → experience, self-healing
(docs/DESIGN.md §2.15).

The composition root for `launcher.py loop` and `bench.py --loop`. One
process hosts the whole production loop:

  traffic driver ──▶ FleetRouter ──▶ N PolicyServer replicas
        │                                   ▲
        ▼                                   │ FleetPublisher (canary,
  ExperienceRecorder ──▶ OffPolicyPipeline  │  fleet-wide rollback)
                              │             │
                              ▼             │
                    ShardedReplayService ──▶ LoopLearner ──▶ Checkpointer

The traffic driver plays REAL episodes: one functional env instance per
simulated user, each round submitting every user's observation through the
router, stepping the env with the served (sampled — the loop config serves
greedy=false) action, and recording the transition. Episode returns are the
ground truth for the policy-improves-under-live-traffic bench: the live arm
must beat the `frozen=True` control arm at matched offered QPS.

Failure handling is first-class: `replica_kill:N` hard-closes replica N
mid-traffic (in-flight requests fail over; the runner restarts the replica
after a cooldown and the router re-admits it — self-healing),
`replica_slow:S` drags one replica's batches (hedging territory), and
`feedback_stall:S` wedges the recorder feeder (the serve path must not
notice). Accounting is zero-silent-drop by construction: every ACCEPTED
request is resolved to exactly one of completed / typed failure, and the
report asserts `accepted == completed + typed_failures`.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from stoix_tpu.base_types import Transition
from stoix_tpu.loop.errors import FleetUnavailableError
from stoix_tpu.loop.learner import LoopLearner
from stoix_tpu.loop.publisher import FleetPublisher
from stoix_tpu.loop.recorder import ExperienceRecorder
from stoix_tpu.loop.router import DirectRouter, FleetRouter
from stoix_tpu.observability import get_logger, get_status_board
from stoix_tpu.parallel.mesh import create_mesh
from stoix_tpu.replay import ShardedReplayService
from stoix_tpu.resilience import faultinject
from stoix_tpu.sebulba.core import OffPolicyPipeline
from stoix_tpu.serve import PolicyServer
from stoix_tpu.serve import checkpoint as serve_checkpoint
from stoix_tpu.serve.client import RetryBudgetExhaustedError, policy_from_config
from stoix_tpu.serve.errors import ServeError
from stoix_tpu.utils import compilecache
from stoix_tpu.utils.checkpointing import Checkpointer
from stoix_tpu.utils.timing import TimingTracker


def _host(tree: Any) -> Any:
    return jax.tree.map(np.asarray, tree)


class _UserStream:
    """One simulated user: a functional env instance stepped with served
    actions. Pure host-side state; the jitted reset/step are shared."""

    def __init__(self, uid: int, reset_j: Any, step_j: Any, seed: int):
        self.uid = uid
        self._reset_j = reset_j
        self._step_j = step_j
        self._key = jax.random.PRNGKey(seed)
        self.episode_return = 0.0
        self._key, reset_key = jax.random.split(self._key)
        self.state, timestep = reset_j(reset_key)
        self.obs = _host(timestep.observation)

    def advance(self, action: int) -> Dict[str, Any]:
        """Step the env with the served action; returns the recorded
        transition fields plus the completed-episode return (or None)."""
        prev_obs = self.obs
        self.state, timestep = self._step_j(self.state, np.int32(action))
        reward = float(np.asarray(timestep.reward))
        done = bool(np.asarray(timestep.last()))
        next_obs = _host(timestep.observation)
        self.episode_return += reward
        finished: Optional[float] = None
        if done:
            finished = self.episode_return
            self.episode_return = 0.0
            self._key, reset_key = jax.random.split(self._key)
            self.state, timestep = self._reset_j(reset_key)
            next_obs = _host(timestep.observation)
        self.obs = next_obs
        return {
            "obs": prev_obs,
            "action": np.int32(action),
            "reward": np.float32(reward),
            "done": np.asarray(done),
            "next_obs": next_obs,
            "finished_return": finished,
        }


def _build_replica(
    bundle: Any, serve_cfg: Any, ordinal: int, seed: int, params: Any = None
) -> PolicyServer:
    batching = serve_cfg.batching
    return PolicyServer(
        apply_fn=bundle.apply_fn,
        params=bundle.params if params is None else params,
        obs_template=bundle.obs_template,
        buckets=[int(b) for b in batching.buckets],
        max_wait_s=float(batching.max_wait_ms) / 1000.0,
        max_queue=int(batching.max_queue),
        greedy=bool(serve_cfg.greedy),
        key=jax.random.PRNGKey(seed),
        compile_deadline_s=float(serve_cfg.compile_deadline_s),
        name=f"loop_replica{ordinal}",
        replica_id=ordinal,
    )


def _store_saver(store_path: str, publish_stride: int) -> Checkpointer:
    """A Checkpointer writing INTO the store the fleet's PolicySource reads:
    store layout is <rel_dir>/<uid>/<model_name> (utils/checkpointing.py), so
    decompose the path back into the ctor's three pieces."""
    path = os.path.abspath(store_path)
    model_name = os.path.basename(path)
    uid = os.path.basename(os.path.dirname(path))
    rel_dir = os.path.dirname(os.path.dirname(path))
    return Checkpointer(
        model_name,
        rel_dir=rel_dir,
        checkpoint_uid=uid,
        save_interval_steps=max(1, int(publish_stride)),
        max_to_keep=None,
    )


def run_loop(config: Any, frozen: bool = False) -> Dict[str, Any]:
    """Run the closed loop for `arch.loop.traffic.duration_s` seconds and
    return the report dict (the `launcher loop` / `bench --loop` payload).

    `frozen=True` is the control arm: identical traffic, recording, and
    ingest load, but the learner never updates and nothing is published — the
    live-vs-frozen end-return delta isolates policy improvement."""
    from stoix_tpu import envs
    from stoix_tpu.systems.anakin import broadcast_to_update_batch

    log = get_logger("stoix_tpu.loop")
    serve_cfg = config.arch.serve
    loop_cfg = config.arch.loop
    fleet_cfg = loop_cfg.fleet
    router_cfg = fleet_cfg.router
    recorder_cfg = loop_cfg.recorder
    replay_cfg = loop_cfg.replay
    learner_cfg = loop_cfg.learner
    traffic_cfg = loop_cfg.traffic

    # Compile economy (docs/DESIGN.md §2.7): before the first compile.
    compilecache.configure(config)
    bundle = serve_checkpoint.load_policy(config)
    learner_on = bool(learner_cfg.enabled) and not frozen
    if bool(bundle.train_config.system.get("normalize_observations", False)):
        raise ValueError(
            "the loop learner trains on raw observations: serve a policy "
            "trained with normalize_observations=false (identity_game ff_ppo "
            "default) or disable the learner (arch.loop.learner.enabled=false)"
        )

    n_replicas = int(fleet_cfg.replicas)
    router_on = bool(router_cfg.enabled)
    if not router_on and n_replicas != 1:
        raise ValueError(
            f"router disabled requires exactly 1 replica, got {n_replicas} "
            "(arch.loop.fleet.router.enabled=false is the pinned single-"
            "server pass-through)"
        )
    seed = int(serve_cfg.get("seed", 0))
    servers: List[PolicyServer] = [
        _build_replica(bundle, serve_cfg, i, seed + i) for i in range(n_replicas)
    ]

    # Replay spine: a data-parallel mesh over the first `shards` devices.
    shards = int(replay_cfg.shards)
    mesh = create_mesh({"data": shards}, devices=jax.devices()[:shards])
    flush_batch = int(recorder_cfg.flush_batch)
    sample_batch = int(replay_cfg.sample_batch_size)
    if flush_batch % shards or sample_batch % shards:
        raise ValueError(
            f"recorder.flush_batch ({flush_batch}) and replay.sample_batch_size "
            f"({sample_batch}) must both divide by replay.shards ({shards})"
        )
    item = Transition(
        obs=_host(bundle.obs_template),
        action=np.int32(0),
        reward=np.float32(0.0),
        done=np.asarray(False),
        next_obs=_host(bundle.obs_template),
        info={},
    )
    service = ShardedReplayService(
        mesh,
        item,
        capacity_per_shard=int(replay_cfg.capacity_per_shard),
        sample_batch_size=sample_batch,
        min_fill=int(replay_cfg.min_fill),
    )
    pipeline = OffPolicyPipeline(num_actors=1)
    recorder = ExperienceRecorder(
        pipeline,
        flush_batch=flush_batch,
        capacity=int(recorder_cfg.capacity),
        push_timeout_s=float(recorder_cfg.push_timeout_s),
    )
    learner = LoopLearner(
        bundle.apply_fn,
        bundle.params,
        service,
        pipeline,
        learning_rate=float(learner_cfg.learning_rate),
        frozen=not learner_on,
        seed=seed,
    )
    publisher = FleetPublisher(
        servers, bundle.source, bundle.step, canary=bool(learner_cfg.canary)
    )
    publish_interval_s = float(learner_cfg.publish_interval_s)
    step_stride = int(learner_cfg.step_stride)
    update_batch = int(bundle.train_config.arch.get("update_batch_size", 1))
    saver = (
        _store_saver(str(serve_cfg.checkpoint.path), step_stride)
        if learner_on
        else None
    )

    # The traffic driver plays the TRAINING env (raw, unwrapped: resets are
    # explicit because episode boundaries are the reward signal).
    env_cfg = bundle.train_config.env
    env = envs.make_single(
        env_cfg.scenario.name,
        suite=env_cfg.get("env_name"),
        **dict(env_cfg.get("kwargs") or {}),
    )
    reset_j = jax.jit(env.reset)
    step_j = jax.jit(env.step)

    for server in servers:
        server.start()
    if router_on:
        router: Any = FleetRouter(
            servers,
            retry=policy_from_config(dict(router_cfg.get("retry") or {})),
            hedge_after_s=(
                float(router_cfg.hedge_ms) / 1000.0
                if router_cfg.get("hedge_ms") is not None
                else None
            ),
            readmit_cooldown_s=float(router_cfg.readmit_cooldown_s),
            max_failovers=int(router_cfg.max_failovers),
        ).register_status()
    else:
        router = DirectRouter(servers[0])
    get_status_board().register_provider(
        "loop_pipeline",
        lambda: {
            "recorder": recorder.stats(),
            "learner": learner.stats(),
            "publisher": publisher.stats(),
        },
    )
    recorder.start()
    learner.start()

    users = [
        _UserStream(u, reset_j, step_j, seed=seed + 1000 + u)
        for u in range(int(traffic_cfg.users))
    ]
    offered_qps = float(traffic_cfg.offered_qps)
    duration_s = float(traffic_cfg.duration_s)
    result_timeout_s = float(traffic_cfg.result_timeout_s)
    last_window_frac = float(traffic_cfg.last_window_frac)
    round_interval = len(users) / max(offered_qps, 1e-6)
    restart_cooldown_s = float(fleet_cfg.restart_cooldown_s)

    accepted = 0
    completed = 0
    typed_failures = 0
    rejected = 0
    n_kills = 0
    n_restarts = 0
    episodes: List[tuple] = []
    restart_due: Dict[int, float] = {}
    tracker = TimingTracker(maxlen=1 << 16)
    last_publish_t = 0.0
    updates_at_publish = 0
    publish_step = int(bundle.step)

    def _fleet_params() -> Any:
        """Best healthy replica's installed params — a restarted replica
        joins at the CURRENT serving step, not the boot checkpoint."""
        for server in servers:
            if server.healthy():
                return server.engine.get_params()
        return bundle.params

    start = time.perf_counter()
    deadline = start + duration_s
    round_idx = 0
    fleet_stats: Optional[Dict[str, Any]] = None
    try:
        while time.perf_counter() < deadline:
            now = time.perf_counter()
            router.tick()

            # -- self-healing: rebuild replicas whose restart cooldown expired.
            for ordinal in [o for o, due in restart_due.items() if now >= due]:
                del restart_due[ordinal]
                replacement = _build_replica(
                    bundle,
                    serve_cfg,
                    ordinal,
                    seed + ordinal + 1000 * (n_restarts + 1),
                    params=_fleet_params(),
                )
                replacement.start()
                servers[ordinal] = replacement
                router.replace(ordinal, replacement)
                publisher.rebind(ordinal, replacement)
                n_restarts += 1
                log.info("[loop] replica %d restarted (self-heal)", ordinal)

            # -- publish cadence: checkpoint the learner, push fleet-wide.
            # The save is gated on fresh learner updates; the PUSH attempt is
            # not — Checkpointer.save is asynchronous, so the step may only
            # become visible to latest_step() a tick or two later, and a push
            # gated on the NEXT update would strand starved runs on the boot
            # checkpoint. publish() is a cheap no-op while nothing new is
            # visible.
            if learner_on and now - last_publish_t >= publish_interval_s:
                last_publish_t = now
                if learner.n_updates > updates_at_publish:
                    updates_at_publish = learner.n_updates
                    publish_step += step_stride
                    saver.save(
                        publish_step,
                        {
                            "params": {
                                "actor_params": broadcast_to_update_batch(
                                    learner.params, update_batch
                                )
                            }
                        },
                        force=True,
                    )
                publisher.publish()

            # -- one traffic round: submit every user, then collect.
            in_flight = []
            for user in users:
                try:
                    in_flight.append((user, router.submit(user.obs)))
                    accepted += 1
                except (FleetUnavailableError, RetryBudgetExhaustedError):
                    rejected += 1
                except ServeError:
                    rejected += 1
            # -- chaos: hard-kill a replica WITH the round in flight (the
            # worst case — accepted requests on the victim must fail over,
            # not vanish) and schedule its self-healing restart.
            victim = faultinject.consume_replica_kill()
            if victim is not None and router_on and 0 <= victim < n_replicas:
                log.warning("[loop] replica_kill: crashing replica %d", victim)
                servers[victim].kill()
                n_kills += 1
                restart_due[victim] = time.perf_counter() + restart_cooldown_s

            for user, fut in in_flight:
                try:
                    result = fut.result(timeout=result_timeout_s)
                except ServeError:
                    # Typed, counted — the observation is retried next round.
                    typed_failures += 1
                    continue
                completed += 1
                tracker.record("latency", float(fut.latency_s))
                outcome = user.advance(int(np.asarray(result.action)))
                recorder.record(
                    Transition(
                        obs=outcome["obs"],
                        action=outcome["action"],
                        reward=outcome["reward"],
                        done=outcome["done"],
                        next_obs=outcome["next_obs"],
                        info={},
                    )
                )
                if outcome["finished_return"] is not None:
                    episodes.append(
                        (time.perf_counter() - start, outcome["finished_return"])
                    )

            round_idx += 1
            next_round = start + round_idx * round_interval
            sleep_s = next_round - time.perf_counter()
            if sleep_s > 0:
                time.sleep(sleep_s)
        # Final drain: quiesce the feed side FIRST (stop() is idempotent with
        # the teardown below; the learner join lets an in-flight update — on
        # a stalled/starved run often the ONLY update — finish counting),
        # then flush the asynchronous save and give the result one last
        # fleet push, so a short or CPU-starved run still publishes what it
        # learned. A push the fleet rejects (e.g. a poisoned candidate
        # rolled back) gets the one retry the next cadence tick would have
        # given it.
        recorder.stop()
        learner.stop()
        if learner_on and learner.n_updates > 0:
            if learner.n_updates > updates_at_publish:
                updates_at_publish = learner.n_updates
                publish_step += step_stride
                saver.save(
                    publish_step,
                    {
                        "params": {
                            "actor_params": broadcast_to_update_batch(
                                learner.params, update_batch
                            )
                        }
                    },
                    force=True,
                )
            saver.wait()
            if publisher.publish() is None:
                publisher.publish()
        # Snapshot fleet health BEFORE teardown closes the replicas.
        fleet_stats = router.stats()
    finally:
        recorder.stop()
        learner.stop()
        pipeline.drain()
        if saver is not None:
            saver.close()
        get_status_board().unregister_provider("loop_pipeline")
        if router_on:
            router.unregister_status()
        for server in servers:
            server.close()

    elapsed = time.perf_counter() - start
    silent_drops = accepted - completed - typed_failures
    returns = [ep_return for _t, ep_return in episodes]
    window_start = elapsed * (1.0 - last_window_frac)
    window_returns = [r for t, r in episodes if t >= window_start] or returns
    percentiles = tracker.percentiles("latency")
    report: Dict[str, Any] = {
        "mode": "loop",
        "frozen": bool(frozen),
        "router": "fleet" if router_on else "direct",
        "replicas": n_replicas,
        "duration_s": round(elapsed, 3),
        "offered_qps": round(accepted / elapsed, 2) if elapsed > 0 else 0.0,
        "achieved_qps": round(completed / elapsed, 2) if elapsed > 0 else 0.0,
        "accepted": accepted,
        "completed": completed,
        "typed_failures": typed_failures,
        "rejected": rejected,
        "silent_drops": silent_drops,
        "latency_ms": {
            name: round(value * 1000.0, 3) for name, value in percentiles.items()
        },
        "episodes": len(episodes),
        "return_mean": round(float(np.mean(returns)), 4) if returns else None,
        "return_mean_last_window": (
            round(float(np.mean(window_returns)), 4) if window_returns else None
        ),
        "serving_step": publisher.current_step,
        "replica_kills": n_kills,
        "replica_restarts": n_restarts,
        "router_stats": fleet_stats if fleet_stats is not None else router.stats(),
        "recorder": recorder.stats(),
        "learner": learner.stats(),
        "publisher": publisher.stats(),
    }
    if silent_drops:
        log.error(
            "[loop] ACCOUNTING VIOLATION: %d accepted request(s) neither "
            "completed nor failed typed", silent_drops,
        )
    return report
