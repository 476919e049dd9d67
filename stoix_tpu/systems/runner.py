"""The Anakin host loop, shared by every Anakin system: a one-window-deep
pipelined dispatcher (docs/DESIGN.md §2.1). A system file keeps its learner
(`get_learner_fn`) and its set-up (`learner_setup`); what is the same for all
of them — set-up's order, the loop, the teardown — is `_Run`, behind
`run_anakin_experiment`. What a run opens and closes on the host beside the
loop is the run host's (`stoix_tpu/run_host.py`, §2.16).

Per eval window the loop DISPATCHES

    learn_k -> gossip_k -> snapshot_k (on-device params/state copies) -> eval_k
            -> fetch_k (ONE coalesced collective over episode+train+eval
               metrics)

and only THEN processes window k-1 on the host (materialize the metrics, log,
track the best params, hand the checkpoint snapshot to orbax), so that host
work overlaps the device executing window k. Three invariants make it legal:

  * Donation stays legal: `snapshot_k` is a fresh on-device copy taken from
    the stream BEFORE `learn_{k+1}` is dispatched, so eval, best-params
    tracking and orbax serialization read buffers no later program donates,
    and an async save needs no `checkpointer.wait()` on the hot path.
  * The dispatch sequence equals the synchronous loop's: the `learn` calls,
    their inputs and the per-window eval key splits are the same, so training
    is bit-identical (`arch.pipelined_loop=false` processes each window at
    once; tests/test_runner_pipeline.py pins trajectory equality).
  * The learner is compiled before the timed loop (`warm_up`), so the first
    window's logged steps_per_second holds no XLA compile;
    `LAST_RUN_STATS["steady_state_sps"]` is the rate after the first window.

Every statement of the main thread between two window completions runs in a
`span` that feeds the phase clock (§2.2), so the phases sum to the loop's wall
(`LAST_RUN_STATS["loop_wall_s"]`). In the pipelined loop they are HOST
attribution: device time in learn/eval surfaces as fetch_s (the materialize
wait), while learn_s/eval_s shrink to dispatch cost.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, NamedTuple, Optional

_IMPORTS_BEGAN = time.perf_counter()  # set-up's phase `imports`: this block's seconds

import jax
import jax.numpy as jnp

from stoix_tpu import envs
from stoix_tpu.evaluator import evaluator_setup, per_episode_evaluator_setup
from stoix_tpu.observability import (
    HeartbeatBoard,
    RunStats,
    get_logger,
    get_ops_server,
    get_registry,
    goodput,
    span,
)
from stoix_tpu.observability import aggregate as fleet_metrics
from stoix_tpu.observability.trace import LAUNCH as setup_launch
from stoix_tpu.parallel import (
    MeshRoles,
    fetch_global,
    fetch_global_async,
    is_coordinator,
    materialize,
    maybe_initialize_distributed,
)
from stoix_tpu.resilience import elastic, faultinject, fleet, guards, preflight
from stoix_tpu.run_host import RunHost
from stoix_tpu.utils import compilecache
from stoix_tpu.utils.checkpointing import Checkpointer, checkpointer_from_config
from stoix_tpu.utils.jax_utils import aot_warmup
from stoix_tpu.utils.logger import LogEvent, StoixLogger
from stoix_tpu.utils.timestep_checker import check_total_timesteps

setup_launch.note_imports(_IMPORTS_BEGAN, time.perf_counter())

# Stats of the most recent run_anakin_experiment call of this process:
# phase_breakdown {compile_s, learn_s, snapshot_s, eval_s, fetch_dispatch_s,
# fetch_s, log_s, host_s, ckpt_s [, gossip_s]}, loop_wall_s, steady_state_sps,
# pipelined, fused_eval, compile, gossip, and the run host's blocks. Read by
# the benchmark's drivers, chip_smoke.py, bench.py and the tests. The series
# live in the process-wide metrics registry (stoix_tpu_runner_*, the source of
# truth); this dict-compatible view is refreshed at run end.
LAST_RUN_STATS = RunStats()

_PHASE_NAMES = (
    "compile_s", "learn_s", "gossip_s", "snapshot_s", "eval_s", "fetch_dispatch_s",
    "fetch_s", "log_s", "host_s", "ckpt_s",
)


class _PhaseClock:
    """Per-run view over the cumulative registry phase counter: records into
    `stoix_tpu_runner_phase_seconds_total{phase=...}` and the run's goodput
    ledger, and reports this run's deltas (the registry is process-wide;
    LAST_RUN_STATS is per-run)."""

    def __init__(self, ledger: goodput.GoodputLedger) -> None:
        self._ledger = ledger
        self._counter = get_registry().counter(
            "stoix_tpu_runner_phase_seconds_total",
            "Cumulative Anakin host-loop wall time per phase",
        )
        self._base = {
            name: self._counter.value({"phase": name}) for name in _PHASE_NAMES
        }
        self._touched: set = set()

    def record(self, name: str, seconds: float) -> None:
        """The `span(..., clock=phases, phase=name)` sink. The goodput ledger
        is told as the span closes (it drops what set-up's wall covers)."""
        self._touched.add(name)
        self._counter.inc(seconds, {"phase": name})
        self._ledger.note(goodput.RUNNER_PHASE_MAP.get(name, name), seconds)

    def breakdown(self) -> dict:
        # gossip_s appears only in runs that dispatched a gossip step; lockstep
        # runs keep the schema the observability contract tests pin.
        return {
            name: self._counter.value({"phase": name}) - self._base[name]
            for name in _PHASE_NAMES
            if name != "gossip_s" or name in self._touched
        }


class AnakinSetup(NamedTuple):
    """What a system's learner_setup returns to the shared runner."""

    learn: Callable[[Any], Any]  # jitted shard_mapped learner
    learner_state: Any
    eval_act_fn: Callable[..., Any]  # act_fn for the evaluator
    eval_params_fn: Callable[[Any], Any]  # learner_state -> params for eval
    # Optional GossipPlan (parallel/gossip.py, docs/DESIGN.md §2.12): when its
    # step is set, the runner dispatches it every plan.interval windows right
    # after the learn dispatch. None (the default) = lockstep — the field
    # defaults keep older setups (and _replace-based wrappers) source-compatible.
    gossip: Any = None
    # Optional elastic-restore seam (docs/DESIGN.md §2.14): a transform over
    # the emergency store's digest-verified host arrays, applied BEFORE
    # tree-path placement. The population setup installs its shrink/grow
    # member re-placement here; None = restore the store as saved.
    restore_transform: Any = None


SetupFn = Callable[[envs.Environment, Any, Any, jax.Array], AnakinSetup]


class _Window(NamedTuple):
    """Everything dispatched for one eval window, processed one iteration
    later (pipelined) or immediately (synchronous)."""

    eval_idx: int
    t: int  # global env-step count at window end
    snapshot: Any  # on-device copy of eval params (donation-safe); None when nothing keeps them
    ckpt_state: Any  # on-device copy of the full learner state, or None
    metrics: Any  # ONE coalesced device tree: episode/train/eval metrics


# ONE jit instance so per-window snapshot copies hit the compile cache
# (jax.jit memoizes per input tree structure/avals).
_TREE_COPY = jax.jit(lambda t: jax.tree.map(jnp.copy, t))


def _tree_copy(tree: Any) -> Any:
    """On-device snapshot: a jitted whole-tree copy (shardings preserved).
    The copy is enqueued in the device stream BEFORE the next learn dispatch,
    so donating the source buffers afterwards is legal."""
    return _TREE_COPY(tree)


class _Run:
    """One run of a system: `set_up`, `warm_up`, `learn`, then `shut_down`
    (whatever happened, however far set-up got) and `close_out`."""

    def __init__(self, config: Any) -> None:
        self.host = RunHost(config, "anakin", "anakin-host-loop")
        self.config = config
        # What `shut_down` closes, once `set_up` has built them.
        self.aggregator = self.checkpointer = self.logger = None

    def set_up(
        self, setup_fn: SetupFn, warmup_fn: Optional[Callable], evaluator_setup_fn: Callable
    ) -> None:
        host, config, setup_phases = self.host, self.config, self.host.setup_phases
        # The program's first touch of the devices on an operator's path (the
        # backend starts here unless an import already started it).
        with span("mesh_build", clock=setup_phases, phase="mesh_build"):
            maybe_initialize_distributed(config)
            # Anakin's learn role owns the whole `arch.mesh` (colocated
            # act/learn/evaluate; parallel/roles.py, docs/DESIGN.md §2.11); the
            # population runner's ("pop", "data") mesh arrives the same way.
            self.mesh = mesh = MeshRoles.from_config(config).learn_mesh()
            host.open_fleet()
            config = check_total_timesteps(config, int(mesh.shape["data"]))
            config.logger.system_name = config.system.system_name

        with span("env_build", clock=setup_phases, phase="env_build"):
            env, eval_env = envs.make(config)

        # The process's first eager programs, unless an import ran some.
        with span("rng_key", clock=setup_phases, phase="rng_key"):
            key = jax.random.PRNGKey(int(config.arch.seed))
            self.key, setup_key = jax.random.split(key)
        # Network init and the learner's build are both the system's own
        # learner_setup; the systems mark `network_init` inside it.
        with span("learner_setup", clock=setup_phases, phase="learner_setup"):
            self.setup = setup = setup_fn(env, config, mesh, setup_key)
        self.learner_state = setup.learner_state

        if warmup_fn is not None:
            with span("state_warmup", clock=setup_phases, phase="state_warmup"):
                self.learner_state = warmup_fn(self.learner_state)
                jax.block_until_ready(jax.tree.leaves(self.learner_state)[0])

        self.start_step, self.restore_report = 0, []
        if config.logger.checkpointing.get("load_model", False):
            self._restore()

        make_evaluators = evaluator_setup_fn or evaluator_setup
        with span("evaluator_setup", clock=setup_phases, phase="evaluator_setup"):
            self.evaluator, self.absolute_evaluator = make_evaluators(
                eval_env, setup.eval_act_fn, config, mesh
            )
        with span("logger_build", clock=setup_phases, phase="logger_build"):
            self.logger = StoixLogger(config)
            self.checkpointer = checkpointer_from_config(config, config.system.system_name)
            # /healthz source: the loop beats once per window, so an injected
            # host_stall (or a wedged loop) lets the age cross stale_after_s.
            self.loop_beats = HeartbeatBoard()
            host.open_ops_plane(
                self.loop_beats,
                step=self.start_step,
                restore_skipped=len(self.restore_report),
                last_restore_report=self.restore_report,
                quarantine_file=dict(config.arch.get("integrity") or {}).get(
                    "quarantine_file", "checkpoints/quarantine.json"
                ),
            )
            self.ops_server = get_ops_server()
            if self.ops_server is not None and host.fleet is not None:
                # Host-level metric federation over the fleet KV store: publish
                # this host's snapshots off the hot path; /metrics/fleet folds
                # every host's newest blob with per-host labels (aggregate.py).
                self.aggregator = fleet_metrics.aggregator_from_fleet(
                    host.fleet,
                    interval_s=float(host.http_cfg.get("aggregate_interval_s", 10.0) or 10.0),
                )
                if self.aggregator is not None:
                    self.aggregator.start()
                    self.ops_server.set_aggregator(self.aggregator)

            if host.sentinel is not None:
                # Bind AFTER restore: the fingerprint program is built once for
                # this mesh + state structure (never per window — STX012). The
                # resume info points a rc-88 relaunch at THIS run's orbax store,
                # whose newest digest-verified step is the recovery target.
                host.sentinel.bind(mesh, self.learner_state)
                if self.checkpointer is not None:
                    host.sentinel.set_resume_info(self.checkpointer.directory)
                host.sentinel.install_excepthook()

    def _restore(self) -> None:
        """Resume: restore a saved learner state into the freshly built
        (correctly sharded) template (reference ff_ppo.py:504-512). Topology-
        elastic (utils/checkpointing.py): a checkpoint of an 8-device mesh
        resumes on 1 device and back with bit-identical params."""
        config, setup_phases = self.config, self.host.setup_phases
        with span("restore", clock=setup_phases, phase="restore"):
            load_args = config.logger.checkpointing.get("load_args") or {}
            load_path = load_args.get("load_path")
            if load_path and fleet.is_emergency_store(load_path):
                # A fleet local-shard emergency store (a partition survivor's
                # rescue save, docs/DESIGN.md §2.6): restore through the same
                # tree-path placement as the topology-elastic path — params
                # round-trip bit-identical onto the (possibly shrunk) new mesh.
                self.learner_state, self.start_step = fleet.restore_emergency(
                    self.learner_state, load_path,
                    raw_transform=getattr(self.setup, "restore_transform", None),
                )
            else:
                loader = Checkpointer(
                    model_name=config.system.system_name,
                    rel_dir=load_path or "checkpoints",
                    checkpoint_uid=load_args.get("checkpoint_uid"),
                )
                loader.check_version()
                self.learner_state, self.start_step = loader.restore(
                    self.learner_state, load_args.get("timestep")
                )
                # The newer-but-unusable checkpoints the fallback walk rejected
                # (with typed reasons — structure / non_finite / digest).
                self.restore_report = list(loader.last_restore_report)
        # Restore wall time is recovery, not compute: a relaunch spending
        # minutes re-reading checkpoints must show up in the badput ledger.
        self.host.ledger.note("recovery", setup_phases.seconds()["restore"])
        if is_coordinator():
            get_logger("stoix_tpu.checkpoint").info(
                "[checkpoint] restored state from step %d%s", self.start_step,
                f" ({len(self.restore_report)} newer checkpoint(s) rejected)"
                if self.restore_report else "",
            )

    def warm_up(self) -> None:
        """The loop's plan, and every program of it that can be compiled
        ahead: the first window's steps_per_second is then throughput."""
        host, config, setup, pf = self.host, self.config, self.setup, self.host.preflight
        self.steps_per_eval = (
            int(config.system.rollout_length)
            * int(config.arch.total_num_envs)
            * int(config.arch.num_updates_per_eval)
        )
        self.num_evaluation = int(config.arch.num_evaluation)

        self.pipelined = bool(config.arch.get("pipelined_loop", True))
        # arch.fused_eval folds a fusion-capable (FF) evaluator INTO the jitted
        # learn program — one XLA launch per window; RNN/stateful evaluators
        # take the snapshot-overlap path.
        self.fused = fused = bool(config.arch.get("fused_eval", False)) and getattr(
            self.evaluator, "supports_fusion", False
        )
        # arch.ckpt_snapshot=false: memory fallback for states too big to copy
        # (off-policy replay buffers near HBM capacity). No on-device snapshot
        # is taken; the loop runs synchronously and saves the LIVE state +
        # wait() before the next donating dispatch.
        self.snapshot_ckpt = bool(config.arch.get("ckpt_snapshot", True))
        if self.checkpointer is not None and not self.snapshot_ckpt:
            self.pipelined = False

        self.learn_step = setup.learn
        # Gossip groups (parallel/gossip.py, docs/DESIGN.md §2.12): the mixing
        # step the grouped setup returned, dispatched through this same
        # pipelined stream every `interval` windows so it overlaps the next
        # window's host work like any other device program. step=None covers
        # both lockstep setups and the single-group identity short-circuit that
        # keeps group:1 bitwise-lockstep.
        self.gossip_plan = plan = getattr(setup, "gossip", None)
        self.gossip_step = plan.step if plan is not None else None
        self.gossip_rounds = 0
        self.phases = phases = _PhaseClock(host.ledger)

        if fused:
            # One XLA program per window: learn + eval-params selection + the FF
            # evaluator, donated like the bare learner. The system's jit wrapper
            # is unwrapped so donation lives ONLY on this outer jit.
            learn_inner = getattr(setup.learn, "__wrapped__", setup.learn)
            evaluator = self.evaluator
            donate = {} if os.environ.get("STOIX_TPU_NO_DONATE") else {"donate_argnums": (0,)}

            def _fused_step(state: Any, eval_key: jax.Array):
                output = learn_inner(state)
                eval_metrics = evaluator(setup.eval_params_fn(output.learner_state), eval_key)
                return output, eval_metrics

            self.fused_step = jax.jit(_fused_step, **donate)

        # With preflight on, the compile runs under a deadline watchdog (a
        # wedged backend raises CompileStallError with a full stack dump instead
        # of hanging). With `arch.compile_cache.export_dir` set, the non-fused
        # learner round-trips the jax.export AOT store (docs/DESIGN.md §2.7): a
        # matching serialized artifact skips trace+lower here, and a miss
        # serializes this compile for peer hosts.
        export_dir = compilecache.settings_from_config(config)["export_dir"]
        cache_before = compilecache.cache_stats()
        aot_info = {"source": "compile", "export_path": None}
        with span("aot_warmup", clock=phases, phase="compile_s", fused=fused):
            with host.watchdog("first_compile", pf.compile_deadline_s):
                faultinject.maybe_slow_compile()
                if fused:
                    # Aval-identical stand-in for the per-window eval keys. (The
                    # fused program embeds the evaluator, so it is not served by
                    # the learn-function export store.)
                    example_key = jax.random.split(jax.random.PRNGKey(0))[1]
                    self.fused_step = aot_warmup(
                        self.fused_step, self.learner_state, example_key
                    )
                else:
                    self.learn_step, aot_info = compilecache.warmup_with_export(
                        self.learn_step, (self.learner_state,), export_dir,
                        name=config.system.system_name,
                    )
                if self.gossip_step is not None:
                    # The mixing program's compile is paid here too, so the first
                    # gossip window's wall time is dispatch cost like every other.
                    self.gossip_step = aot_warmup(
                        self.gossip_step, self.learner_state, jnp.asarray(0, jnp.int32)
                    )
        compile_s = phases.breakdown()["compile_s"]  # this run's: that one span
        host.setup_phases.record("aot_warmup", compile_s)
        # From here to the first completed window: the snapshot and evaluator
        # programs' compiles, the first dispatches and the first window itself.
        host.first_tick = host.setup_phases.open_first_tick()
        # Whether the persistent cache absorbed the warm-up (docs/DESIGN.md §2.7);
        # which program paid how much, by stage, is
        # `stoix_tpu_compile_seconds_total{program, stage}` (utils/compilecache.py).
        cache_after = compilecache.cache_stats()
        self.compile_stats = {
            "compile_s": round(compile_s, 6),
            "cache_hits": cache_after["hits"] - cache_before["hits"],
            "cache_misses": cache_after["misses"] - cache_before["misses"],
            "aot_source": aot_info["source"],
        }
        if pf.enabled:
            # The compiled program's memory_analysis() against device HBM,
            # before anything executes: ResourcePreflightError beats a runtime
            # OOM twenty minutes later.
            preflight.check_device_memory(
                self.fused_step if fused else self.learn_step, headroom=pf.hbm_headroom
            )

    def dispatch(self, eval_idx: int) -> _Window:
        """Enqueue one full eval window on the device stream; never blocks on
        device results (post-compile, each call is dispatch cost only)."""
        phases, setup, fused = self.phases, self.setup, self.fused
        fleet_coord, sentinel, checkpointer = self.host.fleet, self.host.sentinel, self.checkpointer
        with span("learn_dispatch", clock=phases, phase="learn_s",
                  window=eval_idx, fused=fused):
            self.key, eval_key = jax.random.split(self.key)
            if fused:
                output, eval_metrics = self.fused_step(self.learner_state, eval_key)
            else:
                output = self.learn_step(self.learner_state)
            self.learner_state = output.learner_state
        if self.gossip_step is not None and (eval_idx + 1) % self.gossip_plan.interval == 0:
            # Mix BEFORE the snapshot below: eval, best-params tracking, and
            # checkpoints all observe the POST-gossip parameters. The round
            # index seeds random_peer's edge draw deterministically, and the
            # step donates the learn output it consumes (nothing else reads
            # the pre-gossip state).
            with span("gossip_dispatch", clock=phases, phase="gossip_s",
                      window=eval_idx):
                self.learner_state = self.gossip_step(
                    self.learner_state, jnp.asarray(eval_idx, jnp.int32)
                )
                self.gossip_rounds += 1
                get_registry().counter(
                    "stoix_tpu_gossip_rounds_total",
                    "Cross-group parameter mixing rounds dispatched",
                ).inc()

        # On-device snapshots, enqueued BEFORE the next learn dispatch ever
        # happens: donation of learner_state stays legal while eval/best/ckpt
        # consumers read the copies at their leisure. The full-state copy is
        # only taken for windows orbax's save policy will actually accept.
        with span("snapshot_dispatch", clock=phases, phase="snapshot_s",
                  window=eval_idx):
            t = self.start_step + (eval_idx + 1) * self.steps_per_eval
            eval_params = setup.eval_params_fn(self.learner_state)
            snapshot = _tree_copy(eval_params) if self.track_best else None
            take_ckpt = (
                checkpointer is not None
                and self.snapshot_ckpt
                and checkpointer.should_save(t, last_issued=self.last_save_t)
            )
            if take_ckpt:
                self.last_save_t = t
            ckpt_state = _tree_copy(self.learner_state) if take_ckpt else None
            if fleet_coord is not None:
                # Rescue candidate for the partition path: an on-device copy
                # enqueued right after this window's learn, so once the
                # window's metrics materialize the copy is provably complete
                # and readable without any (possibly dead) peer.
                fleet_coord.stage_candidate(
                    t, ckpt_state if take_ckpt else _tree_copy(self.learner_state)
                )

        if not fused:
            with span("eval_dispatch", clock=phases, phase="eval_s", window=eval_idx):
                eval_metrics = self.evaluator(
                    snapshot if self.track_best else eval_params, eval_key
                )

        # ONE coalesced collective fetch for the whole window (episode, train,
        # and eval metrics ride a single pytree -> a single host-sync point).
        with span("fetch_dispatch", clock=phases, phase="fetch_dispatch_s",
                  window=eval_idx):
            tree = {
                "episode": dict(output.episode_metrics),
                "train": dict(output.train_metrics),
                "eval": dict(eval_metrics),
            }
            if fleet_coord is not None:
                # Agreed-stop + skew transport: a tiny per-device payload
                # (stop-flag byte + last window wall-time) rides the SAME
                # coalesced fetch collective — every host decodes every
                # host's values when this window materializes, at zero extra
                # collectives, and the cross-host collective SEQUENCE stays
                # exactly the fetch stream (docs/DESIGN.md §2.6).
                tree["fleet"] = fleet_coord.telemetry_for_fetch(self.mesh)
            if sentinel is not None:
                # Replica fingerprints (docs/DESIGN.md §2.9): each device
                # folds ITS copy of the replicated state groups to a uint32
                # — the reduction is device-local, and the [num_devices]
                # vectors ride this same fetch, so the integrity check adds
                # zero collectives to the window.
                tree["integrity"] = sentinel.fingerprints(output.learner_state)
            metrics = fetch_global_async(tree, self.mesh)
            return _Window(eval_idx, t, snapshot, ckpt_state, metrics)

    def process(self, window: _Window) -> None:
        """Host half: materialize the window's metrics, log, track best
        params, and hand the checkpoint snapshot to orbax (async, no wait)."""
        host, phases, checkpointer = self.host, self.phases, self.checkpointer
        fleet_coord, sentinel = host.fleet, host.sentinel
        with span("fetch_materialize", clock=phases, phase="fetch_s",
                  window=window.eval_idx):
            fetched = materialize(window.metrics)
        if window.eval_idx == 0:
            host.first_tick.close()  # set-up's last phase ends with the first window

        with span("window_bookkeeping", clock=phases, phase="host_s",
                  window=window.eval_idx):
            now = time.perf_counter()
            wall = now - self.window_done_at
            self.window_done_at = now
            self.window_walls.append(wall)

            if sentinel is not None:
                # Integrity verdict FIRST — before this window's checkpoint
                # snapshot is handed to orbax AND before confirm_candidate
                # promotes this window's state to the fleet rescue snapshot: a
                # corrupt state must never be persisted by EITHER path (a
                # concurrent partition would otherwise rescue-save exactly the
                # corruption being proven; window N-1's verified state stays
                # the candidate). The fingerprint vector is replicated data,
                # so every host computes the SAME verdict at the SAME window —
                # the corruption flag on the fleet byte is observability, not
                # the agreement mechanism.
                integrity_payload = fetched.pop("integrity")
                corruption = sentinel.verify(integrity_payload, window.eval_idx, window.t)
                if corruption is not None:
                    # Last ring entry before the rc-88 path unwinds: the dumped
                    # flight record ends with the verdict itself.
                    host.recorder.record(
                        "integrity_verdict",
                        window=window.eval_idx,
                        step=window.t,
                        detail=str(corruption),
                    )
                    if fleet_coord is not None:
                        fleet_coord.request_stop(fleet.FLAG_CORRUPT, note=str(corruption))
                    raise corruption
                if window.eval_idx == 0:
                    # Window 0's fingerprint IS fingerprint(learn(probe_input))
                    # — the determinism probe's reference, recorded for free.
                    sentinel.record_probe_reference(integrity_payload)

            if fleet_coord is not None:
                # This window's metrics are on the host, so (stream ordering)
                # its learn completed — and the sentinel (above) vouched for
                # its state: promote the rescue candidate, decode the
                # fleet-wide flags + straggler wall-times, and record this
                # window's wall for the next dispatch's payload.
                fleet_coord.confirm_candidate(window.t)
                payload = fetched.pop("fleet")
                decision = fleet_coord.decide_from_fetch(payload, self.mesh)
                if decision.stop and self.agreed_stop is None:
                    self.agreed_stop = decision
                fleet_coord.skew_from_fetch(payload, self.mesh, window.eval_idx)
                fleet_coord.note_window_wall(wall)

            episode_metrics = envs.get_final_step_metrics(fetched["episode"])
            train_metrics = fetched["train"]
            eval_metrics = fetched["eval"]
            # Divergence guard, host half: fold this window's skipped-update
            # flags into the registry counter; update_guard=halt raises
            # DivergenceError here, naming the step and the offending metric.
            guards.publish_guard_metrics(host.guard_mode, train_metrics, window.t)
            sps = self.steps_per_eval / wall
            get_registry().gauge(
                "stoix_tpu_runner_steps_per_second",
                "Env-steps/sec over the most recent eval window",
            ).set(sps)
            # Ops plane: /statusz freshness + one flight-recorder ring entry
            # per completed window (the last N of these are what an
            # rc-86/87/88 dump hands the post-mortem).
            host.status.update(
                {"window": window.eval_idx, "step": window.t,
                 "steps_per_second": round(sps, 3)}
            )
            host.recorder.record(
                "window",
                window=window.eval_idx,
                step=window.t,
                wall_s=round(wall, 6),
                steps_per_second=round(sps, 3),
                phases={k: round(v, 6) for k, v in phases.breakdown().items()},
                fleet=fleet_coord is not None,
                fleet_stop=self.agreed_stop.describe() if self.agreed_stop is not None else None,
                integrity=sentinel is not None,
            )
            mean_return = float(eval_metrics["episode_return"].mean())
            self.final_return = mean_return
            if self.track_best and mean_return >= float(self.best_return):
                self.best_return = mean_return
                self.best_params = window.snapshot  # already a donation-safe copy

        if is_coordinator():
            with span("log", clock=phases, phase="log_s", window=window.eval_idx):
                self.logger.log(
                    {**episode_metrics, "steps_per_second": sps},
                    window.t, window.eval_idx, LogEvent.ACT,
                )
                self.logger.log(
                    jax.tree.map(lambda x: x.mean(), train_metrics),
                    window.t, window.eval_idx, LogEvent.TRAIN,
                )
                self.logger.log(eval_metrics, window.t, window.eval_idx, LogEvent.EVAL)

        if checkpointer is not None:
            # Orbax saves sharded globals collectively: ALL processes call
            # save. The snapshot is not donated to anything, so the async save
            # needs no wait() here — serialization overlaps the next window.
            with span("ckpt_save", clock=phases, phase="ckpt_s", window=window.eval_idx):
                if window.ckpt_state is not None:
                    checkpointer.save(window.t, window.ckpt_state, mean_return)
                elif not self.snapshot_ckpt and checkpointer.should_save(window.t):
                    # ckpt_snapshot=false forced the loop synchronous: the live
                    # state is not yet donated here, so save it directly and
                    # wait before the next dispatch can donate it. Record the
                    # step so the preemption path does not force-rewrite an
                    # identical emergency checkpoint.
                    checkpointer.save(window.t, self.learner_state, mean_return)
                    checkpointer.wait()
                    self.last_save_t = window.t

        if window.eval_idx == self.profile_window:
            with span("profile_stop", clock=phases, phase="host_s"):
                try:
                    jax.profiler.stop_trace()
                except Exception:  # noqa: BLE001 — profiling must never kill a run
                    pass

    def learn(self) -> None:
        """The window loop, the drain, the final-boundary agreement, then the
        preemption save or the absolute metric."""
        host, config, phases = self.host, self.config, self.phases
        fleet_coord, sentinel, pf = host.fleet, host.sentinel, host.preflight
        # Only the absolute-metric evaluation at the run's end reads the best
        # parameters. Without it no snapshot is kept or even taken: the evaluator
        # is dispatched on the live parameters BEFORE the next (donating) learn
        # dispatch, so the device stream orders its reads first, and nothing on
        # the host reads them afterwards. A 2.5 GB policy then costs no copy.
        self.track_best = bool(config.arch.get("absolute_metric", True))
        self.best_params = (
            _tree_copy(self.setup.eval_params_fn(self.learner_state)) if self.track_best else None
        )
        self.best_return = -jnp.inf
        self.final_return = 0.0

        profile_dir = os.environ.get("STOIX_TPU_PROFILE_DIR")
        # Profile a steady-state window (the second) when there is one; the first
        # window still carries one-off costs (evaluator/fetch compiles).
        self.profile_window = (1 if self.num_evaluation > 1 else 0) if profile_dir else -1

        self.window_walls: list = []
        self.window_done_at = time.perf_counter()
        # Step of the most recent window we DECIDED to checkpoint (the save is
        # issued one window later): orbax's own latest_step lags by that window,
        # so should_save consults this to avoid a spurious full-state copy.
        self.last_save_t: Optional[int] = None
        # SIGTERM/SIGINT set a flag; the loop observes it at the next window
        # boundary, drains the one-window-deep dispatcher, writes an emergency
        # checkpoint, and returns normally (exit code 0) so the run resumes from
        # the saved state instead of losing the window.
        preempt = host.watch_for_stop()
        self.preempted = False
        self.agreed_stop: Optional[fleet.FleetDecision] = None
        self.dispatched_t = self.start_step
        pending: Optional[_Window] = None
        if sentinel is not None and sentinel.probe_enabled:
            # Determinism-probe input: a donation-safe copy of the state going
            # into window 0 (every replay runs learn on a fresh copy of it).
            sentinel.capture_probe_input(_tree_copy(self.learner_state))
        loop_started = time.perf_counter()
        for eval_idx in range(self.num_evaluation):
            # One beat per window top: an injected host_stall (next line) or
            # a wedged dispatch stops the beats and /healthz goes 503 once
            # the age crosses the stale threshold. (An injected stall is the
            # goodput ledger's `stall`, not a phase of the loop.)
            self.loop_beats.beat("window")
            faultinject.maybe_host_stall(eval_idx)
            if eval_idx == self.profile_window:
                # Before the window's first span opens, so the session holds
                # every one of them whole.
                try:
                    jax.profiler.start_trace(profile_dir)
                except Exception:  # noqa: BLE001
                    self.profile_window = -1
            with span("window_bookkeeping", clock=phases, phase="host_s", window=eval_idx):
                # Chaos: `bitflip:N` rebuilds the replicated state with ONE
                # mantissa bit flipped in one device's copy going INTO window
                # N — the silent-corruption class only the sentinel can see.
                self.learner_state = faultinject.maybe_bitflip(self.learner_state, eval_idx)
                if sentinel is not None and sentinel.should_probe(eval_idx):
                    # The determinism probe replays a recorded learn step and
                    # compares output fingerprints bitwise (docs/DESIGN.md §2.9).
                    probe_err = sentinel.run_probe(self.setup.learn, _tree_copy)
                    if probe_err is not None:
                        if fleet_coord is not None:
                            fleet_coord.request_stop(
                                fleet.FLAG_CORRUPT, note=str(probe_err)
                            )
                        raise probe_err
            if eval_idx == 0 and pf.enabled:
                # First-window execution watchdog (docs/DESIGN.md §2.4): force
                # this window's metrics to the host under a deadline, so a
                # backend that compiled fine but wedges on EXECUTION raises
                # CompileStallError instead of hanging the run's first fetch.
                # The extra sync exists only with preflight on; the dispatched
                # program sequence (and hence the trajectory) is unchanged.
                with host.watchdog("first_window", pf.first_window_deadline_s):
                    window = self.dispatch(eval_idx)
                    with span("first_window_wait", clock=phases, phase="fetch_s"):
                        jax.block_until_ready(window.metrics)
            else:
                window = self.dispatch(eval_idx)
            self.dispatched_t = window.t
            faultinject.maybe_sigterm(eval_idx)
            faultinject.maybe_host_loss(eval_idx)
            if self.pipelined:
                # Process LAST window's host work while the device runs this one.
                if pending is not None:
                    self.process(pending)
                pending = window
            else:
                self.process(window)
            with span("window_bookkeeping", clock=phases, phase="host_s", window=eval_idx):
                if self._stops_after(eval_idx, preempt):
                    self.preempted = True
                    break
        # Drain the dispatcher: the final (or preemption-interrupted) window's
        # host half — metrics, logging, and its pending checkpoint snapshot.
        if pending is not None:
            self.process(pending)
        self.loop_wall_s = time.perf_counter() - loop_started

        if fleet_coord is not None and not self.preempted:
            # Final-boundary agreement: a SIGTERM that landed during the last
            # window(s) has no later fetch to carry its flag, so without this
            # vote it would be silently dropped (no acknowledge, no forced
            # emergency save, and a march into the absolute-metric eval under
            # a scheduler kill deadline). One bounded KV vote — not a device
            # collective — at a point every host reaches; every host computes
            # the same verdict, so the skip-absolute decision stays
            # collective-safe.
            host.vote_to_stop("during the final window")
            final_decision = fleet_coord.agree_at_window(self.num_evaluation)
            if final_decision.stop:
                if self.agreed_stop is None:
                    self.agreed_stop = final_decision
                self.preempted = True

        if self.preempted:
            self._secure_state(preempt)
        elif self.track_best:
            self.key, ek = jax.random.split(self.key)
            abs_metrics = fetch_global(self.absolute_evaluator(self.best_params, ek), self.mesh)
            if is_coordinator():
                self.logger.log(
                    abs_metrics,
                    self.start_step + int(config.arch.total_timesteps),
                    self.num_evaluation,
                    LogEvent.ABSOLUTE,
                )
            self.final_return = float(abs_metrics["episode_return"].mean())

    def _stops_after(self, eval_idx: int, preempt: Any) -> bool:
        """The end of window `eval_idx`: does the loop stop here?"""
        fleet_coord = self.host.fleet
        # Chaos: `shrink:N`/`grow:N` vacate for a different topology
        # (docs/DESIGN.md §2.14). AFTER `process` so the newest CONFIRMED
        # rescue candidate exists — the resize exit's emergency snapshot is
        # what the relaunch restores digest-identically.
        resize_action = faultinject.maybe_resize(eval_idx)
        if resize_action is not None:
            elastic.resize_exit(
                resize_action,
                config=self.config,
                window_idx=eval_idx,
                step=self.dispatched_t,
                fleet_coord=fleet_coord,
            )
        if fleet_coord is None:
            return preempt.stop_requested()
        # Fleet mode: this host's flag rides the NEXT window's fetch, and every
        # host breaks together once the combined decision (identical
        # everywhere, it is a pure function of the same replicated flag vector)
        # comes back. A partition verdict from the monitor thread surfaces here
        # as the typed error instead of a hung collective.
        fleet_coord.check_partition()
        self.host.vote_to_stop(f"at window {eval_idx}")
        return self.agreed_stop is not None

    def _secure_state(self, preempt: Any) -> None:
        """A preempted run's last act: acknowledge, and force-save the live
        state unless the regular cadence already covered the last window."""
        checkpointer, dispatched_t = self.checkpointer, self.dispatched_t
        if preempt.stop_requested():
            preempt.acknowledge(dispatched_t)
        elif self.agreed_stop is not None:
            # This host is stopping on a PEER's flag: same drain, same
            # emergency checkpoint, same window — the coordinated half
            # of graceful preemption (docs/DESIGN.md §2.6).
            get_logger("stoix_tpu.resilience").warning(
                "[fleet] %s — draining and checkpointing at step %d in "
                "lockstep with the fleet", self.agreed_stop.describe(), dispatched_t,
            )
        if checkpointer is not None:
            if self.last_save_t != dispatched_t:
                # No later program donates the live state (nothing was
                # dispatched after it): save it and block until it is on disk.
                with span("emergency_ckpt", step=dispatched_t):
                    checkpointer.save(
                        dispatched_t, self.learner_state, self.final_return, force=True
                    )
                    checkpointer.wait()
            get_logger("stoix_tpu.resilience").warning(
                "[preemption] emergency state secured at step %d — exiting "
                "cleanly; resume with logger.checkpointing.load_model=true",
                dispatched_t,
            )
        else:
            get_logger("stoix_tpu.resilience").warning(
                "[preemption] checkpointing disabled "
                "(logger.checkpointing.save_model=false): stopping "
                "cleanly at step %d WITHOUT saving state", dispatched_t,
            )

    def shut_down(self) -> None:
        """Runs in `run_anakin_experiment`'s `finally`, a failure possibly
        propagating, set-up possibly unfinished."""
        self.host.close()
        if self.aggregator is not None:
            self.aggregator.close()
            self.ops_server.set_aggregator(None)
        if self.checkpointer is not None:
            # Drain in-flight async saves; otherwise interpreter shutdown races
            # orbax's executor ("cannot schedule new futures after shutdown").
            self.checkpointer.close()
        if self.logger is not None:
            self.logger.close()

    def close_out(self) -> float:
        """The steady-state gauge, `LAST_RUN_STATS`, the final return."""
        walls, plan = self.window_walls, self.gossip_plan
        steady = (
            self.steps_per_eval * (len(walls) - 1) / sum(walls[1:])
            if len(walls) > 1
            else (self.steps_per_eval / walls[0] if walls else 0.0)
        )
        get_registry().gauge(
            "stoix_tpu_runner_steady_state_sps",
            "Post-first-window env-steps/sec of the most recent Anakin run",
        ).set(steady)
        gossip = None
        if plan is not None:  # the plan's facts, all but its jitted step, and the rounds run
            gossip = {**plan._asdict(), "rounds": self.gossip_rounds}
            del gossip["step"]
        LAST_RUN_STATS.clear()
        LAST_RUN_STATS.update(
            {
                **self.host.run_stats(
                    self.preempted,
                    resume_capable=self.checkpointer is not None,
                    preflight=self.host.preflight.enabled,
                    fleet_agreed_stop=(
                        self.agreed_stop.describe() if self.agreed_stop is not None else None
                    ),
                    restore_skipped=len(self.restore_report),
                ),
                "phase_breakdown": {k: round(v, 6) for k, v in self.phases.breakdown().items()},
                "loop_wall_s": round(self.loop_wall_s, 6),
                "steady_state_sps": steady,
                "pipelined": self.pipelined,
                "fused_eval": self.fused,
                "compile": self.compile_stats,
                "gossip": gossip,
            }
        )
        return self.final_return


def run_anakin_experiment(
    config: Any,
    setup_fn: SetupFn,
    warmup_fn: Optional[Callable] = None,
    evaluator_setup_fn: Callable = None,
) -> float:
    """Generic Anakin experiment: returns final eval episode-return mean."""
    run = _Run(config)
    try:
        run.set_up(setup_fn, warmup_fn, evaluator_setup_fn)
        run.warm_up()
        with run.host.interrupt_as_partition(rescue=True):
            run.learn()
    finally:
        run.shut_down()
    return run.close_out()


def run_rnn_anakin_experiment(config: Any, setup_fn: SetupFn) -> float:
    """Anakin host loop for recurrent systems: identical to
    run_anakin_experiment but evaluates with the hidden-state-carrying RNN
    evaluator (setup_fn's eval_act_fn must have the rnn_act_fn signature)."""
    from stoix_tpu.networks.base import ScannedRNN

    hidden_size = int(config.network.get("rnn_hidden_size", 128))
    cell_type = str(config.network.get("rnn_cell_type", "gru"))

    rnn_evaluator_setup = per_episode_evaluator_setup(
        lambda: ScannedRNN.initialize_carry(cell_type, hidden_size, (1,))
    )
    return run_anakin_experiment(config, setup_fn, evaluator_setup_fn=rnn_evaluator_setup)
