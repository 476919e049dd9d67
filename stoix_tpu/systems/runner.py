"""Shared Anakin host loop — a PIPELINED dispatcher.

The reference repeats `run_experiment` in every system file (deliberate
duplication, reference README.md:50-52); here the host loop — the part that is
genuinely identical across systems — is shared, while each system file keeps
its full learner (`get_learner_fn`) and setup (`learner_setup`) for
hackability.

The Podracer/Anakin promise is that the accelerator never idles, yet the
original synchronous loop serialized every eval window:

    learn -> block_until_ready -> 2x collective fetch -> eval launch
          -> checkpointer.save + wait  (state donated to the next learn)

Every host-side phase in that chain was dead accelerator time. This loop is a
one-window-deep software pipeline instead. Per eval window it DISPATCHES

    learn_k -> snapshot_k (on-device params/state copy) -> eval_k
            -> fetch_k (ONE coalesced collective over episode+train+eval
               metrics)

and only THEN processes window k-1 on the host (materialize metrics, log,
update best params, hand the checkpoint snapshot to orbax). JAX async dispatch
overlaps all of that host work with the device executing window k. The
invariants that make it legal:

  * Donation stays legal: `snapshot_k` is a fresh on-device copy taken from
    the stream BEFORE `learn_{k+1}` is dispatched, so eval, best-params
    tracking, and orbax serialization read buffers no later program donates.
    The forced `checkpointer.wait()` on the hot path is gone — async saves
    serialize the snapshot, not the donated state (utils/checkpointing.py).
  * Bit-identical training: the sequence of `learn` calls, their inputs, and
    the per-window eval key splits are exactly those of the synchronous loop
    (`arch.pipelined_loop=false` keeps that loop as a debug fallback;
    tests/test_runner_pipeline.py pins trajectory equality).
  * The learner is AOT-compiled (utils/jax_utils.aot_warmup) before the timed
    loop, so the first window's logged steps_per_second no longer includes
    XLA compile time; `LAST_RUN_STATS["steady_state_sps"]` additionally
    reports the post-first-window rate.

`arch.fused_eval` folds a fusion-capable (FF) evaluator INTO the jitted learn
program — classic Anakin, one XLA launch per window; RNN/stateful evaluators
fall back to the snapshot-overlap path automatically.

Observability (stoix_tpu/observability, docs/DESIGN.md §2.2): every
statement of the main thread between two window completions runs inside a
`span` that feeds the phase clock — learn_dispatch (learn_s), gossip_dispatch
(gossip_s), snapshot_dispatch (snapshot_s), eval_dispatch (eval_s),
fetch_dispatch (fetch_dispatch_s), fetch_materialize (fetch_s: the blocked
wait alone), log (log_s), ckpt_save (ckpt_s) and window_bookkeeping (host_s:
what is left — best-params tracking, integrity and fleet checks, the loop's
own tests) — so the phases sum to the loop's wall
(`LAST_RUN_STATS["loop_wall_s"]`; tests/test_runner_pipeline.py holds them
to 95% of it). The seconds accumulate in the process-wide metrics registry
(`stoix_tpu_runner_phase_seconds_total{phase=...}`) and are mirrored into
`LAST_RUN_STATS["phase_breakdown"]` at run end (bench.py forwards it).
Every span is a `jax.profiler.TraceAnnotation`, so the device trace that
STOIX_TPU_PROFILE_DIR=<dir> wraps around one steady-state eval window (or
any other profiler session) shows them on the device ops' clock; with
`logger.telemetry.enabled=true` they are also recorded for the Perfetto JSON
export. In the pipelined loop the phases are HOST attribution: device time
spent in learn/eval surfaces as fetch_s (the materialize wait), while
learn_s/eval_s shrink to dispatch cost. Set-up goes to
`stoix_tpu_setup_phase_seconds{phase=...}` the same way, through a
`SetupClock` that is open from this function's first statement to the first
completed window: its phases (mesh_build, env_build, rng_key, learner_setup,
state_warmup, restore, evaluator_setup, logger_build, aot_warmup,
first_tick) partition that wall, and what no span covered is `unspanned`.

Resilience (stoix_tpu/resilience, docs/DESIGN.md §2.3): SIGTERM/SIGINT
request a graceful stop at the next window boundary — the loop drains the
one-window-deep dispatcher, force-saves an emergency checkpoint of the live
state, and returns cleanly so the run resumes instead of losing the window.
`system.update_guard` wires the in-jit divergence guard's host half through
process_window (skip counting / halt raising), and STOIX_TPU_FAULT /
arch.fault_spec arms the deterministic chaos layer.

Launch hardening (docs/DESIGN.md §2.4, `arch.preflight`): with
`arch.preflight.enabled=true` the run starts with a subprocess-isolated
backend probe (bounded timeout + backoff retries — a wedged PJRT runtime
raises BackendUnavailableError instead of hanging this process) and config
cross-validation BEFORE any device work; the AOT compile and the first
window's execution run under deadline watchdogs that dump all thread stacks
+ the registry snapshot and raise CompileStallError on stall; and the
compiled learner's memory_analysis() is checked against device HBM
(ResourcePreflightError beats a 20-minutes-later runtime OOM). Off (the
default) adds zero work and zero host syncs — bit-identical. On, the only
semantic change is ONE block_until_ready on the first window's metrics (the
watchdogged first-execution check); trajectory values are unchanged.

Restore is topology-elastic (utils/checkpointing.py): a checkpoint saved on
an 8-device mesh resumes on 1 device (and vice versa) with bit-identical
params — the state materializes to host and re-places via the fresh
template's shardings.

State integrity (stoix_tpu/resilience/integrity.py, docs/DESIGN.md §2.9,
`arch.integrity`): with the sentinel on, every window's dispatch also
enqueues a tiny shard_mapped fingerprint program over the replicated state
groups; the resulting [num_devices] uint32 vectors ride the SAME coalesced
metric fetch (zero extra collectives) and are compared on the host when the
window materializes — a cross-replica disagreement (HBM bit-flip, wrong-math
core) raises StateCorruptionError BEFORE that window's checkpoint snapshot
is handed to orbax, so a corrupt state is never saved. The optional
determinism probe replays a recorded learn step every N windows and compares
output fingerprints bitwise. Off (the default) adds zero dispatches and zero
host work — bit-identical (tests/test_integrity.py pins on AND off).
"""

from __future__ import annotations

import contextlib
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
    SetupClock,
    flightrec,
    get_health_monitor,
    get_logger,
    get_ops_server,
    get_registry,
    get_status_board,
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
from stoix_tpu.resilience import (
    PreemptionHandler,
    Watchdog,
    elastic,
    faultinject,
    fleet,
    guards,
    integrity,
    preflight,
)
from stoix_tpu.ops import scan_kernels
from stoix_tpu.utils import compilecache
from stoix_tpu.utils.checkpointing import checkpointer_from_config
from stoix_tpu.utils.jax_utils import aot_warmup
from stoix_tpu.utils.logger import LogEvent, StoixLogger
from stoix_tpu.utils.timestep_checker import check_total_timesteps

setup_launch.note_imports(_IMPORTS_BEGAN, time.perf_counter())

# Stats of the most recent run_anakin_experiment call (this process):
# phase_breakdown {compile_s, learn_s, snapshot_s, eval_s, fetch_dispatch_s,
# fetch_s, log_s, host_s, ckpt_s [, gossip_s]}, loop_wall_s,
# steady_state_sps, pipelined, fused_eval. bench.py reads this. The values
# are published to the process-wide metrics registry during the run
# (stoix_tpu_runner_* series — the source of truth) and refreshed into this
# dict-compatible view at run end.
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
        # gossip_s appears only in runs that actually dispatched a gossip step;
        # lockstep runs keep the schema bench.py and the observability
        # contract tests pin.
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
    later (pipelined) or immediately (synchronous fallback)."""

    eval_idx: int
    t: int  # global env-step count at window end
    snapshot: Any  # on-device copy of eval params (donation-safe); None when nothing keeps them
    ckpt_state: Any  # on-device copy of the full learner state, or None
    metrics: Any  # ONE coalesced device tree: episode/train/eval metrics


def _maybe_watchdog(pf: Any, stage: str, deadline_s: float):
    """A deadline Watchdog when preflight is enabled; a free nullcontext
    otherwise (the off path must add zero threads and zero work)."""
    if not pf.enabled:
        return contextlib.nullcontext()
    return Watchdog(stage, deadline_s, hard_exit_grace_s=pf.hard_exit_grace_s)


# ONE jit instance so per-window snapshot copies hit the compile cache
# (jax.jit memoizes per input tree structure/avals).
_TREE_COPY = jax.jit(lambda t: jax.tree.map(jnp.copy, t))


def _tree_copy(tree: Any) -> Any:
    """On-device snapshot: a jitted whole-tree copy (shardings preserved).
    The copy is enqueued in the device stream BEFORE the next learn dispatch,
    so donating the source buffers afterwards is legal."""
    return _TREE_COPY(tree)


def run_anakin_experiment(
    config: Any,
    setup_fn: SetupFn,
    warmup_fn: Optional[Callable] = None,
    evaluator_setup_fn: Callable = None,
) -> float:
    """Generic Anakin experiment: returns final eval episode-return mean."""
    # Goodput ledger (docs/DESIGN.md §2.13): opened before any setup work so
    # restore/compile/stall seconds are all inside the attributed wall. Pure
    # host arithmetic — always on, bit-identity untouched. set_active lets
    # out-of-loop sites (faultinject stalls, watchdog) charge their seconds.
    ledger = goodput.GoodputLedger().start()
    goodput.set_active(ledger)
    # Set-up phases -> stoix_tpu_setup_phase_seconds{phase}: what the wait
    # before the first window is made of (host memory only). Open from here
    # to the close of `first_tick`; no statement between is outside a span
    # that costs more than the span would.
    setup_phases = SetupClock(ledger)
    # Resilience (docs/DESIGN.md §2.3): arm the chaos plan (no-op unless
    # STOIX_TPU_FAULT / arch.fault_spec is set) BEFORE the learner is built —
    # the in-jit nan_loss guard reads it at trace time — and resolve the
    # divergence-guard mode for the host-side checks below.
    faultinject.configure(config.arch.get("fault_spec"))
    guard_mode = guards.resolve_mode(config)
    # Compile economy (docs/DESIGN.md §2.7): the persistent cache must be
    # configured before the FIRST compile this process does (network init
    # included), and the multistep scan-kernel default before the learner is
    # traced — both are trace/compile-time statics.
    compilecache.configure(config)
    scan_kernels.configure_from_config(config)
    # Launch hardening (docs/DESIGN.md §2.4): probe the backend in a
    # SUBPROCESS and cross-validate the config BEFORE this process commits to
    # device work — a wedged PJRT runtime or a bad shape aborts here with a
    # typed error, not twenty minutes in. Off by default (zero added work).
    pf = preflight.settings_from_config(config)
    if pf.enabled:
        with span("preflight", clock=setup_phases, phase="preflight"):
            probe = preflight.probe_backend(
                timeout_s=pf.probe_timeout_s,
                attempts=pf.probe_attempts,
                backoff_base_s=pf.probe_backoff_base_s,
                backoff_max_s=pf.probe_backoff_max_s,
            )
            preflight.validate_config(config, device_count=probe.device_count)
            get_logger("stoix_tpu.resilience").info(
                "[preflight] backend healthy (%s x%d, attempt %d) and config "
                "cross-checks pass", probe.platform, probe.device_count,
                probe.attempts,
            )
    # The program's first touch of the devices on an operator's path (the
    # backend starts here unless an import already started it).
    with span("mesh_build", clock=setup_phases, phase="mesh_build"):
        maybe_initialize_distributed(config)
        # Device assignment goes through the unified mesh-role abstraction
        # (parallel/roles.py, docs/DESIGN.md §2.11): Anakin's learn role owns the
        # whole `arch.mesh` (colocated act/learn/evaluate), so this is the same
        # mesh create_mesh built directly before MeshRoles existed — and the
        # population runner's ("pop", "data") mesh arrives through the same path.
        roles = MeshRoles.from_config(config)
        mesh = roles.learn_mesh()
        # Fleet coordination (docs/DESIGN.md §2.6, arch.fleet): cross-host agreed
        # stop decisions (flags piggybacked on the coalesced metric fetch),
        # heartbeat-based partition detection, straggler skew telemetry, and the
        # local-shard emergency checkpoint. Off (the default) = None = zero extra
        # work, bit-identical host loop.
        fleet_coord = fleet.fleet_from_config(config)
        if fleet_coord is not None:
            fleet_coord.start()
        # State-integrity sentinel (docs/DESIGN.md §2.9, arch.integrity): bound
        # below once the learner state exists. None (the default) = zero extra
        # dispatches, zero host work, bit-identical host loop.
        sentinel = integrity.sentinel_from_config(config)
        config = check_total_timesteps(config, int(mesh.shape["data"]))
        config.logger.system_name = config.system.system_name

    with span("env_build", clock=setup_phases, phase="env_build"):
        env, eval_env = envs.make(config)

    # The process's first eager programs, unless an import ran some.
    with span("rng_key", clock=setup_phases, phase="rng_key"):
        key = jax.random.PRNGKey(int(config.arch.seed))
        key, setup_key = jax.random.split(key)
    # Network init and the learner's build are both the system's own
    # learner_setup; the systems mark `network_init` inside it.
    with span("learner_setup", clock=setup_phases, phase="learner_setup"):
        setup = setup_fn(env, config, mesh, setup_key)
    learner_state = setup.learner_state

    if warmup_fn is not None:
        with span("state_warmup", clock=setup_phases, phase="state_warmup"):
            learner_state = warmup_fn(learner_state)
            jax.block_until_ready(jax.tree.leaves(learner_state)[0])

    # Resume: restore a saved learner state into the freshly built (correctly
    # sharded) template (reference ff_ppo.py:504-512 via Checkpointer.restore).
    ckpt_cfg = config.logger.checkpointing
    start_step = 0
    restore_skipped = 0
    restore_report: list = []
    if ckpt_cfg.get("load_model", False):
        with span("restore", clock=setup_phases, phase="restore"):
            load_args = ckpt_cfg.get("load_args") or {}
            load_path = load_args.get("load_path")
            if load_path and fleet.is_emergency_store(load_path):
                # A fleet local-shard emergency store (a partition survivor's
                # rescue save, docs/DESIGN.md §2.6): restore through the same
                # tree-path placement as the topology-elastic path — params
                # round-trip bit-identical onto the (possibly shrunk) new mesh.
                learner_state, start_step = fleet.restore_emergency(
                    learner_state, load_path,
                    raw_transform=getattr(setup, "restore_transform", None),
                )
            else:
                from stoix_tpu.utils.checkpointing import Checkpointer

                loader = Checkpointer(
                    model_name=config.system.system_name,
                    rel_dir=load_path or "checkpoints",
                    checkpoint_uid=load_args.get("checkpoint_uid"),
                )
                loader.check_version()
                learner_state, start_step = loader.restore(
                    learner_state, load_args.get("timestep")
                )
                # How many newer-but-unusable checkpoints the fallback walk
                # rejected (with typed reasons — structure / non_finite /
                # digest), surfaced in LAST_RUN_STATS.resilience below.
                restore_skipped = len(loader.last_restore_report)
                restore_report = list(loader.last_restore_report)
        # Restore wall time is recovery, not compute: a relaunch spending
        # minutes re-reading checkpoints must show up in the badput ledger.
        ledger.note("recovery", setup_phases.seconds()["restore"])
        if is_coordinator():
            get_logger("stoix_tpu.checkpoint").info(
                "[checkpoint] restored state from step %d%s", start_step,
                f" ({restore_skipped} newer checkpoint(s) rejected)"
                if restore_skipped else "",
            )

    make_evaluators = evaluator_setup_fn or evaluator_setup
    with span("evaluator_setup", clock=setup_phases, phase="evaluator_setup"):
        evaluator, absolute_evaluator = make_evaluators(
            eval_env, setup.eval_act_fn, config, mesh
        )
    with span("logger_build", clock=setup_phases, phase="logger_build"):
        logger = StoixLogger(config)
        checkpointer = checkpointer_from_config(config, config.system.system_name)

        # Ops plane (docs/DESIGN.md §2.13), wired AFTER StoixLogger: its
        # observability.configure() call is the per-run reset (fresh
        # HealthMonitor + flight-recorder ring) and starts the /metrics·/healthz
        # ·/statusz·/varz server when logger.telemetry.http.enabled. Everything
        # below is host-memory bookkeeping — always on, bit-identity untouched.
        telemetry_cfg = dict(config.logger.get("telemetry") or {})
        http_cfg = dict(telemetry_cfg.get("http") or {})
        recorder = flightrec.get_flight_recorder()
        recorder.set_context(
            architecture="anakin",
            system=str(config.system.system_name),
            seed=int(config.arch.seed),
        )
        status = get_status_board()
        status.update(
            {
                "run_id": f"{config.system.system_name}_seed{int(config.arch.seed)}",
                "architecture": "anakin",
                "system": str(config.system.system_name),
                "step": start_step,
                "restore_skipped": restore_skipped,
                "last_restore_report": restore_report,
                "quarantine_file": dict(config.arch.get("integrity") or {}).get(
                    "quarantine_file", "checkpoints/quarantine.json"
                ),
            }
        )
        # /healthz source: the host loop beats once per window; an injected
        # host_stall (or a genuinely wedged loop) lets the age cross
        # stale_after_s and the endpoint flips to 503. Registered fresh each run
        # — configure() above already dropped any previous incarnation's board.
        monitor = get_health_monitor()
        loop_beats = HeartbeatBoard()
        monitor.register_board(
            "anakin-host-loop",
            loop_beats,
            stale_after_s=float(http_cfg.get("stale_after_s", 60.0) or 60.0),
        )
        ops_server = get_ops_server()
        aggregator = None
        if ops_server is not None and fleet_coord is not None:
            # Host-level metric federation over the fleet KV store: publish this
            # host's snapshots off the hot path; /metrics/fleet folds every
            # host's newest blob with per-host labels (aggregate.py).
            aggregator = fleet_metrics.aggregator_from_fleet(
                fleet_coord,
                interval_s=float(http_cfg.get("aggregate_interval_s", 10.0) or 10.0),
            )
            if aggregator is not None:
                aggregator.start()
                ops_server.set_aggregator(aggregator)

        if sentinel is not None:
            # Bind AFTER restore: the fingerprint program is built once for this
            # mesh + state structure (never per window — STX012). The resume info
            # points a rc-88 relaunch at THIS run's orbax store, whose newest
            # digest-verified step is the recovery target.
            sentinel.bind(mesh, learner_state)
            if checkpointer is not None:
                sentinel.set_resume_info(checkpointer.directory)
            sentinel.install_excepthook()

    steps_per_eval = (
        int(config.system.rollout_length)
        * int(config.arch.total_num_envs)
        * int(config.arch.num_updates_per_eval)
    )
    num_evaluation = int(config.arch.num_evaluation)

    pipelined = bool(config.arch.get("pipelined_loop", True))
    fused = bool(config.arch.get("fused_eval", False)) and getattr(
        evaluator, "supports_fusion", False
    )
    # arch.ckpt_snapshot=false: memory fallback for states too big to copy
    # (off-policy replay buffers near HBM capacity). No on-device snapshot is
    # taken; the loop runs synchronously and saves the LIVE state + wait()
    # before the next donating dispatch — the pre-pipeline semantics.
    snapshot_ckpt = bool(config.arch.get("ckpt_snapshot", True))
    if checkpointer is not None and not snapshot_ckpt:
        pipelined = False

    learn = setup.learn
    # Gossip groups (parallel/gossip.py, docs/DESIGN.md §2.12): the mixing
    # step the grouped setup returned, dispatched through this same pipelined
    # stream every `interval` windows so it overlaps the next window's host
    # work like any other device program. step=None covers both lockstep
    # setups and the single-group identity short-circuit that keeps group:1
    # bitwise-lockstep.
    gossip_plan = getattr(setup, "gossip", None)
    gossip_step = gossip_plan.step if gossip_plan is not None else None
    gossip_interval = gossip_plan.interval if gossip_plan is not None else 0
    gossip_rounds = 0
    gossip_counter = (
        get_registry().counter(
            "stoix_tpu_gossip_rounds_total",
            "Cross-group parameter mixing rounds dispatched",
        )
        if gossip_step is not None
        else None
    )
    phases = _PhaseClock(ledger)

    if fused:
        # One XLA program per window: learn + eval-params selection + the FF
        # evaluator, donated like the bare learner. The system's jit wrapper
        # is unwrapped so donation lives ONLY on this outer jit.
        learn_inner = getattr(learn, "__wrapped__", learn)
        donate = {} if os.environ.get("STOIX_TPU_NO_DONATE") else {"donate_argnums": (0,)}

        def _fused_step(state: Any, eval_key: jax.Array):
            output = learn_inner(state)
            eval_metrics = evaluator(setup.eval_params_fn(output.learner_state), eval_key)
            return output, eval_metrics

        fused_step = jax.jit(_fused_step, **donate)

    # AOT warmup: pay the learner's XLA compile before the timed loop so the
    # first window's steps_per_second is throughput, not compile time. With
    # preflight on, the compile runs under a deadline watchdog (a wedged
    # backend raises CompileStallError with a full stack dump instead of
    # hanging) and the compiled program's memory_analysis() is gated against
    # device HBM before anything executes. With `arch.compile_cache.export_dir`
    # set, the non-fused learner additionally round-trips the jax.export AOT
    # store (docs/DESIGN.md §2.7): a matching serialized artifact skips
    # trace+lower here, and a miss serializes this compile for peer hosts.
    export_dir = compilecache.settings_from_config(config)["export_dir"]
    cache_before = compilecache.cache_stats()
    aot_info = {"source": "compile", "export_path": None}
    with span("aot_warmup", clock=phases, phase="compile_s", fused=fused):
        with _maybe_watchdog(pf, "first_compile", pf.compile_deadline_s):
            faultinject.maybe_slow_compile()
            if fused:
                # Aval-identical stand-in for the per-window eval keys below.
                # (The fused program embeds the evaluator, so it is not served
                # by the learn-function export store.)
                example_key = jax.random.split(jax.random.PRNGKey(0))[1]
                fused_step = aot_warmup(fused_step, learner_state, example_key)
            else:
                learn, aot_info = compilecache.warmup_with_export(
                    learn, (learner_state,), export_dir,
                    name=config.system.system_name,
                )
            if gossip_step is not None:
                # The mixing program's compile is paid here too, so the first
                # gossip window's wall time is dispatch cost like every other.
                gossip_step = aot_warmup(
                    gossip_step, learner_state, jnp.asarray(0, jnp.int32)
                )
    compile_s = phases.breakdown()["compile_s"]  # this run's: that one span
    setup_phases.record("aot_warmup", compile_s)
    # From here to the first completed window: the snapshot and evaluator
    # programs' compiles, the first dispatches and the first window itself.
    first_tick = setup_phases.open_first_tick()
    # Whether the persistent cache absorbed the warm-up (docs/DESIGN.md §2.7);
    # which program paid how much, by stage, is
    # `stoix_tpu_compile_seconds_total{program, stage}` (utils/compilecache.py).
    cache_after = compilecache.cache_stats()
    compile_stats = {
        "compile_s": round(compile_s, 6),
        "cache_hits": cache_after["hits"] - cache_before["hits"],
        "cache_misses": cache_after["misses"] - cache_before["misses"],
        "aot_source": aot_info["source"],
    }
    if pf.enabled:
        preflight.check_device_memory(
            fused_step if fused else learn, headroom=pf.hbm_headroom
        )

    # Only the absolute-metric evaluation at the run's end reads the best
    # parameters. Without it no snapshot is kept or even taken: the evaluator
    # is dispatched on the live parameters BEFORE the next (donating) learn
    # dispatch, so the device stream orders its reads first, and nothing on
    # the host reads them afterwards. A 2.5 GB policy then costs no copy.
    track_best = bool(config.arch.get("absolute_metric", True))
    best_params = _tree_copy(setup.eval_params_fn(learner_state)) if track_best else None
    best_return = -jnp.inf
    final_return = 0.0

    profile_dir = os.environ.get("STOIX_TPU_PROFILE_DIR")
    # Profile a steady-state window (the second) when there is one; the first
    # window still carries one-off costs (evaluator/fetch compiles).
    profile_window = (1 if num_evaluation > 1 else 0) if profile_dir else -1

    window_walls: list = []
    window_done_at = time.perf_counter()
    # Step of the most recent window we DECIDED to checkpoint (the save is
    # issued one window later): orbax's own latest_step lags by that window,
    # so should_save consults this to avoid a spurious full-state copy.
    last_save_t: Optional[int] = None

    def dispatch_window(eval_idx: int) -> _Window:
        """Enqueue one full eval window on the device stream; never blocks on
        device results (post-compile, each call is dispatch cost only)."""
        nonlocal learner_state, key, last_save_t, gossip_rounds
        with span("learn_dispatch", clock=phases, phase="learn_s",
                  window=eval_idx, fused=fused):
            key, eval_key = jax.random.split(key)
            if fused:
                output, eval_metrics = fused_step(learner_state, eval_key)
            else:
                output = learn(learner_state)
            learner_state = output.learner_state
        if gossip_step is not None and (eval_idx + 1) % gossip_interval == 0:
            # Mix BEFORE the snapshot below: eval, best-params tracking, and
            # checkpoints all observe the POST-gossip parameters. The round
            # index seeds random_peer's edge draw deterministically, and the
            # step donates the learn output it consumes (nothing else reads
            # the pre-gossip state).
            with span("gossip_dispatch", clock=phases, phase="gossip_s",
                      window=eval_idx):
                learner_state = gossip_step(
                    learner_state, jnp.asarray(eval_idx, jnp.int32)
                )
                gossip_rounds += 1
                gossip_counter.inc()

        # On-device snapshots, enqueued BEFORE the next learn dispatch ever
        # happens: donation of learner_state stays legal while eval/best/ckpt
        # consumers read the copies at their leisure. The full-state copy is
        # only taken for windows orbax's save policy will actually accept.
        with span("snapshot_dispatch", clock=phases, phase="snapshot_s",
                  window=eval_idx):
            t = start_step + (eval_idx + 1) * steps_per_eval
            eval_params = setup.eval_params_fn(learner_state)
            snapshot = _tree_copy(eval_params) if track_best else None
            take_ckpt = (
                checkpointer is not None
                and snapshot_ckpt
                and checkpointer.should_save(t, last_issued=last_save_t)
            )
            if take_ckpt:
                last_save_t = t
            ckpt_state = _tree_copy(learner_state) if take_ckpt else None
            if fleet_coord is not None:
                # Rescue candidate for the partition path: an on-device copy
                # enqueued right after this window's learn, so once the
                # window's metrics materialize the copy is provably complete
                # and readable without any (possibly dead) peer.
                fleet_coord.stage_candidate(
                    t, ckpt_state if take_ckpt else _tree_copy(learner_state)
                )

        if not fused:
            with span("eval_dispatch", clock=phases, phase="eval_s", window=eval_idx):
                eval_metrics = evaluator(snapshot if track_best else eval_params, eval_key)

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
                tree["fleet"] = fleet_coord.telemetry_for_fetch(mesh)
            if sentinel is not None:
                # Replica fingerprints (docs/DESIGN.md §2.9): each device
                # folds ITS copy of the replicated state groups to a uint32
                # — the reduction is device-local, and the [num_devices]
                # vectors ride this same fetch, so the integrity check adds
                # zero collectives to the window.
                tree["integrity"] = sentinel.fingerprints(output.learner_state)
            metrics = fetch_global_async(tree, mesh)
            window = _Window(eval_idx, t, snapshot, ckpt_state, metrics)
        return window

    def process_window(window: _Window) -> None:
        """Host half: materialize the window's metrics, log, track best
        params, and hand the checkpoint snapshot to orbax (async, no wait)."""
        nonlocal best_params, best_return, final_return, window_done_at, last_save_t
        nonlocal agreed_stop
        with span("fetch_materialize", clock=phases, phase="fetch_s",
                  window=window.eval_idx):
            fetched = materialize(window.metrics)
        if window.eval_idx == 0:
            first_tick.close()  # set-up's last phase ends with the first window

        with span("window_bookkeeping", clock=phases, phase="host_s",
                  window=window.eval_idx):
            now = time.perf_counter()
            wall = now - window_done_at
            window_done_at = now
            window_walls.append(wall)

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
                    recorder.record(
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
                decision = fleet_coord.decide_from_fetch(payload, mesh)
                if decision.stop and agreed_stop is None:
                    agreed_stop = decision
                fleet_coord.skew_from_fetch(payload, mesh, window.eval_idx)
                fleet_coord.note_window_wall(wall)

            episode_metrics = envs.get_final_step_metrics(fetched["episode"])
            train_metrics = fetched["train"]
            eval_metrics = fetched["eval"]
            # Divergence guard, host half: fold this window's skipped-update
            # flags into the registry counter; update_guard=halt raises
            # DivergenceError here, naming the step and the offending metric.
            guards.publish_guard_metrics(guard_mode, train_metrics, window.t)
            sps = steps_per_eval / wall
            get_registry().gauge(
                "stoix_tpu_runner_steps_per_second",
                "Env-steps/sec over the most recent eval window",
            ).set(sps)
            # Ops plane: /statusz freshness + one flight-recorder ring entry
            # per completed window (the last N of these are what an
            # rc-86/87/88 dump hands the post-mortem).
            status.update(
                {"window": window.eval_idx, "step": window.t,
                 "steps_per_second": round(sps, 3)}
            )
            recorder.record(
                "window",
                window=window.eval_idx,
                step=window.t,
                wall_s=round(wall, 6),
                steps_per_second=round(sps, 3),
                phases={k: round(v, 6) for k, v in phases.breakdown().items()},
                fleet=fleet_coord is not None,
                fleet_stop=agreed_stop.describe() if agreed_stop is not None else None,
                integrity=sentinel is not None,
            )
            mean_return = float(eval_metrics["episode_return"].mean())
            final_return = mean_return
            if track_best and mean_return >= float(best_return):
                best_return = mean_return
                best_params = window.snapshot  # already a donation-safe copy

        if is_coordinator():
            with span("log", clock=phases, phase="log_s", window=window.eval_idx):
                logger.log(
                    {**episode_metrics, "steps_per_second": sps},
                    window.t, window.eval_idx, LogEvent.ACT,
                )
                logger.log(
                    jax.tree.map(lambda x: x.mean(), train_metrics),
                    window.t, window.eval_idx, LogEvent.TRAIN,
                )
                logger.log(eval_metrics, window.t, window.eval_idx, LogEvent.EVAL)

        if checkpointer is not None:
            # Orbax saves sharded globals collectively: ALL processes call
            # save. The snapshot is not donated to anything, so the async save
            # needs no wait() here — serialization overlaps the next window.
            with span("ckpt_save", clock=phases, phase="ckpt_s", window=window.eval_idx):
                if window.ckpt_state is not None:
                    checkpointer.save(window.t, window.ckpt_state, mean_return)
                elif not snapshot_ckpt and checkpointer.should_save(window.t):
                    # ckpt_snapshot=false forced the loop synchronous: the live
                    # state is not yet donated here, so save it directly and
                    # wait before the next dispatch can donate it (old
                    # semantics). Record the step so the preemption path does
                    # not force-rewrite an identical emergency checkpoint.
                    checkpointer.save(window.t, learner_state, mean_return)
                    checkpointer.wait()
                    last_save_t = window.t

        if window.eval_idx == profile_window:
            with span("profile_stop", clock=phases, phase="host_s"):
                try:
                    jax.profiler.stop_trace()
                except Exception:  # noqa: BLE001 — profiling must never kill a run
                    pass

    # Graceful preemption: SIGTERM/SIGINT set a flag; the loop observes it at
    # the next window boundary, drains the one-window-deep dispatcher, writes
    # an emergency checkpoint, and returns normally (exit code 0) so the run
    # resumes from the saved state instead of losing the window.
    preempt = PreemptionHandler().install()
    preempted = False
    agreed_stop: Optional[fleet.FleetDecision] = None
    skipped_base = guards.skipped_counter().value()
    dispatched_t = start_step
    pending: Optional[_Window] = None
    if sentinel is not None and sentinel.probe_enabled:
        # Determinism-probe input: a donation-safe copy of the state going
        # into window 0 (every replay runs learn on a fresh copy of it).
        sentinel.capture_probe_input(_tree_copy(learner_state))
    loop_started = time.perf_counter()
    try:
        for eval_idx in range(num_evaluation):
            # One beat per window top: an injected host_stall (next line) or
            # a wedged dispatch stops the beats and /healthz goes 503 once
            # the age crosses the stale threshold. (An injected stall is the
            # goodput ledger's `stall`, not a phase of the loop.)
            loop_beats.beat("window")
            faultinject.maybe_host_stall(eval_idx)
            if eval_idx == profile_window:
                # Before the window's first span opens, so the session holds
                # every one of them whole.
                try:
                    jax.profiler.start_trace(profile_dir)
                except Exception:  # noqa: BLE001
                    profile_window = -1
            with span("window_bookkeeping", clock=phases, phase="host_s", window=eval_idx):
                # Chaos: `bitflip:N` rebuilds the replicated state with ONE
                # mantissa bit flipped in one device's copy going INTO window
                # N — the silent-corruption class only the sentinel can see.
                learner_state = faultinject.maybe_bitflip(learner_state, eval_idx)
                if sentinel is not None and sentinel.should_probe(eval_idx):
                    probe_err = sentinel.run_probe(setup.learn, _tree_copy)
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
                with _maybe_watchdog(pf, "first_window", pf.first_window_deadline_s):
                    window = dispatch_window(eval_idx)
                    with span("first_window_wait", clock=phases, phase="fetch_s"):
                        jax.block_until_ready(window.metrics)
            else:
                window = dispatch_window(eval_idx)
            dispatched_t = window.t
            faultinject.maybe_sigterm(eval_idx)
            faultinject.maybe_host_loss(eval_idx)
            if pipelined:
                # Process LAST window's host work while the device runs this one.
                if pending is not None:
                    process_window(pending)
                pending = window
            else:
                process_window(window)
            with span("window_bookkeeping", clock=phases, phase="host_s", window=eval_idx):
                # Chaos: `shrink:N`/`grow:N` vacate for a different topology
                # (docs/DESIGN.md §2.14). AFTER process_window so the newest
                # CONFIRMED rescue candidate exists — the resize exit's
                # emergency snapshot is what the relaunch restores
                # digest-identically.
                resize_action = faultinject.maybe_resize(eval_idx)
                if resize_action is not None:
                    elastic.resize_exit(
                        resize_action,
                        config=config,
                        window_idx=eval_idx,
                        step=dispatched_t,
                        fleet_coord=fleet_coord,
                    )
                if fleet_coord is None:
                    if preempt.stop_requested():
                        preempted = True
                        break
                else:
                    # Fleet mode: a host-local stop request is never acted on
                    # alone — it becomes this host's flag on the NEXT window's
                    # fetch, and every host breaks together once the combined
                    # decision (identical everywhere, it is a pure function of
                    # the same replicated flag vector) comes back. A partition
                    # verdict from the monitor thread surfaces here as the
                    # typed error instead of a hung collective.
                    fleet_coord.check_partition()
                    if preempt.stop_requested():
                        fleet_coord.request_stop(
                            fleet.FLAG_PREEMPT,
                            note=f"{preempt.signal_name} at window {eval_idx}",
                        )
                    if agreed_stop is not None:
                        preempted = True
                        break
        # Drain the dispatcher: the final (or preemption-interrupted) window's
        # host half — metrics, logging, and its pending checkpoint snapshot.
        if pending is not None:
            process_window(pending)
            pending = None
        loop_wall_s = time.perf_counter() - loop_started

        if fleet_coord is not None and not preempted:
            # Final-boundary agreement: a SIGTERM that landed during the last
            # window(s) has no later fetch to carry its flag, so without this
            # vote it would be silently dropped (no acknowledge, no forced
            # emergency save, and a march into the absolute-metric eval under
            # a scheduler kill deadline). One bounded KV vote — not a device
            # collective — at a point every host reaches; every host computes
            # the same verdict, so the skip-absolute decision stays
            # collective-safe.
            if preempt.stop_requested():
                fleet_coord.request_stop(
                    fleet.FLAG_PREEMPT,
                    note=f"{preempt.signal_name} during the final window",
                )
            final_decision = fleet_coord.agree_at_window(num_evaluation)
            if final_decision.stop:
                if agreed_stop is None:
                    agreed_stop = final_decision
                preempted = True

        if preempted:
            if preempt.stop_requested():
                preempt.acknowledge(dispatched_t)
            elif agreed_stop is not None:
                # This host is stopping on a PEER's flag: same drain, same
                # emergency checkpoint, same window — the coordinated half
                # of graceful preemption (docs/DESIGN.md §2.6).
                get_logger("stoix_tpu.resilience").warning(
                    "[fleet] %s — draining and checkpointing at step %d in "
                    "lockstep with the fleet", agreed_stop.describe(), dispatched_t,
                )
            if checkpointer is not None:
                if last_save_t != dispatched_t:
                    # The regular cadence did not cover the last completed
                    # window: force an emergency save of the live state (no
                    # later program donates it — nothing was dispatched after
                    # it) and block until it is on disk.
                    with span("emergency_ckpt", step=dispatched_t):
                        checkpointer.save(
                            dispatched_t, learner_state, final_return, force=True
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
        elif bool(config.arch.get("absolute_metric", True)):
            key, ek = jax.random.split(key)
            abs_metrics = fetch_global(absolute_evaluator(best_params, ek), mesh)
            if is_coordinator():
                logger.log(
                    abs_metrics,
                    start_step + int(config.arch.total_timesteps),
                    num_evaluation,
                    LogEvent.ABSOLUTE,
                )
            final_return = float(abs_metrics["episode_return"].mean())
    except KeyboardInterrupt:
        # The fleet monitor interrupts the main thread when a peer dies (the
        # main thread may even have been wedged inside the dead collective).
        # Convert its interrupt into the typed error; a genuine operator ^C
        # (no partition declared) re-raises untouched.
        if fleet_coord is not None and fleet_coord.partition_event.is_set():
            fleet_coord.emergency_save()  # idempotent; monitor usually saved
            raise fleet_coord.partition_error from None
        raise
    finally:
        first_tick.close()  # a run that never completed a window
        preempt.uninstall()
        goodput.set_active(None)
        monitor.unregister("anakin-host-loop")
        if aggregator is not None:
            aggregator.close()
            if ops_server is not None:
                ops_server.set_aggregator(None)
        if sentinel is not None:
            # BEFORE fleet stop, so the excepthook chain unwinds in reverse
            # install order. Restores the hook UNLESS a corruption verdict
            # is propagating — that error must still translate to exit code
            # 88 for the supervising launcher after this finally completes.
            sentinel.deactivate()
        if fleet_coord is not None:
            fleet_coord.stop()
        if checkpointer is not None:
            # Drain in-flight async saves; otherwise interpreter shutdown races
            # orbax's executor ("cannot schedule new futures after shutdown").
            checkpointer.close()
        logger.close()

    steady = (
        steps_per_eval * (len(window_walls) - 1) / sum(window_walls[1:])
        if len(window_walls) > 1
        else (steps_per_eval / window_walls[0] if window_walls else 0.0)
    )
    get_registry().gauge(
        "stoix_tpu_runner_steady_state_sps",
        "Post-first-window env-steps/sec of the most recent Anakin run",
    ).set(steady)
    # Close the goodput books: the phase clock told the ledger of each span as
    # it closed and set-up's wall is booked whole, so the residual is steady
    # state's (host idle while the device computes, in the pipelined loop) and
    # goes to compute. Fractions sum to 1 by construction
    # (tests/test_opsplane.py pins it on a real pipelined run).
    goodput_report = ledger.finalize()
    LAST_RUN_STATS.clear()
    LAST_RUN_STATS.update(
        {
            "phase_breakdown": {k: round(v, 6) for k, v in phases.breakdown().items()},
            "loop_wall_s": round(loop_wall_s, 6),
            "setup_phases": {k: round(v, 6) for k, v in setup_phases.seconds().items()},
            "launch_phases": setup_phases.launch,
            "goodput": goodput_report,
            "steady_state_sps": steady,
            "pipelined": pipelined,
            "fused_eval": fused,
            "compile": compile_stats,
            "resilience": {
                "update_guard": guard_mode,
                "skipped_updates": guards.skipped_counter().value() - skipped_base,
                "preempted": preempted,
                "resume_capable": checkpointer is not None,
                "preflight": pf.enabled,
                "fleet": fleet_coord is not None,
                "fleet_agreed_stop": (
                    agreed_stop.describe() if agreed_stop is not None else None
                ),
                "restore_skipped": restore_skipped,
            },
            "integrity": (
                sentinel.stats() if sentinel is not None
                else integrity.disabled_stats()
            ),
            "gossip": (
                {
                    "num_groups": gossip_plan.num_groups,
                    "interval": gossip_plan.interval,
                    "topology": gossip_plan.topology,
                    "mixing_weight": gossip_plan.mixing_weight,
                    "average_opt_states": gossip_plan.average_opt_states,
                    "rounds": gossip_rounds,
                }
                if gossip_plan is not None
                else None
            ),
        }
    )
    return final_return


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
