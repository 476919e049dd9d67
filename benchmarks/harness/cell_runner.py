"""Runs one cell once and builds the result line. What belongs to one
architecture (which seams, what a tick is) is the cell's driver file; what
belongs to one configuration (its plain reference, what is compared and how
closely) is the configuration's reference file; every metric, end-to-end or
per-layer, is a reader file of its own. What is common — the device gate,
the clock, the compile counter, the traced window, the verdict — is here,
and nothing here knows a cell, a network or a metric by name."""

from __future__ import annotations

import math
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import clock as clock_lib
from benchmarks.harness import loader, observe
from benchmarks.harness.trace_capture import TraceWindow


# A traced run's sessions are over by this many seconds after the process
# began: the 360 s the benchmark's contract gives a run, less 30 s for the
# program's own stop, the reference's check after the run and the exit (8 s
# in the Ant cells; the token cell's 100 s replay follows sessions that cost
# 25 s each, three of which are over by 170 s: PERF.md section 2). A further
# session opens only if what the last one cost, as measured, still fits.
SESSIONS_END_BY_S = 330.0


class DeviceMismatch(RuntimeError):
    """JAX found another platform or number of chips than the cell asks for."""


class RunContext:
    """What a driver fills in while the program runs, and what the per-layer
    readers read afterwards."""

    def __init__(self, cell: loader.Cell, seed: int, seconds: float, trace: bool,
                 process_start: float, extra_overrides: Sequence[str]) -> None:
        self.cell = cell
        self.seed = int(seed)
        self.trace = bool(trace)
        self.extra_overrides = list(extra_overrides)
        self.compiles = observe.CompileCounter()
        # How the run is ended when the interval is over: the program's own
        # graceful stop. A driver whose system stops otherwise sets its own.
        self.stop: Callable[[], None] = observe.request_graceful_stop
        # A traced run reports no rate: once its interval is over it goes on
        # while this holds (the tracer needs a further session).
        self.hold_stop: Callable[[], bool] = lambda: False
        self._stop_due = False
        self._stop_lock = threading.Lock()
        self.clock = clock_lib.IntervalClock(
            seconds,
            warmup_ticks=int(cell.spec.get("warmup_ticks", 1)),
            on_deadline=self._interval_over,
            ready=self._ready,
            process_start=process_start,
        )
        self.ready_checks: List[Callable[[], bool]] = []
        # Filled by the driver:
        self.train: List[Tuple[int, Dict[str, float]]] = []  # (tick index, losses)
        self.evals: List[Tuple[int, float]] = []  # (env steps, mean eval return)
        self.misc: List[Tuple[float, Dict[str, float]]] = []  # (time, MISC metrics)
        self.placement: Optional[Dict[str, Any]] = None
        self.networks: Optional[Dict[str, Any]] = None  # what the reference file compares
        self.shapes: Dict[str, Any] = {}  # what the composed config resolved to; flops
        self.health: Dict[str, Any] = {}  # skipped updates, restarts, ...
        self.run_stats: Dict[str, Any] = {}  # the system's LAST_RUN_STATS
        self.problems: List[str] = []  # anything that makes the run incorrect
        # Filled by run_cell:
        self.device: Dict[str, Any] = {}
        self.trace_data: Optional[Any] = None  # trace_reduce.Trace
        self.registry_marks: List[Tuple[int, float, Dict[Any, float]]] = []
        self.cache_stats: Dict[str, int] = {}
        self.errors: Dict[str, Tuple[float, float]] = {}  # reference: (error, tolerance)
        self.rate: Optional[clock_lib.Rate] = None

    def _ready(self) -> bool:
        return all(check() for check in self.ready_checks)

    def _interval_over(self) -> None:
        self._stop_due = True
        self.release_stop()

    def release_stop(self) -> None:
        """Ends the run if its interval is over and nothing holds the stop
        back: called at the deadline (the timer's thread) and at each tick."""
        with self._stop_lock:
            if self._stop_due and not self.hold_stop():
                self._stop_due = False
                self.stop()

    def overrides(self) -> List[str]:
        return self.cell.overrides + [
            f"arch.seed={self.seed}",
            "arch.absolute_metric=False",
            "logger.use_console=False",
            "logger.checkpointing.save_model=False",
        ] + self.extra_overrides

    # Registry deltas over whole ticks: from the tick that ended set-up to the
    # last tick inside the interval (in a traced run, to the tick at which
    # the traced window opened).
    def registry_span(self) -> Optional[Tuple[Dict[Any, float], Dict[Any, float], float]]:
        if len(self.registry_marks) < 2:
            return None
        (_, t0, before), (_, t1, after) = self.registry_marks[0], self.registry_marks[-1]
        return before, after, t1 - t0


def _gate_devices(cell: loader.Cell, require_platform: str) -> List[Any]:
    import jax

    devices = jax.devices()
    if devices[0].platform != require_platform or len(devices) != cell.chips:
        raise DeviceMismatch(
            f"cell {cell.name} needs {cell.chips} {require_platform} chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind}). "
            "There is no fallback: nothing was run."
        )
    return devices


def run_cell(
    cell: loader.Cell, seed: int, seconds: float, trace: bool, process_start: float,
    *, require_platform: str = "tpu", extra_overrides: Sequence[str] = (),
    scratch_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """One run of one cell. `require_platform` and `extra_overrides` exist
    for the CPU rehearsals in tests/benchmark; the command passes neither."""
    devices = _gate_devices(cell, require_platform)
    ctx = RunContext(cell, seed, seconds, trace, process_start, extra_overrides)
    ctx.compiles.install()
    driver = loader.load_driver(cell.driver, cell.root)
    reference = loader.load_reference(cell.reference, cell.root) if cell.reference else None

    tracer: Optional[TraceWindow] = None
    if trace:
        from benchmarks.harness import trace_reduce

        base = scratch_dir or os.path.join(cell.root, "bench_out", "trace")
        directory = os.path.join(base, f"{cell.name}-seed{seed}")
        shutil.rmtree(directory, ignore_errors=True)
        os.makedirs(directory, exist_ok=True)

        def judge(path: str) -> Tuple[Any, Dict[str, Any]]:
            data = trace_reduce.read_xplane(path, host_names=cell.config.get("host_annotations", []))
            return data, trace_reduce.describe(data, cell.config.get("programs", {}).get("learn"))

        tracer = TraceWindow(
            directory, int(cell.spec.get("trace_start_tick", 2)), int(cell.spec.get("trace_ticks", 2)),
            judge, seconds_left=lambda: SESSIONS_END_BY_S - (time.perf_counter() - process_start),
        )
        ctx.hold_stop = lambda: tracer.busy

    def on_tick(index: int, tick: clock_lib.Tick) -> None:
        if ctx.clock.start_index is None:
            return
        # Registry marks at whole ticks of the interval, up to the one the
        # first traced session opens at; then the trace.
        if ctx.clock.in_interval(tick) and not (tracer and (tracer.running or tracer.records)):
            ctx.registry_marks.append((index, tick.time, observe.flat_registry()))
        if tracer is not None:
            tracer.on_tick(index - ctx.clock.start_index)
            ctx.release_stop()

    ctx.clock.on_tick(on_tick)

    # What the reference can check without a run (the functions the learner
    # calls) is checked first and is part of set-up.
    if reference is None:
        ctx.problems.append("the configuration names no reference: nothing says its outputs are correct")
    elif hasattr(reference, "check_before"):
        ctx.errors.update(reference.check_before(ctx))

    try:
        driver.run(ctx)
    finally:
        ctx.clock.cancel()
        if tracer is not None:
            tracer.close()
    run_end = time.perf_counter()

    from stoix_tpu.utils import compilecache

    ctx.cache_stats = compilecache.cache_stats()
    ctx.device = observe.device_facts(devices)
    if reference is not None and hasattr(reference, "check_after"):
        ctx.errors.update(reference.check_after(ctx))
    try:
        return build_result(ctx, tracer, run_end)
    finally:
        if tracer is not None:
            # A trace is hundreds of MB: its directory goes with the run.
            shutil.rmtree(tracer.directory, ignore_errors=True)


def build_result(ctx: RunContext, tracer: Optional[TraceWindow], run_end: float) -> Dict[str, Any]:
    cell, clock = ctx.cell, ctx.clock
    problems = list(ctx.problems)
    detail: Dict[str, Any] = {
        "errors": {name: error for name, (error, _) in ctx.errors.items()},
        "tolerances": {name: tol for name, (_, tol) in ctx.errors.items()},
        "health": ctx.health,
    }

    if clock.start is None:
        raise RuntimeError("set-up never ended: the program finished before its warm-up tick")
    rate = ctx.rate = clock.rate()
    first, last = rate.first, rate.last
    interval_end = clock.start + clock.seconds
    detail.update({
        "ticks_in_interval": last - first + 1,
        "interval_steps": rate.steps,
        "interval_wall_s": rate.seconds,
        "drift_last_over_first_third": clock_lib.drift(clock.ticks, first, last),
        "exit_after_interval_s": run_end - interval_end,
    })

    # correct: device, placement, compilations, losses, health, references,
    # learn_check.
    place = ctx.placement
    if not place or place["platforms"] != [ctx.device["platform"]] or not (
        set(place["device_ids"]) <= set(range(cell.chips))
    ) or len(place["device_ids"]) != int(cell.spec.get("state_chips", cell.chips)):
        problems.append(f"learner state placement {place} not on the cell's chips")
    compiles = ctx.compiles.inside(clock.start, interval_end)
    detail["compiles_in_interval"] = compiles
    if compiles:
        problems.append(f"{compiles} compilation(s) inside the measured interval")

    in_interval = [rec for idx, rec in ctx.train if first < idx <= last]
    failed = sum(1 for rec in in_interval if observe.non_finite(rec))
    skipped = int(ctx.health.get("skipped_updates", 0))
    if any(observe.non_finite(rec) for _, rec in ctx.train):
        problems.append("a logged loss is not finite")
    if not ctx.train:
        problems.append("no training metrics were observed")
    for key in ("skipped_updates", "actor_restarts", "actor_crashes", "evaluator_errors"):
        if ctx.health.get(key, 0):
            problems.append(f"{key} = {ctx.health[key]}")
    if not ctx.errors:
        problems.append("the reference compared nothing")
    for name, (err, tol) in ctx.errors.items():
        if not (err <= tol):
            problems.append(f"reference {name}: error {err:.3e} > {tol:.1e}")
    verdict = observe.learn_check_verdict(ctx.evals, cell.spec.get("learn_check"))
    if verdict is not None:
        detail["learn_check"] = verdict
        if not verdict["ok"]:
            problems.append(f"learn_check failed: {verdict}")
    detail["evals"] = ctx.evals[:3] + ctx.evals[-3:] if len(ctx.evals) > 6 else ctx.evals

    metrics: Dict[str, Dict[str, Any]] = {}
    device = dict(ctx.device)
    breakdown = trace_report = None
    if ctx.trace:
        from benchmarks.harness import trace_reduce

        # What happened to the trace goes on the result line and, for the
        # reader of stderr, into `health`.
        trace_report = tracer.report() if tracer is not None else {"sessions": 0}
        detail["health"] = {**ctx.health, "trace": trace_report}
        if tracer is None or tracer.chosen is None:
            problems.append("the traced run wrote no .xplane.pb")
        else:
            ctx.trace_data, used = tracer.chosen
            sessions = (
                f"{len(tracer.records)} session(s) of {tracer.ticks} ticks, unreadable seconds "
                f"{[round(r.get('unreadable_s', 0.0), 4) for r in tracer.records]}"
            )
            if used.get("chips_with_whole_execution") == 0:
                problems.append(
                    f"no whole learner execution could be read: {sessions}, the per-layer "
                    "metrics that need one are left out"
                )
            if not used.get("window_sound"):
                # The readers of the window's shares return nothing then
                # (trace_reduce.sound_window): none is printed as if right.
                problems.append(
                    "the profiler damaged the traced window: lost_inside_s "
                    f"{used.get('lost_inside_s', 0.0):.4f} of {used.get('raw_window_s', 0.0):.4f} s, "
                    f"whole learner executions on the chip with fewest {used.get('whole_learner_executions')} "
                    f"({sessions}), the window's shares are left out"
                )
            busy = trace_reduce.busy_and_window(ctx.trace_data)
            if busy is None or busy["busy_s"] <= 0.0:
                problems.append("no operation ran on the device in the traced window")
            else:
                # Readable seconds and the busy seconds inside them.
                device["busy_s"] = busy["busy_s"]
                device["window_s"] = busy["window_s"]
            breakdown = {
                "device_ops": trace_reduce.top_device_ops(ctx.trace_data, 10),
                "idle_gaps": trace_reduce.longest_idle_gaps(
                    ctx.trace_data, cell.config.get("host_annotations", []), 10
                ),
            }
    # --trace 0: the cell's end-to-end metrics; --trace 1: its per-layer ones.
    # Each is its own reader file; one that finds nothing to read returns
    # None and is left out of the line.
    kind = "per_layer" if ctx.trace else "end_to_end"
    for entry, read in loader.load_readers(kind, cell.name, cell.root):
        value = read(ctx)
        if value is not None and math.isfinite(float(value)):
            metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
        elif kind == "end_to_end":
            problems.append(f"end-to-end metric {entry['name']} has no value")

    result: Dict[str, Any] = {
        "correct": not problems,
        "attempted": last - first,
        "failed": failed + skipped,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if trace_report is not None:
        result["trace"] = trace_report
    # Every number the reference compared, beside its limit: last on the line.
    result["compared"] = {
        name: {"value": error, "limit": limit} for name, (error, limit) in ctx.errors.items()
    }
    result["problems"] = problems
    result["detail"] = detail
    return result
