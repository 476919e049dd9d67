"""Goodput/badput ledger: wall-clock attribution over a fixed taxonomy
(docs/DESIGN.md §2.13).

Every second of a run is classified into exactly one of ten phases —

    setup       from `run_experiment`'s first statement to the first completed
                window or update (`SetupClock`'s wall), less the compile,
                recovery and stall seconds noted inside it
    compute     device learn steps making training progress (goodput)
    eval        evaluator dispatch/execution
    checkpoint  orbax serialization handed off on the host path
    fetch_wait  host blocked materializing the coalesced metric fetch
    queue_wait  Sebulba learner blocked collecting actor rollouts
    gossip      cross-group parameter mixing dispatch
    compile     AOT warmup / XLA compile
    stall       injected or detected host stalls (faultinject, watchdog)
    recovery    checkpoint restore, actor respawn backoff, rescue saves

— by consuming the phase timings the pipelined runner, the Sebulba core and
the serve worker already record. The ledger is pure host arithmetic over a
monotonic clock: no threads, no device work, always safe to run (the
`logger.telemetry.http` bit-identity pin holds with it active).

Set-up is not goodput. Between `begin_setup()` and `end_setup(wall)` (the
run's `SetupClock` calls both) a note under a steady-state phase is dropped:
the first window's dispatches and waits, the first update's queue wait and
learn step are set-up's, and `end_setup` books the whole of set-up's wall as
`setup`, less what was noted inside it as `compile`, `stall` or `recovery`.

The attribution invariant: `finalize()` assigns the residual wall time (wall
minus the explicitly timed phases) to `compute`; with set-up booked whole,
that residual is steady state's alone. In the pipelined Anakin
loop that residual IS device compute — the host dispatches in microseconds
and idles while the accelerator executes the window — so goodput is measured
as "wall time not proven to be anything else", the same convention Google's
goodput ladder uses. The fractions therefore sum to 1 exactly (±float
epsilon), which tests/test_opsplane.py pins on a real pipelined ff_ppo run.

Exported as `stoix_tpu_goodput_seconds_total{phase=...}` counters plus the
derived `stoix_tpu_goodput_fraction` gauge; bench payloads carry
`goodput {fraction, stall_s, recovery_s, fractions}` first-class.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Mapping, Optional

from stoix_tpu.observability.registry import MetricsRegistry, get_registry

# The fixed taxonomy. Order is presentation order in /statusz and DESIGN.md.
PHASES = (
    "setup",
    "compute",
    "eval",
    "checkpoint",
    "fetch_wait",
    "queue_wait",
    "gossip",
    "compile",
    "stall",
    "recovery",
)

# What a note inside set-up may still be booked as: not part of `setup`.
_KEPT_IN_SETUP = ("compile", "stall", "recovery")

# Anakin runner phase-clock names (stoix_tpu_runner_phase_seconds_total
# labels) -> taxonomy. learn_s is dispatch cost in the pipelined loop; the
# device execution it overlaps lands in the compute residual either way.
# fetch_s is the blocked materialize wait alone; the other host phases
# (snapshot/fetch dispatch, logging, per-window bookkeeping) were part of the
# compute residual before the clock covered them, and stay there.
RUNNER_PHASE_MAP = {
    "compile_s": "compile",
    "learn_s": "compute",
    "gossip_s": "gossip",
    "snapshot_s": "compute",
    "eval_s": "eval",
    "fetch_dispatch_s": "compute",
    "fetch_s": "fetch_wait",
    "log_s": "compute",
    "host_s": "compute",
    "ckpt_s": "checkpoint",
}

# Sebulba TimingTracker keys -> taxonomy (learner-loop attribution).
# `ingest` is the off-policy poll/warmup-block path (ff_dqn): time spent
# waiting on actor experience, same class as the on-policy rollout collect.
SEBULBA_PHASE_MAP = {
    "rollout_get": "queue_wait",
    "ingest": "queue_wait",
    "assemble": "compute",
    "learn": "compute",
}


class GoodputLedger:
    """One run's attribution ledger. `start()` opens the wall clock;
    `note()`/`note_phases()` attribute explicitly timed seconds;
    `finalize()` closes the books, assigns the residual, exports the
    counters/gauge, and returns the report dict bench.py forwards."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._registry = registry or get_registry()
        self._counter = self._registry.counter(
            "stoix_tpu_goodput_seconds_total",
            "Run wall-clock seconds attributed per goodput-taxonomy phase",
        )
        self._gauge = self._registry.gauge(
            "stoix_tpu_goodput_fraction",
            "Goodput (compute) fraction of wall time for the most recent run",
        )
        self._lock = threading.Lock()
        self._seconds: Dict[str, float] = {phase: 0.0 for phase in PHASES}
        self._t0: Optional[float] = None
        self._in_setup = False

    def start(self) -> "GoodputLedger":
        self._t0 = time.perf_counter()
        return self

    def note(self, phase: str, seconds: float) -> None:
        if phase not in self._seconds:
            raise ValueError(
                f"unknown goodput phase {phase!r} (taxonomy: {PHASES})"
            )
        seconds = max(0.0, float(seconds))
        if seconds == 0.0 or (self._in_setup and phase not in _KEPT_IN_SETUP):
            return
        with self._lock:
            self._seconds[phase] += seconds
        self._counter.inc(seconds, {"phase": phase})

    def begin_setup(self) -> None:
        """From here to `end_setup`, steady-state notes are dropped: those
        seconds are set-up's, booked whole when it ends."""
        self._in_setup = True

    def end_setup(self, wall_s: float) -> None:
        """Set-up took `wall_s` seconds: book as `setup` what of them was not
        noted as compile, stall or recovery meanwhile. Once a run."""
        if not self._in_setup:
            return
        self._in_setup = False
        self.note("setup", wall_s - sum(self.seconds().values()))

    def note_phases(
        self, breakdown: Mapping[str, float], mapping: Optional[Mapping[str, str]] = None
    ) -> None:
        """Attribute a whole phase-breakdown dict at once. `mapping` renames
        source keys into the taxonomy (default: the Anakin runner names);
        keys already in the taxonomy pass through, unknown keys are refused
        loudly — an unmapped phase would silently inflate the residual."""
        mapping = dict(RUNNER_PHASE_MAP if mapping is None else mapping)
        for name, seconds in breakdown.items():
            phase = mapping.get(name, name)
            self.note(phase, seconds)

    def seconds(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._seconds)

    def finalize(self, wall_s: Optional[float] = None) -> Dict[str, object]:
        """Close the ledger: residual wall time -> compute, fractions
        normalized to the (possibly clamped) wall so they always sum to 1."""
        if self._t0 is None and wall_s is None:
            raise RuntimeError("GoodputLedger.finalize() before start()")
        wall = float(wall_s) if wall_s is not None else time.perf_counter() - self._t0
        attributed = sum(self.seconds().values())
        residual = wall - attributed
        if residual > 0:
            self.note("compute", residual)
        else:
            # Explicitly timed phases can (rarely) over-cover the wall when
            # timers overlap; the books still balance by taking the
            # attributed total as the denominator.
            wall = attributed
        seconds = self.seconds()
        denom = wall if wall > 0 else 1.0
        fractions = {phase: seconds[phase] / denom for phase in PHASES}
        fraction = fractions["compute"]
        self._gauge.set(fraction)
        return {
            "wall_s": round(wall, 6),
            "fraction": round(fraction, 6),
            "stall_s": round(seconds["stall"], 6),
            "recovery_s": round(seconds["recovery"], 6),
            "seconds": {phase: round(seconds[phase], 6) for phase in PHASES},
            "fractions": {phase: fractions[phase] for phase in PHASES},
        }


_lock = threading.Lock()
_active: Optional[GoodputLedger] = None


def set_active(ledger: Optional[GoodputLedger]) -> None:
    """Install/clear the run's ledger so out-of-loop attribution sites
    (faultinject stalls, supervisor respawn backoff, watchdog verdicts) can
    feed it without threading a handle through every call chain."""
    global _active
    with _lock:
        _active = ledger


def get_active() -> Optional[GoodputLedger]:
    with _lock:
        return _active


def note_stall(seconds: float) -> None:
    """Attribute stall seconds to the active run's ledger (no-op between
    runs — a stall with no ledger has no wall clock to charge)."""
    ledger = get_active()
    if ledger is not None:
        ledger.note("stall", seconds)


def note_recovery(seconds: float) -> None:
    ledger = get_active()
    if ledger is not None:
        ledger.note("recovery", seconds)


def disabled_report() -> Dict[str, object]:
    """The schema-complete zero report for paths that never ran a ledger
    (bench fallback payloads): same keys, all-zero, fraction 0."""
    return {
        "wall_s": 0.0,
        "fraction": 0.0,
        "stall_s": 0.0,
        "recovery_s": 0.0,
        "seconds": {phase: 0.0 for phase in PHASES},
        "fractions": {phase: 0.0 for phase in PHASES},
    }
