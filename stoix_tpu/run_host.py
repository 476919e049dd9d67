"""The host side of one run (docs/DESIGN.md §2.16): which host subsystems a
run has, the order they come up in and the order they go down in. Both
runners (`systems/runner.py`, `sebulba/runner.py`) build one `RunHost` as
their first statement and close it in their teardown, whatever happened in
between; what only one of them does stays with that one. Everything here is
host-memory bookkeeping unless a config key turns a subsystem on: a default
run gains no thread, no dispatch and no host sync from it.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterator

from stoix_tpu.observability import (
    SetupClock,
    flightrec,
    get_health_monitor,
    get_logger,
    get_status_board,
    goodput,
    span,
)
from stoix_tpu.ops import scan_kernels
from stoix_tpu.resilience import (
    PreemptionHandler,
    Watchdog,
    faultinject,
    fleet,
    guards,
    integrity,
    preflight,
)
from stoix_tpu.utils import compilecache


class RunHost:
    """Opened by `__init__` in the order below, because the order is the
    contract; closed by `close`. A runner calls `open_fleet` where it builds
    its mesh, `open_ops_plane` right after its `StoixLogger`, sets
    `first_tick` where set-up's last phase opens, and calls `watch_for_stop`
    before its loop."""

    # What `close` finds of a run that did not get that far.
    fleet = sentinel = preempt = first_tick = None

    def __init__(self, config: Any, architecture: str, board_name: str) -> None:
        self.config, self.architecture, self.board_name = config, architecture, board_name
        # Goodput ledger (§2.13): before any set-up work, so restore, compile
        # and stall seconds are all inside the attributed wall; active, so
        # sites outside the loop (injected stalls, the watchdog) can charge it.
        self.ledger = goodput.GoodputLedger().start()
        goodput.set_active(self.ledger)
        try:
            # Set-up phases -> stoix_tpu_setup_phase_seconds{phase}: open from
            # here to the close of `first_tick`, and booked whole as the
            # ledger's `setup`.
            self.setup_phases = SetupClock(self.ledger)
            # The chaos plan (§2.3; a no-op unless STOIX_TPU_FAULT or
            # arch.fault_spec is set) before anything is traced: the in-jit
            # nan_loss fault binds at trace time.
            faultinject.configure(config.arch.get("fault_spec"))
            self.guard_mode = guards.resolve_mode(config)
            # Compile economy (§2.7): the persistent cache before the process's
            # first compile (network init included), the multistep scan-kernel
            # default before a learner is traced.
            compilecache.configure(config)
            scan_kernels.configure_from_config(config)
            # Launch hardening (§2.4, arch.preflight; off by default): probe
            # the backend in a SUBPROCESS and cross-validate the config before
            # this process commits to device work, so a wedged PJRT runtime or
            # a bad device split aborts here with a typed error.
            self.preflight = pf = preflight.settings_from_config(config)
            if pf.enabled:
                with span("preflight", clock=self.setup_phases, phase="preflight"):
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
        except BaseException:
            self.close()
            raise

    def watchdog(self, stage: str, deadline_s: float):
        """A deadline Watchdog when preflight is on; a free nullcontext
        otherwise (the off path adds no thread and no work)."""
        if not self.preflight.enabled:
            return contextlib.nullcontext()
        return Watchdog(stage, deadline_s, hard_exit_grace_s=self.preflight.hard_exit_grace_s)

    def open_fleet(self) -> None:
        """Fleet coordination (§2.6, arch.fleet) and the state-integrity
        sentinel (§2.9, arch.integrity); None (the default) = an unchanged
        loop. The fleet starts, and chains its excepthook, first: the runner
        binds the sentinel to its mesh and state and installs its hook later,
        so `close` unwinds the chain in reverse."""
        self.fleet = fleet.fleet_from_config(self.config)
        if self.fleet is not None:
            self.fleet.start()
        self.sentinel = integrity.sentinel_from_config(self.config)

    def open_ops_plane(self, heartbeats: Any, **status: Any) -> None:
        """The ops plane (§2.13), AFTER the runner's StoixLogger: its
        observability.configure() is the per-run reset (a fresh health monitor
        and flight-recorder ring; the ops HTTP server when
        logger.telemetry.http.enabled), so this run's identity and its
        heartbeat board go on the fresh instances. /healthz turns 503 once a
        beat on the board is older than `stale_after_s`."""
        config = self.config
        system, seed = str(config.system.system_name), int(config.arch.seed)
        self.http_cfg = dict(dict(config.logger.get("telemetry") or {}).get("http") or {})
        self.recorder = flightrec.get_flight_recorder()
        self.recorder.set_context(architecture=self.architecture, system=system, seed=seed)
        self.status = get_status_board()
        self.status.update(
            {
                "run_id": f"{system}_seed{seed}",
                "architecture": self.architecture,
                "system": system,
                **status,
            }
        )
        get_health_monitor().register_board(
            self.board_name,
            heartbeats,
            stale_after_s=float(self.http_cfg.get("stale_after_s", 60.0) or 60.0),
        )

    def watch_for_stop(self) -> PreemptionHandler:
        """Graceful preemption (§2.3): SIGTERM/SIGINT set a flag the loop
        reads at its next boundary. Also the base of this run's count of
        skipped updates."""
        self.preempt = PreemptionHandler().install()
        self.skipped_base = guards.skipped_counter().value()
        return self.preempt

    def vote_to_stop(self, where: str) -> None:
        """Fleet mode: a host-local stop request is never acted on alone. It
        becomes this host's flag at the fleet's next agreement, so that every
        host stops at the same boundary."""
        if self.preempt.stop_requested():
            self.fleet.request_stop(
                fleet.FLAG_PREEMPT, note=f"{self.preempt.signal_name} {where}"
            )

    @contextlib.contextmanager
    def interrupt_as_partition(self, rescue: bool = False) -> Iterator[None]:
        """The fleet monitor interrupts the main thread when a peer dies (the
        thread may be wedged in the dead collective, or in a bounded queue
        get): that KeyboardInterrupt becomes the typed error, which the fleet's
        excepthook turns into EXIT_CODE_FLEET_PARTITION for the supervising
        launcher. An operator's ^C (no partition declared) passes untouched.
        `rescue`: save the staged rescue snapshot first (idempotent; the
        monitor usually has)."""
        try:
            yield
        except KeyboardInterrupt:
            if self.fleet is None or not self.fleet.partition_event.is_set():
                raise
            if rescue:
                self.fleet.emergency_save()
            raise self.fleet.partition_error from None

    def close(self) -> None:
        """Whatever happened, and however far the opening got."""
        if self.first_tick is not None:
            self.first_tick.close()  # a run that never completed a window or update
        if self.preempt is not None:
            self.preempt.uninstall()
        goodput.set_active(None)
        get_health_monitor().unregister(self.board_name)
        if self.sentinel is not None:
            # BEFORE the fleet stops, so the excepthook chain unwinds in
            # reverse install order. Restores the hook UNLESS a corruption
            # verdict is propagating: that error must still become exit code
            # 88 after the runner's teardown completes.
            self.sentinel.deactivate()
        if self.fleet is not None:
            self.fleet.stop()

    def run_stats(self, preempted: bool, **resilience: Any) -> dict:
        """The blocks of LAST_RUN_STATS every run has, `resilience` with the
        runner's own keys beside the shared four. Closes the goodput books: spans told the ledger of their seconds as
        they closed and set-up's wall is booked whole, so the residual (host
        idle while the device computes) goes to compute and the fractions
        sum to 1 (tests/test_opsplane.py)."""
        return {
            "goodput": self.ledger.finalize(),
            "setup_phases": {k: round(v, 6) for k, v in self.setup_phases.seconds().items()},
            "launch_phases": self.setup_phases.launch,
            "resilience": {
                "update_guard": self.guard_mode,
                "skipped_updates": guards.skipped_counter().value() - self.skipped_base,
                "preempted": preempted,
                "fleet": self.fleet is not None,
                **resilience,
            },
            "integrity": (
                self.sentinel.stats() if self.sentinel is not None
                else integrity.disabled_stats()
            ),
        }
