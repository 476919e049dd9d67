"""Unified telemetry for both Podracer architectures (docs/DESIGN.md §2.2).

Three pillars, all zero-dependency and off by default:

  * **Tracing** (trace.py / trace_export.py): `span()` is the one host
    primitive — always a `jax.profiler.TraceAnnotation`, so any profiler
    session carries the host threads on the device trace's clock; optionally
    a phase-clock feed; recorded for the Chrome-trace/Perfetto JSON export
    (its own epoch) when telemetry is on. `annotate()` tags jitted code with
    a scope name from `SCOPES`.
  * **Metrics** (registry.py / exporters.py): process-wide counters, gauges,
    and histograms with labels, snapshot-on-demand, Prometheus text
    exposition + JSONL sinks. `RunStats` is the dict-compatible per-run view
    that replaced the ad-hoc module-level stats dicts (lint STX002).
  * **Introspection** (introspect.py / health.py): a device-telemetry poller
    (memory_stats, live buffers) sampled off the hot path, plus Sebulba
    heartbeats and a stall detector that names the starved component.

`configure(cfg.logger.telemetry)` is the single switch — called by
StoixLogger on construction. Disabled (the default), spans are bare
TraceAnnotations that record nothing, no poller thread starts, and no files
are written: behavior is bit-identical to a build without telemetry
(tests/test_observability.py pins this) and PR 1's pipelined-loop
no-host-sync guarantees are untouched — every instrument here is host-memory
only.
"""

from __future__ import annotations

import logging
import sys
import threading
from typing import Any, Optional

from stoix_tpu.observability.exporters import (  # noqa: F401 — public API
    JsonlMetricsWriter,
    flatten_snapshot,
    to_prometheus_text,
    write_prometheus,
)
from stoix_tpu.observability.aggregate import (  # noqa: F401
    FleetMetricsAggregator,
    aggregator_from_fleet,
)
from stoix_tpu.observability.flightrec import (  # noqa: F401
    FlightRecorder,
    dump_flight_record,
    get_flight_recorder,
    validate_flight_record,
)
from stoix_tpu.observability.goodput import (  # noqa: F401
    GoodputLedger,
)
from stoix_tpu.observability.health import (  # noqa: F401
    ActorStarvationError,
    HealthMonitor,
    HeartbeatBoard,
    StallDetector,
    get_health_monitor,
)
from stoix_tpu.observability.httpz import (  # noqa: F401
    OpsServer,
    StatusBoard,
    get_status_board,
    render_statusz,
    server_from_config,
)
from stoix_tpu.observability.introspect import (  # noqa: F401
    DeviceTelemetryPoller,
    sample_device_telemetry,
)
from stoix_tpu.observability.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RunStats,
    get_registry,
)
from stoix_tpu.observability.trace import (  # noqa: F401
    BLOCK_SCOPES,
    DELTA_SCOPES,
    DIFFUSION_SCOPES,
    HOST_SPANS,
    HYBRID_SCOPES,
    LATENT_SCOPES,
    PROMPT_SCOPES,
    SCOPES,
    SetupClock,
    WINDOW_SCOPES,
    annotate,
    get_recorder,
    is_enabled,
    set_enabled,
    span,
)
from stoix_tpu.observability.trace_export import (  # noqa: F401
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)

_lock = threading.Lock()
_poller: Optional[DeviceTelemetryPoller] = None
_http_server: Optional[OpsServer] = None


def get_logger(name: str = "stoix_tpu") -> logging.Logger:
    """Library status-line logger. Library code uses this instead of bare
    print() — lint rule STX002 — so stdout stays reserved for machine-readable
    output contracts (bench.py, sweep.py) and the ConsoleSink.

    Defers to the application's logging config when one exists: if the root
    logger (or the 'stoix_tpu' logger itself) already has handlers, nothing
    is attached and records propagate normally. Only in the bare-CLI case —
    no handlers anywhere — does this attach a message-only stderr handler at
    INFO (the behavior the old print() calls had). Call this at the log
    site, not at module import, so an app's logging.basicConfig() wins."""
    root = logging.getLogger("stoix_tpu")
    with _lock:
        if not root.handlers and not logging.getLogger().handlers:
            handler = logging.StreamHandler(sys.stderr)
            handler.setFormatter(logging.Formatter("%(message)s"))
            root.addHandler(handler)
            root.setLevel(logging.INFO)
            root.propagate = False
    return logging.getLogger(name)


def configure(telemetry_cfg: Any = None) -> bool:
    """Apply a `logger.telemetry` config block (a plain/Config dict or None).
    Returns whether telemetry is enabled. Idempotent: reconfiguring replaces
    the poller (and the ops HTTP server); disabling stops them and turns
    span recording off. Output paths are the TelemetrySink's concern
    (utils/logger.py wires them).

    This is also the per-run reset seam for the ops plane (docs/DESIGN.md
    §2.13): every run start — supervised relaunch included — gets a fresh
    HealthMonitor (no stale heartbeat boards from the previous incarnation
    can trip an instant 503/stall verdict) and a fresh flight-recorder ring
    (a crash dump covers THIS run's windows, not the last run's). Both are
    host-memory resets: no device work, bit-identity untouched."""
    cfg = telemetry_cfg or {}
    enabled = bool(cfg.get("enabled", False))
    global _poller, _http_server
    with _lock:
        set_enabled(enabled)
        if _poller is not None:
            _poller.stop()
            _poller = None
        if _http_server is not None:
            _http_server.close()
            _http_server = None
        get_health_monitor().reset()
        get_flight_recorder().clear()
        # `logger.telemetry.http` is its own switch: the endpoints serve the
        # registry/health state that exists regardless of whether span/file
        # telemetry is on. Off by default = no socket, no thread.
        _http_server = server_from_config(cfg.get("http"))
        if enabled:
            # Fresh span buffer per enabled run: without this, a second
            # telemetry run in the same process would export the previous
            # run's spans too (the buffer survives shutdown() so the LAST
            # run stays exportable).
            get_recorder().clear()
            interval = float(cfg.get("device_poll_interval_s", 5.0) or 0.0)
            if interval > 0:
                _poller = DeviceTelemetryPoller(interval_s=interval)
                _poller.start()
            # Seed one synchronous sample so even short runs snapshot device
            # memory series (the poller's first tick is one interval away).
            sample_device_telemetry()
    return enabled


def shutdown() -> None:
    """Stop the poller and the ops HTTP server, and disable span recording
    (buffer/registry contents are kept — the caller may still export
    them)."""
    global _poller, _http_server
    with _lock:
        if _poller is not None:
            _poller.stop()
            _poller = None
        if _http_server is not None:
            _http_server.close()
            _http_server = None
        set_enabled(False)


def get_ops_server() -> Optional[OpsServer]:
    """The live OpsServer started by configure(), or None when
    `logger.telemetry.http.enabled` is off. Tests and the runner read the
    ephemeral port (`get_ops_server().port`) from here; the runner also
    attaches the fleet aggregator through it."""
    with _lock:
        return _http_server
