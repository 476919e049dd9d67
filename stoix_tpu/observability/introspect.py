"""Device/runtime introspection sampled OFF the hot path.

A daemon thread polls, per local device, `device.memory_stats()` (PJRT
metadata queries — they read allocator counters, they do not join the device
stream, so polling never stalls a dispatched program) plus the process-wide
live-buffer count (`jax.live_arrays()`), publishing gauges:

    stoix_tpu_device_memory_bytes{device=..., kind=bytes_in_use|peak_bytes_in_use|...,
                                  source=memory_stats|live_buffer_sum}
    stoix_tpu_device_live_buffers{}
    stoix_tpu_device_poll_errors_total{}

Cumulative compile time is a registry counter too, and no business of the
poller's, which only samples what the runtime exposes:
`stoix_tpu_compile_seconds_total{program, stage=trace|lower|backend}`, fed
at compile events by `utils/compilecache.py`'s listener of jax's stage
durations (every program of either architecture, where the counter it
replaced saw the Anakin runner's warm-up span alone).

CPU backends expose no `memory_stats()` (returns None / raises): for those,
`bytes_in_use` is estimated by summing live-buffer nbytes per device (source
label `live_buffer_sum`), so every backend still produces memory series.
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional

from stoix_tpu.observability.registry import MetricsRegistry, get_registry

# memory_stats() keys worth a series (backend-dependent; absent keys skipped).
_MEMORY_KINDS = (
    "bytes_in_use",
    "peak_bytes_in_use",
    "bytes_limit",
    "largest_alloc_size",
    "num_allocs",
)


def sample_device_telemetry(registry: Optional[MetricsRegistry] = None) -> int:
    """One synchronous sample (also the poller's body); returns the number of
    memory series updated. Safe to call from tests without a thread."""
    import jax

    registry = registry or get_registry()
    mem_gauge = registry.gauge(
        "stoix_tpu_device_memory_bytes",
        "Per-device allocator stats from PJRT memory_stats()",
    )
    buf_gauge = registry.gauge(
        "stoix_tpu_device_live_buffers",
        "Live jax.Array count in this process (jax.live_arrays)",
    )
    err_counter = registry.counter(
        "stoix_tpu_device_poll_errors_total",
        "Introspection sampling errors (backend gaps count once per poll)",
    )
    updated = 0
    try:
        devices: List[Any] = jax.local_devices()
    except Exception:  # noqa: BLE001 — backend not initialized yet
        err_counter.inc()
        return 0
    statless = []
    for device in devices:
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — CPU/older plugins: no stats
            stats = None
        if not stats:
            statless.append(device)
            continue
        label_dev = str(device)
        for kind in _MEMORY_KINDS:
            if kind in stats:
                mem_gauge.set(
                    float(stats[kind]),
                    {"device": label_dev, "kind": kind, "source": "memory_stats"},
                )
                updated += 1
    try:
        live = jax.live_arrays()
        buf_gauge.set(float(len(live)))
        if statless:
            # Backend exposes no allocator stats (CPU): estimate bytes in use
            # from live buffers, splitting replicated arrays across devices.
            in_use = {str(d): 0.0 for d in statless}
            for arr in live:
                try:
                    arr_devices = [str(d) for d in arr.devices()]
                    per_device = arr.nbytes / max(1, len(arr_devices))
                except Exception:  # noqa: BLE001 — deleted/exotic arrays
                    continue
                for d in arr_devices:
                    if d in in_use:
                        in_use[d] += per_device
            for d, nbytes in in_use.items():
                mem_gauge.set(
                    nbytes,
                    {"device": d, "kind": "bytes_in_use", "source": "live_buffer_sum"},
                )
                updated += 1
    except Exception:  # noqa: BLE001 — private-ish API; never fatal
        err_counter.inc()
    return updated


class DeviceTelemetryPoller:
    """Daemon polling thread; `interval_s <= 0` disables it entirely."""

    def __init__(self, interval_s: float = 5.0,
                 registry: Optional[MetricsRegistry] = None):
        self._interval = float(interval_s)
        self._registry = registry or get_registry()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if self._interval <= 0 or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="device-telemetry", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self._interval):
            sample_device_telemetry(self._registry)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
