"""Seams for reading a run from outside without changing it (the pattern of
chip_smoke.py, copied, not imported). The harness touches the program only
through `config_lib.compose`, `<system>.run_experiment` (and the module
attributes it looks up at call time), `LAST_RUN_STATS`, `get_registry()`,
`compilecache.cache_stats()` and `StoixLogger.log`."""

from __future__ import annotations

import contextlib
import math
import os
import signal
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple


def placement(tree: Any) -> Dict[str, Any]:
    """Where a pytree of jax Arrays lives: platforms, the union of device
    ids, and the fewest devices any one leaf spans."""
    import jax

    platforms, union, narrowest = set(), set(), None
    for leaf in jax.tree.leaves(tree):
        devices = leaf.sharding.device_set
        platforms |= {d.platform for d in devices}
        union |= {d.id for d in devices}
        narrowest = len(devices) if narrowest is None else min(narrowest, len(devices))
    return {
        "platforms": sorted(platforms),
        "device_ids": sorted(union),
        "narrowest_leaf_span": narrowest,
    }


@contextlib.contextmanager
def tee_logger(on_event: Callable[[Dict[str, Any], int, int, Any], None]) -> Iterator[None]:
    """Call `on_event(metrics, t, t_eval, event)` for every StoixLogger.log
    call, before the sinks see it. `t` is the program's own count of training
    env steps so far."""
    from stoix_tpu.utils.logger import StoixLogger

    original = StoixLogger.log

    def log(self, metrics, t, t_eval, event):
        on_event(metrics, t, t_eval, event)
        return original(self, metrics, t, t_eval, event)

    StoixLogger.log = log
    try:
        yield
    finally:
        StoixLogger.log = original


def mean_scalars(metrics: Dict[str, Any]) -> Dict[str, float]:
    import numpy as np

    return {k: float(np.mean(np.asarray(v))) for k, v in metrics.items()}


def non_finite(record: Dict[str, float]) -> Dict[str, float]:
    return {k: v for k, v in record.items() if not math.isfinite(v)}


class CompileCounter:
    """Counts backend compilations by the time they ended (a
    `jax.monitoring` duration listener). `inside(a, b)` is the number that
    ended in [a, b] on the perf_counter clock."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.ended_at: List[float] = []
        self.durations: List[float] = []

    def _listen(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.ended_at.append(time.perf_counter())
            self.durations.append(float(duration))

    def install(self) -> "CompileCounter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def inside(self, start: float, end: float) -> int:
        return sum(1 for t in self.ended_at if start <= t <= end)


def flat_registry() -> Dict[Tuple[str, Tuple[Tuple[str, str], ...], str], float]:
    """The process-wide metrics registry as {(name, labels, field): number}:
    counters and gauges under field "value", histograms under "sum" and
    "count". Two of these subtract into the deltas over an interval."""
    from stoix_tpu.observability import get_registry

    flat: Dict[Tuple[str, Tuple[Tuple[str, str], ...], str], float] = {}
    for name, instrument in get_registry().snapshot().items():
        for series in instrument["series"]:
            labels = tuple(sorted((str(k), str(v)) for k, v in series["labels"].items()))
            if "summary" in series:
                flat[(name, labels, "sum")] = float(series["summary"].get("sum", 0.0))
                flat[(name, labels, "count")] = float(series["summary"].get("count", 0.0))
            else:
                flat[(name, labels, "value")] = float(series["value"])
    return flat


def registry_delta(
    before: Dict[Any, float], after: Dict[Any, float], name: str, field: str = "value",
    **labels: str,
) -> float:
    """Sum over the series of `name` whose labels include `labels` of
    after - before."""
    want = {(k, str(v)) for k, v in labels.items()}
    total = 0.0
    for (series_name, series_labels, series_field), value in after.items():
        if series_name == name and series_field == field and want <= set(series_labels):
            total += value - before.get((series_name, series_labels, series_field), 0.0)
    return total


def request_graceful_stop() -> None:
    """The program's own graceful stop: SIGTERM to this process, which its
    PreemptionHandler turns into a clean return at the next boundary."""
    os.kill(os.getpid(), signal.SIGTERM)


def device_facts(devices: List[Any]) -> Dict[str, Any]:
    """platform / kind / count as JAX reports them, and the peak device
    memory on the fullest chip.

    The TPU allocator keeps two books: `peak_bytes_in_use` counts live
    arrays, `peak_bytes_reserved` the scratch space XLA programs hold while
    they run (a probe on the v5e, PR 22: a program whose `memory_analysis()`
    gives 4,295,064,064 bytes of temp left `peak_bytes_in_use` at its 1 GiB
    of arguments and raised `peak_bytes_reserved` to 4,295,016,448). The two
    peaks need not fall at the same moment, so their sum is no peak of
    anything. `memory_peak_bytes` is the LARGER of the two: each is a peak
    the allocator really saw, so it never overstates what the chip held; it
    understates it by the arrays live while the largest program ran (or, in
    a cell that live arrays fill, by that moment's scratch). Both parts are
    printed beside it."""
    best: Dict[str, int] = {}
    for device in devices:
        stats = {k: int(v) for k, v in (device.memory_stats() or {}).items()}
        in_use, reserved = stats.get("peak_bytes_in_use", 0), stats.get("peak_bytes_reserved", 0)
        if not best or max(in_use, reserved) > best["memory_peak_bytes"]:
            best = {
                "memory_peak_bytes": max(in_use, reserved),
                "peak_bytes_in_use": in_use,
                "peak_bytes_reserved": reserved,
            }
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        **best,
    }


class RecordingLearn:
    """Stands where the jitted Anakin learner stood, forwards every call to
    it (or to the executable the runner AOT-compiles from it) and hands each
    output to `on_output`. One Python call a window; nothing is read from
    the device."""

    def __init__(self, inner: Any, on_output: Callable[[Any], None]) -> None:
        self._inner = inner
        self._on_output = on_output

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        output = self._inner(*args, **kwargs)
        self._on_output(output)
        return output

    def lower(self, *args: Any, **kwargs: Any) -> Any:
        return _RecordingLowered(self._inner.lower(*args, **kwargs), self._on_output)

    def __getattr__(self, name: str) -> Any:
        return getattr(self._inner, name)


class _RecordingLowered:
    def __init__(self, lowered: Any, on_output: Callable[[Any], None]) -> None:
        self._lowered = lowered
        self._on_output = on_output

    def compile(self, *args: Any, **kwargs: Any) -> RecordingLearn:
        return RecordingLearn(self._lowered.compile(*args, **kwargs), self._on_output)

    def __getattr__(self, name: str) -> Any:
        return getattr(self._lowered, name)


def learn_check_verdict(
    evals: List[Tuple[int, float]], learn_check: Optional[Dict[str, Any]]
) -> Optional[Dict[str, Any]]:
    """`evals` is [(env steps, mean evaluation return)]. The verdict on the
    first evaluation at or after `learn_check.steps`; None with no check."""
    if not learn_check:
        return None
    steps, floor = int(learn_check["steps"]), float(learn_check["min_return"])
    for t, value in evals:
        if t >= steps:
            return {"at_steps": t, "return": value, "min_return": floor, "ok": value >= floor}
    return {"at_steps": None, "return": None, "min_return": floor, "ok": False}
