"""Process-wide metrics registry: counters, gauges, histograms with labels.

Zero-dependency (stdlib only). All instruments are host-side and thread-safe;
recording never touches a device or forces a host sync, so always-on recording
preserves the pipelined-loop guarantees (docs/DESIGN.md §2.1). Naming follows
the `stoix_tpu_<area>_<name>` convention (docs/DESIGN.md §2.2); labels are
plain string dicts and each distinct label set is its own series.

Snapshots (`MetricsRegistry.snapshot()`) are point-in-time copies consumed by
the exporters (observability/exporters.py: Prometheus text exposition + JSONL)
and by `RunStats` — the dict-compatible view that replaced the ad-hoc
module-level `LAST_RUN_STATS = {}` accumulators (lint rule STX002 forbids
those in library code).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterable, List, Optional, Tuple

LabelKey = Tuple[Tuple[str, str], ...]

# Bucket upper bounds (seconds) tuned for host-loop phases: sub-ms dispatch
# costs up to minutes-long stalls. +Inf is implicit.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 180.0,
)


def _label_key(labels: Optional[Dict[str, str]]) -> LabelKey:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class _Instrument:
    """One named metric family; per-label-set series live in `_series`."""

    kind = "untyped"

    def __init__(self, name: str, help_text: str = ""):
        self.name = name
        self.help = help_text
        self._lock = threading.Lock()
        self._series: Dict[LabelKey, Any] = {}

    def labels_and_values(self) -> List[Tuple[LabelKey, Any]]:
        with self._lock:
            return list(self._series.items())


class Counter(_Instrument):
    """Monotonically increasing float per label set."""

    kind = "counter"

    def inc(self, amount: float = 1.0, labels: Optional[Dict[str, str]] = None) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease (got {amount})")
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + float(amount)

    def value(self, labels: Optional[Dict[str, str]] = None) -> float:
        with self._lock:
            return float(self._series.get(_label_key(labels), 0.0))


class Gauge(_Instrument):
    """Last-write-wins float per label set."""

    kind = "gauge"

    def set(self, value: float, labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._series[_label_key(labels)] = float(value)

    def inc(self, amount: float = 1.0, labels: Optional[Dict[str, str]] = None) -> None:
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + float(amount)

    def dec(self, amount: float = 1.0, labels: Optional[Dict[str, str]] = None) -> None:
        self.inc(-amount, labels)

    def remove(self, labels: Optional[Dict[str, str]] = None) -> None:
        """Take the series away: what no longer holds is absent, not stale."""
        with self._lock:
            self._series.pop(_label_key(labels), None)

    def value(self, labels: Optional[Dict[str, str]] = None) -> float:
        with self._lock:
            return float(self._series.get(_label_key(labels), 0.0))


class _HistogramSeries:
    __slots__ = ("count", "total", "minimum", "maximum", "bucket_counts")

    def __init__(self, n_buckets: int):
        self.count = 0
        self.total = 0.0
        self.minimum = float("inf")
        self.maximum = float("-inf")
        self.bucket_counts = [0] * (n_buckets + 1)  # last slot = +Inf


class Histogram(_Instrument):
    """Prometheus-style cumulative-bucket histogram per label set."""

    kind = "histogram"

    def __init__(
        self,
        name: str,
        help_text: str = "",
        buckets: Iterable[float] = DEFAULT_BUCKETS,
    ):
        super().__init__(name, help_text)
        self.buckets: Tuple[float, ...] = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError(f"histogram {name} needs at least one bucket bound")

    def observe(self, value: float, labels: Optional[Dict[str, str]] = None) -> None:
        value = float(value)
        key = _label_key(labels)
        with self._lock:
            series = self._series.get(key)
            if series is None:
                series = self._series[key] = _HistogramSeries(len(self.buckets))
            series.count += 1
            series.total += value
            series.minimum = min(series.minimum, value)
            series.maximum = max(series.maximum, value)
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    series.bucket_counts[i] += 1
                    break
            else:
                series.bucket_counts[-1] += 1

    def record(self, labels: Optional[Dict[str, str]], seconds: float) -> None:
        """The `span(..., clock=histogram, phase=labels)` sink: the span's
        seconds observed into the labelled series."""
        self.observe(seconds, labels)

    def summary(self, labels: Optional[Dict[str, str]] = None) -> Dict[str, float]:
        with self._lock:
            series = self._series.get(_label_key(labels))
            if series is None or series.count == 0:
                return {"count": 0, "sum": 0.0}
            return self._summarize(series)

    @staticmethod
    def _summarize(series: _HistogramSeries) -> Dict[str, float]:
        return {
            "count": series.count,
            "sum": series.total,
            "min": series.minimum,
            "max": series.maximum,
            "mean": series.total / series.count,
        }

    def export(self) -> List[Tuple[LabelKey, Dict[str, float], Dict[float, int]]]:
        """Atomic (summary, cumulative-buckets) pairs per label set — ONE
        critical section, so an exported snapshot keeps the Prometheus
        invariant count == +Inf bucket even while other threads observe."""
        out = []
        with self._lock:
            for key, series in self._series.items():
                cumulative, buckets = 0, {}
                for bound, n in zip(self.buckets, series.bucket_counts):
                    cumulative += n
                    buckets[bound] = cumulative
                buckets[float("inf")] = cumulative + series.bucket_counts[-1]
                out.append((key, self._summarize(series), buckets))
        return out


class MetricsRegistry:
    """Named instruments; get-or-create semantics so call sites never race on
    registration. One process-wide default lives behind `get_registry()`."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._instruments: Dict[str, _Instrument] = {}

    def _get_or_create(self, cls, name: str, help_text: str, **kwargs) -> Any:
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = self._instruments[name] = cls(name, help_text, **kwargs)
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name} already registered as {inst.kind}, "
                    f"requested {cls.kind}"
                )
            return inst

    def counter(self, name: str, help_text: str = "") -> Counter:
        return self._get_or_create(Counter, name, help_text)

    def gauge(self, name: str, help_text: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help_text)

    def histogram(
        self,
        name: str,
        help_text: str = "",
        buckets: Iterable[float] = DEFAULT_BUCKETS,
    ) -> Histogram:
        return self._get_or_create(Histogram, name, help_text, buckets=buckets)

    def instruments(self) -> List[_Instrument]:
        with self._lock:
            return list(self._instruments.values())

    def series_count(self) -> int:
        return sum(len(inst.labels_and_values()) for inst in self.instruments())

    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time copy: {name: {"kind", "help", "series": [{"labels",
        "value"|"summary"}]}}. Histogram series carry count/sum/min/max/mean
        plus per-bucket cumulative counts keyed by upper bound."""
        out: Dict[str, Any] = {}
        for inst in self.instruments():
            series_list: List[Dict[str, Any]] = []
            if isinstance(inst, Histogram):
                for key, summary, buckets in inst.export():
                    series_list.append(
                        {"labels": dict(key), "summary": summary, "buckets": buckets}
                    )
            else:
                for key, raw in inst.labels_and_values():
                    series_list.append({"labels": dict(key), "value": float(raw)})
            out[inst.name] = {
                "kind": inst.kind,
                "help": inst.help,
                "series": series_list,
            }
        return out

    def clear(self) -> None:
        with self._lock:
            self._instruments.clear()


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _REGISTRY


class RunStats(dict):
    """Dict-compatible per-run stats view (drop-in for the old module-level
    `LAST_RUN_STATS = {}` accumulators, which lint rule STX002 now forbids).
    Producers publish to the metrics registry during the run and refresh this
    view once at the end; consumers (bench.py, tests) keep plain dict reads."""
