"""Unified telemetry subsystem tests (stoix_tpu/observability).

Pins: registry counter/gauge/histogram semantics under threads, Chrome-trace/
Perfetto export schema, Prometheus text exposition parseability, Sebulba
stall diagnosis, TimingTracker percentiles, and — the PR 1 compatibility
contract — that telemetry OFF leaves runner.LAST_RUN_STATS-compatible output
unchanged and records no spans.
"""

import json
import queue
import re
import threading

import numpy as np
import pytest

from stoix_tpu import observability as obs
from stoix_tpu.observability.registry import MetricsRegistry
from stoix_tpu.observability.trace import TraceRecorder
from stoix_tpu.utils.timing import TimingTracker

# ---------------------------------------------------------------- registry


def test_counter_exact_under_threads():
    registry = MetricsRegistry()
    counter = registry.counter("stoix_tpu_test_threads_total")

    def work():
        for _ in range(1000):
            counter.inc(labels={"worker": "shared"})

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert counter.value({"worker": "shared"}) == 8000.0


def test_labels_are_distinct_series_and_kind_conflicts_raise():
    registry = MetricsRegistry()
    gauge = registry.gauge("stoix_tpu_test_gauge")
    gauge.set(1.0, {"a": "x"})
    gauge.set(2.0, {"a": "y"})
    gauge.set(3.0)  # unlabeled series  # noqa: STX019 — deliberate label-split exercise
    assert gauge.value({"a": "x"}) == 1.0
    assert gauge.value({"a": "y"}) == 2.0
    assert gauge.value() == 3.0
    assert registry.series_count() == 3
    try:
        registry.counter("stoix_tpu_test_gauge")  # noqa: STX019 — deliberate kind-conflict exercise
        raise AssertionError("kind conflict should raise")
    except TypeError:
        pass


def test_histogram_summary_and_cumulative_buckets():
    registry = MetricsRegistry()
    hist = registry.histogram("stoix_tpu_test_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        hist.observe(v)
    summary = hist.summary()
    assert summary["count"] == 4
    assert abs(summary["sum"] - 55.55) < 1e-9
    assert summary["min"] == 0.05 and summary["max"] == 50.0
    snap = registry.snapshot()["stoix_tpu_test_seconds"]["series"][0]
    buckets = snap["buckets"]
    # Cumulative and monotonically non-decreasing, +Inf == count.
    assert buckets[0.1] == 1 and buckets[1.0] == 2 and buckets[10.0] == 3
    assert buckets[float("inf")] == 4
    bounds = sorted(buckets)
    assert all(buckets[a] <= buckets[b] for a, b in zip(bounds, bounds[1:]))


def test_run_stats_is_dict_compatible():
    stats = obs.RunStats()
    stats.update({"steady_state_sps": 1.5})
    assert isinstance(stats, dict)
    assert stats.get("steady_state_sps") == 1.5
    stats.clear()
    assert stats.get("steady_state_sps") is None


# ---------------------------------------------------------------- tracing


def test_trace_export_validates_and_is_thread_aware():
    recorder = TraceRecorder()
    recorder.enabled = True
    barrier = threading.Barrier(3)  # overlap so thread idents are distinct

    def worker(i):
        barrier.wait(timeout=10)
        with recorder.span("work", idx=i):
            pass

    threads = [threading.Thread(target=worker, args=(i,), name=f"worker-{i}")
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with recorder.span("main_phase"):
        with recorder.span("nested"):
            pass

    trace = obs.to_chrome_trace(recorder)
    assert obs.validate_chrome_trace(trace) == []
    events = trace["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert len(complete) == 5
    # Complete events sorted by ts; all carry non-negative ts/dur in us.
    ts = [e["ts"] for e in complete]
    assert ts == sorted(ts)
    # Thread metadata names every participating thread.
    names = {e["args"]["name"] for e in meta}
    assert {"worker-0", "worker-1", "worker-2"} <= names
    assert len({e["tid"] for e in complete}) == 4  # 3 workers + main
    # The full object round-trips as JSON (what Perfetto loads).
    assert json.loads(json.dumps(trace)) == trace


def test_span_is_noop_when_disabled():
    recorder = TraceRecorder()
    assert recorder.enabled is False
    with recorder.span("invisible"):
        pass
    assert recorder.event_count() == 0


def test_trace_buffer_bounded_with_drop_count():
    recorder = TraceRecorder(max_events=2)
    recorder.enabled = True
    for i in range(5):
        with recorder.span(f"e{i}"):
            pass
    assert recorder.event_count() == 2
    assert recorder.dropped == 3
    assert obs.to_chrome_trace(recorder)["metadata"]["dropped_events"] == 3


# ------------------------------------------------------------- prometheus

# Exposition-format sample line: metric name, optional {labels}, value.
_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" (-?[0-9.e+-]+|[+-]Inf|NaN)$"
)


def test_prometheus_text_parses_line_by_line():
    registry = MetricsRegistry()
    registry.counter("stoix_tpu_a_total", "a help").inc(3, {"actor": "0"})
    registry.gauge("stoix_tpu_test_b").set(-1.5)
    registry.histogram("stoix_tpu_c_seconds", buckets=(0.5,)).observe(0.1)
    text = obs.to_prometheus_text(registry)
    assert text.endswith("\n")
    for line in text.rstrip("\n").splitlines():
        if line.startswith("# HELP") or line.startswith("# TYPE"):
            continue
        assert _PROM_SAMPLE.match(line), f"unparseable exposition line: {line!r}"
    assert 'stoix_tpu_a_total{actor="0"} 3.0' in text
    assert '# TYPE stoix_tpu_c_seconds histogram' in text
    assert 'stoix_tpu_c_seconds_bucket{le="+Inf"} 1' in text
    assert "stoix_tpu_c_seconds_count 1" in text


def test_jsonl_writer_flattens_labels(tmp_path):
    registry = MetricsRegistry()
    registry.gauge("stoix_tpu_test_depth").set(2.0, {"queue": "rollout", "actor": "1"})
    writer = obs.JsonlMetricsWriter(str(tmp_path / "m.jsonl"))
    writer.write_snapshot(100, registry)
    writer.close()
    rows = [json.loads(l) for l in open(tmp_path / "m.jsonl")]
    assert rows[0]["t"] == 100
    assert rows[0]["metrics"]["stoix_tpu_test_depth{actor=1,queue=rollout}"] == 2.0


# ----------------------------------------------------- health / sebulba


def test_collect_rollouts_names_starved_actor():
    from stoix_tpu.sebulba.core import OnPolicyPipeline

    pipeline = OnPolicyPipeline(num_actors=2)
    pipeline.send_rollout(1, "payload")  # actor-1 delivered; actor-0 never did
    try:
        pipeline.collect_rollouts(timeout=0.05)
        raise AssertionError("expected ActorStarvationError")
    except obs.ActorStarvationError as exc:
        assert exc.actor_id == 0
        assert "actor-0" in str(exc)
        assert "never" in str(exc)  # never beat -> likely crashed in setup
        assert exc.heartbeat_age is None
    # queue.Empty compatibility gone on purpose — but it IS a RuntimeError,
    # which the shutdown paths catch via Exception.
    assert issubclass(obs.ActorStarvationError, RuntimeError)


def test_collect_rollouts_diagnoses_wedged_pipeline():
    from stoix_tpu.sebulba.core import OnPolicyPipeline

    pipeline = OnPolicyPipeline(num_actors=1)
    pipeline.send_rollout(0, "payload")
    assert pipeline.collect_rollouts(timeout=1.0) == ["payload"]
    # Actor-0 beat moments ago but contributes nothing now: the verdict must
    # say the actor is alive and point at the hand-off, with its beat age.
    try:
        pipeline.collect_rollouts(timeout=0.05)
        raise AssertionError("expected ActorStarvationError")
    except obs.ActorStarvationError as exc:
        assert exc.heartbeat_age is not None
        assert "alive" in str(exc) and "last beat" in str(exc)


def test_stall_detector_names_stalled_component():
    board = obs.HeartbeatBoard(MetricsRegistry())
    board.beat("actor-0")
    detector = obs.StallDetector(board, stale_after_s=0.0)
    verdict = detector.diagnose(waiting_on="actor-0")
    assert "actor-0" in verdict and "stalled" in verdict
    assert "never produced" in obs.StallDetector(board).diagnose(waiting_on="actor-7")


def test_queue_metrics_recorded():
    from stoix_tpu.observability import get_registry
    from stoix_tpu.sebulba.core import OnPolicyPipeline

    pipeline = OnPolicyPipeline(num_actors=1)
    pipeline.send_rollout(0, "x")
    pipeline.collect_rollouts(timeout=1.0)
    registry = get_registry()
    depth = registry.gauge("stoix_tpu_sebulba_queue_depth")
    assert depth.value({"queue": "rollout", "actor": "0"}) == 0.0  # drained
    waits = registry.histogram("stoix_tpu_sebulba_queue_get_wait_seconds")
    assert waits.summary({"queue": "rollout", "actor": "0"})["count"] >= 1
    assert pipeline.heartbeats.count("actor-0") >= 1
    assert pipeline.heartbeats.count("learner") >= 1


# -------------------------------------------------- TimingTracker (utils)


def test_timing_tracker_percentiles_empty_and_single():
    timer = TimingTracker()
    assert timer.percentiles("missing") == {}
    assert timer.all_percentiles() == {}
    timer._times.setdefault("x", __import__("collections").deque(maxlen=10)).append(0.5)
    stats = timer.percentiles("x")
    assert stats == {"p50": 0.5, "p95": 0.5, "p99": 0.5, "max": 0.5}
    assert timer.all_percentiles(prefix="pre_")["pre_x_p95"] == 0.5
    assert timer.all_percentiles(prefix="pre_")["pre_x_p99"] == 0.5


def test_timing_tracker_percentiles_window_eviction():
    from collections import deque

    timer = TimingTracker(maxlen=5)
    d = timer._times.setdefault("y", deque(maxlen=5))
    for v in (100.0, 1.0, 2.0, 3.0, 4.0, 5.0):  # 100.0 evicted by maxlen
        d.append(v)
    stats = timer.percentiles("y")
    assert stats["max"] == 5.0  # the evicted outlier is gone
    assert stats["p50"] == 3.0
    assert stats["p95"] == 5.0
    assert stats["p99"] == 5.0
    # all_means API intact alongside.
    assert abs(timer.mean("y") - 3.0) < 1e-9


def test_timing_tracker_p99_separates_tail_from_p50(monkeypatch=None):
    """p99 is the SLO tail statistic (docs/DESIGN.md §2.8): with a window
    large enough to resolve it, one outlier moves p99 but not p50/p95."""
    from collections import deque

    timer = TimingTracker(maxlen=50)
    d = timer._times.setdefault("lat", deque(maxlen=50))
    for _ in range(49):
        d.append(0.010)
    d.append(9.0)  # one tail request
    stats = timer.percentiles("lat")
    assert stats["p50"] == 0.010
    assert stats["p95"] == 0.010
    # nearest-rank with n=50: p99 -> index int(0.99*50+0.5)-1 = 49, the tail.
    assert stats["p99"] == 9.0
    assert stats["max"] == 9.0


# --------------------------------------- telemetry off == seed behavior


def _tiny_anakin_config(tmp_path, enabled: bool):
    from stoix_tpu.utils import config as config_lib

    return config_lib.compose(
        config_lib.default_config_dir(),
        "default/anakin/default_ff_ppo.yaml",
        [
            "env=identity_game",
            "arch.total_num_envs=8",
            "arch.num_updates=2",
            "arch.total_timesteps=~",
            "arch.num_evaluation=1",
            "arch.num_eval_episodes=4",
            "arch.absolute_metric=False",
            "system.rollout_length=4",
            "system.epochs=1",
            "system.num_minibatches=2",
            "logger.use_console=False",
            f"logger.telemetry.enabled={enabled}",
            f"logger.base_exp_path={tmp_path / 'results'}",
        ],
    )


def test_telemetry_off_keeps_last_run_stats_contract_and_records_nothing(tmp_path):
    import glob

    from stoix_tpu.systems import runner
    from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup

    obs.shutdown()  # defensive: a prior test must not leave tracing on
    before = obs.get_recorder().event_count()
    runner.run_anakin_experiment(_tiny_anakin_config(tmp_path, False), learner_setup)
    # No spans recorded, no telemetry directory written.
    assert obs.get_recorder().event_count() == before
    assert glob.glob(str(tmp_path / "results" / "**" / "telemetry"), recursive=True) == []
    # LAST_RUN_STATS keeps the PR 1 schema bench.py and tests read.
    stats = runner.LAST_RUN_STATS
    assert set(stats["phase_breakdown"]) == {
        "compile_s", "learn_s", "snapshot_s", "eval_s", "fetch_dispatch_s", "fetch_s",
        "log_s", "host_s", "ckpt_s",
    }
    assert all(v >= 0.0 for v in stats["phase_breakdown"].values())
    assert stats["phase_breakdown"]["compile_s"] > 0.0
    assert stats["steady_state_sps"] > 0.0
    assert stats["pipelined"] is True and stats["fused_eval"] is False


def test_telemetry_on_writes_valid_trace_and_prometheus(tmp_path):
    import glob

    from stoix_tpu.systems import runner
    from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup

    obs.get_recorder().clear()
    runner.run_anakin_experiment(_tiny_anakin_config(tmp_path, True), learner_setup)
    tdirs = glob.glob(str(tmp_path / "results" / "**" / "telemetry"), recursive=True)
    assert len(tdirs) == 1
    trace = json.load(open(tdirs[0] + "/trace.json"))
    assert obs.validate_chrome_trace(trace) == []
    span_names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"learn_dispatch", "fetch_materialize"} <= span_names
    prom = open(tdirs[0] + "/metrics.prom").read()
    assert "stoix_tpu_runner_phase_seconds_total{" in prom
    assert "stoix_tpu_device_memory_bytes{" in prom
    for line in prom.rstrip("\n").splitlines():
        if not line.startswith("#"):
            assert _PROM_SAMPLE.match(line), f"unparseable line: {line!r}"
    # Registry phase totals are the source LAST_RUN_STATS mirrors.
    phase_counter = obs.get_registry().counter("stoix_tpu_runner_phase_seconds_total")
    assert phase_counter.value({"phase": "compile_s"}) >= (
        runner.LAST_RUN_STATS["phase_breakdown"]["compile_s"]
    )
    # The sink's close() turned tracing back off for the next run.
    assert obs.is_enabled() is False


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: counts what a span opens."""

    opened: list = []

    def __init__(self, name, **kwargs):
        assert not kwargs, "a span's TraceAnnotation carries the name only"
        self.name = name

    def __enter__(self):
        _FakeAnnotation.opened.append(self.name)
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.opened.append("/" + self.name)


class _Clock:
    def __init__(self):
        self.records = []

    def record(self, phase, seconds):
        self.records.append((phase, seconds))


@pytest.mark.parametrize("enabled", [False, True])
def test_span_always_opens_a_trace_annotation(monkeypatch, enabled):
    """The one host primitive: with telemetry off a span is still a
    TraceAnnotation under exactly its name (so any profiler session carries
    it) and records nothing; with telemetry on it is recorded too."""
    from stoix_tpu.observability import trace

    monkeypatch.setattr(trace, "_trace_annotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "opened", [])
    obs.shutdown()
    obs.get_recorder().clear()
    obs.set_enabled(enabled)
    try:
        with obs.span("learn_dispatch", window=3):
            pass
    finally:
        obs.set_enabled(False)
    assert _FakeAnnotation.opened == ["learn_dispatch", "/learn_dispatch"]
    events = obs.get_recorder().events()
    assert [e["name"] for e in events] == (["learn_dispatch"] if enabled else [])
    if enabled:
        assert events[0]["args"] == {"window": 3}


@pytest.mark.parametrize("enabled", [False, True])
def test_span_feeds_its_phase_clock_whether_or_not_it_records(monkeypatch, enabled):
    from stoix_tpu.observability import trace

    monkeypatch.setattr(trace, "_trace_annotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "opened", [])
    obs.get_recorder().clear()
    obs.set_enabled(enabled)
    clock = _Clock()
    try:
        with obs.span("fetch_materialize", clock=clock, phase="fetch_s"):
            pass
        with pytest.raises(KeyError):
            with obs.span("log", clock=clock, phase="log_s"):
                raise KeyError("a span closes, and feeds its clock, on an error too")
    finally:
        obs.set_enabled(False)
    assert [phase for phase, _ in clock.records] == ["fetch_s", "log_s"]
    assert all(seconds >= 0.0 for _, seconds in clock.records)
    assert _FakeAnnotation.opened == [
        "fetch_materialize", "/fetch_materialize", "log", "/log"
    ]
    assert obs.get_recorder().event_count() == (2 if enabled else 0)


def test_span_sinks_histogram_timing_tracker_and_setup_clock():
    """What a span can feed: a labelled wait histogram (phase = its labels),
    a TimingTracker (phase = the timer's name), the set-up gauge."""
    from stoix_tpu.utils.timing import StepAccumulator

    registry = MetricsRegistry()
    hist = registry.histogram("stoix_tpu_test_wait_seconds")
    with obs.span("pipeline_get", clock=hist, phase={"queue": "rollout"}):
        pass
    assert hist.summary({"queue": "rollout"})["count"] == 1
    tracker = TimingTracker()
    with obs.span("learner_update", clock=tracker, phase="learn"):
        pass
    assert tracker.latest("learn") >= 0.0 and "learn_time" in tracker.all_means()
    # Per-step phases of one rollout become ONE sample, the mean a step.
    steps = StepAccumulator()
    for seconds in (0.25, 0.75):
        steps.record("inference", seconds)
    steps.flush(tracker, steps=2)
    assert tracker.latest("inference") == 0.5
    steps.flush(tracker, steps=2)  # flushed: nothing left to record
    assert tracker.mean("inference") == 0.5
    setup = obs.SetupClock()
    with obs.span("env_build", clock=setup, phase="env_build"):
        pass
    gauge = obs.get_registry().gauge("stoix_tpu_setup_phase_seconds")
    assert gauge.value({"phase": "env_build"}) == setup.seconds()["env_build"]
    assert gauge.value({"phase": "first_tick"}) == 0.0  # a fresh run starts at 0


def test_removed_tracing_entry_points_are_gone():
    """`instant` and the public `device_annotation` had no caller: a host
    phase is a `span`, and nothing else."""
    assert not hasattr(obs, "instant") and not hasattr(obs, "device_annotation")
    assert set(obs.SCOPES) >= {
        "rollout", "rollout_policy", "rollout_env", "gae", "minibatch_shuffle"
    }


def test_describe_masks_non_finite():
    # Satellite regression: one NaN/inf must not poison the summary stats
    # (lives here too because the telemetry JSONL rows go through describe
    # consumers; the primary regression test is tests/test_logger.py).
    from stoix_tpu.utils.logger import describe

    stats = describe(np.array([1.0, np.nan, 3.0, np.inf]))
    assert stats["mean"] == 2.0 and stats["min"] == 1.0 and stats["max"] == 3.0
    assert stats["non_finite_count"] == 2.0
