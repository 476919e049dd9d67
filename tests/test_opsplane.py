"""Fleet ops plane tests (docs/DESIGN.md §2.13).

Covers the four new surfaces end to end: the /metrics·/healthz·/statusz·/varz
HTTP server (live mid-run scrape matching the registry byte-for-byte, 503
under injected host_stall and queue_stall faults, per-run lifecycle through
`observability.configure()`), the goodput/badput ledger (taxonomy math,
residual and over-attribution clamping, fractions summing to 1 on a real
pipelined ff_ppo run), the crash flight recorder (ring semantics, schema
validation, and the rc-86/rc-87/rc-88 dump paths each leaving a schema-valid
flight_record.json next to their crash artifacts), the fleet metrics
aggregator (per-host labels over the KV store, torn-blob tolerance), the
Prometheus exposition audit (label-value escaping round-trips, name
sanitization, HELP/TYPE once per family), and the satellite regression that a
supervised relaunch starts with a FRESH health monitor (run_supervised's
fresh-subprocess guarantee, pinned at the configure() seam both paths share).

The telemetry-off bit-identity pin lives here too: `logger.telemetry.http`
on vs off must produce the exact same final eval performance.
"""

import json
import os
import re
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from stoix_tpu import observability as obs
from stoix_tpu.observability import exporters, flightrec, goodput
from stoix_tpu.observability.aggregate import (
    FleetMetricsAggregator,
    decode_snapshot,
    encode_snapshot,
)
from stoix_tpu.observability.health import HeartbeatBoard, get_health_monitor
from stoix_tpu.observability.httpz import (
    OpsServer,
    StatusBoard,
    get_status_board,
    render_statusz,
    server_from_config,
)
from stoix_tpu.observability.registry import MetricsRegistry, get_registry
from stoix_tpu.resilience import faultinject, fleet, integrity, watchdog
from stoix_tpu.resilience.errors import FleetPartitionError, StateCorruptionError
from stoix_tpu.resilience.exit_codes import (
    EXIT_CODE_FLEET_PARTITION,
    EXIT_CODE_STALL,
    EXIT_CODE_STATE_CORRUPTION,
)

# One exposition sample line: name, optional {labels} (values may contain any
# escaped char), numeric value. Tighter than test_observability's pin: label
# values here allow escaped quotes, so the audit tests can round-trip them.
_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\.)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\.)*\")*\})?"
    r" (-?[0-9.e+-]+|[+-]Inf|NaN)$"
)
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\\n]|\\.)*)"')


def _unescape_label_value(value: str) -> str:
    """Inverse of exporters._escape_label_value (the spec's three escapes)."""
    out, i = [], 0
    while i < len(value):
        ch = value[i]
        if ch == "\\" and i + 1 < len(value):
            nxt = value[i + 1]
            out.append({"\\": "\\", '"': '"', "n": "\n"}.get(nxt, "\\" + nxt))
            i += 2
        else:
            out.append(ch)
            i += 1
    return "".join(out)


def _http_get(port: int, path: str):
    url = f"http://127.0.0.1:{port}{path}"
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode("utf-8"), resp.headers.get(
                "Content-Type"
            )
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode("utf-8"), err.headers.get("Content-Type")


def _reset_ops_plane():
    faultinject.reset()
    goodput.set_active(None)
    obs.shutdown()
    get_health_monitor().reset()
    get_status_board().clear()
    flightrec.get_flight_recorder().clear()


@pytest.fixture(autouse=True)
def _ops_plane_isolation():
    # Reset on the way IN as well: other test modules share the process-wide
    # monitor/board/ring singletons and may have left state behind.
    _reset_ops_plane()
    yield
    _reset_ops_plane()


# ------------------------------------------------------------ exposition audit


def test_label_value_escaping_round_trips():
    registry = MetricsRegistry()
    gauge = registry.gauge("stoix_tpu_unit_escape", "escape audit")
    hostile = [
        'back\\slash',
        'quo"te',
        'new\nline',
        'all\\of"them\ntogether',
        'trailing\\',
    ]
    for i, value in enumerate(hostile):
        gauge.set(float(i), {"v": value})
    text = exporters.to_prometheus_text(registry)
    lines = [ln for ln in text.rstrip("\n").splitlines() if not ln.startswith("#")]
    # Every sample stays on ONE line (raw newlines would corrupt the format)
    # and parses under the exposition grammar.
    assert len(lines) == len(hostile)
    recovered = {}
    for line in lines:
        match = _SAMPLE.match(line)
        assert match, f"unparseable exposition line: {line!r}"
        labels = dict(
            (k, _unescape_label_value(v)) for k, v in _LABEL.findall(match.group(2))
        )
        recovered[labels["v"]] = float(match.group(4))
    assert recovered == {value: float(i) for i, value in enumerate(hostile)}


def test_name_sanitization_never_raises_and_is_spec_valid():
    assert exporters.sanitize_metric_name("stoix_tpu_ok_total") == "stoix_tpu_ok_total"
    assert exporters.sanitize_metric_name("rule:recorded:sum") == "rule:recorded:sum"
    assert exporters.sanitize_metric_name("9leads-with.digit") == "_9leads_with_digit"
    assert exporters.sanitize_metric_name("bad metric!") == "bad_metric_"
    assert exporters.sanitize_metric_name("") == "_"
    assert exporters.sanitize_label_name("ok_label") == "ok_label"
    assert exporters.sanitize_label_name("bad-label.x") == "bad_label_x"
    assert exporters.sanitize_label_name("0digit") == "_0digit"
    # Colons are metric-name-only grammar: label names must collapse them.
    assert exporters.sanitize_label_name("a:b") == "a_b"


def test_help_and_type_emitted_once_per_family():
    registry = MetricsRegistry()
    counter = registry.counter("stoix_tpu_unit_family_total", "one header pair")
    for actor in range(3):
        counter.inc(labels={"actor": str(actor)})
    hist = registry.histogram("stoix_tpu_unit_lat_seconds", buckets=(0.1, 1.0))
    hist.observe(0.05, {"path": "a"})
    hist.observe(5.0, {"path": "b"})
    text = exporters.to_prometheus_text(registry)
    assert text.count("# HELP stoix_tpu_unit_family_total") == 1
    assert text.count("# TYPE stoix_tpu_unit_family_total") == 1
    assert text.count("# TYPE stoix_tpu_unit_lat_seconds histogram") == 1
    # All three labeled children render under the single header pair.
    for actor in range(3):
        assert f'stoix_tpu_unit_family_total{{actor="{actor}"}} 1.0' in text
    # Histogram families expand to _bucket/_sum/_count with a +Inf bound.
    assert 'stoix_tpu_unit_lat_seconds_bucket{le="+Inf",path="a"} 1' in text
    assert "stoix_tpu_unit_lat_seconds_sum" in text
    assert "stoix_tpu_unit_lat_seconds_count" in text


# ------------------------------------------------------------- OpsServer unit


def test_ops_server_serves_registry_status_and_varz():
    get_registry().counter(
        "stoix_tpu_unit_opsplane_total", "ops server unit sentinel"
    ).inc(7.0)
    get_status_board().update({"run_id": "unit_run", "architecture": "anakin"})
    server = OpsServer().start()
    try:
        assert server.port > 0
        code, body, ctype = _http_get(server.port, "/metrics")
        assert code == 200
        assert ctype == "text/plain; version=0.0.4; charset=utf-8"
        # The endpoint body IS the registry exposition, byte for byte.
        assert body == exporters.to_prometheus_text(get_registry())
        assert "stoix_tpu_unit_opsplane_total 7.0" in body
        # Trailing slash and query strings route to the same endpoint.
        assert _http_get(server.port, "/metrics/?x=1")[0] == 200

        code, body, ctype = _http_get(server.port, "/varz")
        assert code == 200 and ctype == "application/json"
        varz = json.loads(body)
        assert varz["status"]["run_id"] == "unit_run"
        assert varz["healthy"] is True
        assert varz["metrics"] == exporters.flatten_snapshot(get_registry().snapshot())

        code, body, _ = _http_get(server.port, "/statusz")
        assert code == 200 and "unit_run" in body

        # /metrics/fleet without an aggregator is a 404, not an error.
        code, body, _ = _http_get(server.port, "/metrics/fleet")
        assert code == 404 and "aggregator" in body

        code, body, _ = _http_get(server.port, "/nosuch")
        assert code == 404
        for endpoint in ("/metrics", "/healthz", "/statusz", "/varz"):
            assert endpoint in body  # 404 lists what IS servable
    finally:
        server.close()


def test_healthz_flips_to_503_when_a_board_goes_stale():
    monitor = get_health_monitor()
    board = HeartbeatBoard(registry=MetricsRegistry())
    monitor.register_board("unit-loop", board, stale_after_s=0.15)
    server = OpsServer().start()
    try:
        # Never-beaten components are healthy: compile/warmup precedes the
        # first beat and must not read as a stall.
        assert _http_get(server.port, "/healthz")[0] == 200
        board.beat("window")
        assert _http_get(server.port, "/healthz")[0] == 200
        time.sleep(0.35)
        code, body, _ = _http_get(server.port, "/healthz")
        assert code == 503
        assert "unit-loop" in body
        # A beat recovers the verdict — 503 is live state, not a latch.
        board.beat("window")
        assert _http_get(server.port, "/healthz")[0] == 200
    finally:
        server.close()
        monitor.unregister("unit-loop")


def test_server_from_config_and_configure_lifecycle():
    assert server_from_config(None) is None
    assert server_from_config({"enabled": False}) is None
    # http has its own switch: telemetry disabled, endpoints still up.
    enabled = obs.configure({"http": {"enabled": True, "port": 0}})
    assert enabled is False
    server = obs.get_ops_server()
    assert server is not None
    assert _http_get(server.port, "/healthz")[0] == 200
    # Reconfiguring without http closes the server (per-run lifecycle).
    obs.configure({})
    assert obs.get_ops_server() is None
    obs.configure({"http": {"enabled": True}})
    assert obs.get_ops_server() is not None
    obs.shutdown()
    assert obs.get_ops_server() is None


def test_supervised_relaunch_gets_fresh_health_monitor():
    """Satellite regression: StallDetector/HealthMonitor state is process-
    local and must NOT leak across supervised relaunches. `launcher.py
    --supervise` relaunches in a fresh subprocess, and every in-process run
    start goes through observability.configure() — both paths land on a
    monitor with no boards, no checks, and a re-based watchdog counter
    (run_supervised references this pin)."""
    monitor = get_health_monitor()
    stale_board = HeartbeatBoard(registry=MetricsRegistry())
    stale_board.beat("window")
    time.sleep(0.05)
    monitor.register_board("previous-incarnation", stale_board, stale_after_s=0.01)
    monitor.register_check("previous-check", lambda: "dead component")
    healthy, detail = monitor.verdict()
    assert healthy is False and "previous-incarnation" in detail
    # A watchdog stall from the previous run must not poison the next one.
    get_registry().counter(
        "stoix_tpu_watchdog_stalls_total", "Watchdog deadlines blown, by stage"
    ).inc(labels={"stage": "unit-previous-run"})
    flightrec.get_flight_recorder().record("window", window=99)

    obs.configure({})  # the run-start reset seam

    healthy, detail = get_health_monitor().verdict()
    assert healthy is True, detail
    # The flight-recorder ring is fresh too: a crash dump covers THIS run.
    assert flightrec.get_flight_recorder().events() == []


def test_statusz_surfaces_restore_report_quarantine_and_slo(tmp_path):
    status = StatusBoard()
    registry = MetricsRegistry()
    quarantine = tmp_path / "quarantine.json"
    status.update(
        {
            "run_id": "statusz_unit",
            "architecture": "anakin",
            "system": "ff_ppo",
            "window": 3,
            "step": 4096,
            "restore_skipped": 2,
            "last_restore_report": [
                {"step": 500, "reason": "digest"},
                {"step": 400, "reason": "non_finite"},
            ],
            "quarantine_file": str(quarantine),
        }
    )
    page = render_statusz(status, registry)
    assert "statusz_unit" in page
    assert "restore_skipped" in page and "2" in page
    assert "digest" in page and "non_finite" in page
    # The quarantine pointer renders only once the record actually exists.
    assert "quarantine_record" not in page
    quarantine.write_text("{}")
    assert "quarantine_record" in render_statusz(status, registry)
    # The serve SLO ladder renders from the live provider (serve/server.py
    # registers telemetry.slo_snapshot; a broken provider must not 500).
    status.register_provider("serve_slo", lambda: {"p99_ms": 4.2, "shed": 0})
    page = render_statusz(status, registry)
    assert "serve SLO ladder" in page and "p99_ms" in page
    # A broken provider degrades to an error string (captured in as_dict for
    # /varz) and the page still renders — just without the SLO section.
    status.register_provider("serve_slo", lambda: (_ for _ in ()).throw(ValueError("x")))
    assert "provider error" in str(status.as_dict()["serve_slo"])
    page = render_statusz(status, registry)
    assert "statusz_unit" in page and "serve SLO ladder" not in page


# ------------------------------------------------------------ flight recorder


def test_flight_recorder_ring_and_dump_round_trip(tmp_path):
    recorder = flightrec.FlightRecorder(capacity=8)
    recorder.set_context(run_id="ring_unit", architecture="anakin")
    for i in range(12):
        recorder.record("window", window=i)
    events = recorder.events()
    assert len(events) == 8  # bounded: oldest 4 dropped
    assert [e["window"] for e in events] == list(range(4, 12))
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == 8

    path = recorder.dump(str(tmp_path / "flight_record.json"), "unit dump", 87)
    record = json.load(open(path))
    assert flightrec.validate_flight_record(record) == []
    assert record["reason"] == "unit dump" and record["exit_code"] == 87
    assert record["context"]["run_id"] == "ring_unit"
    assert len(record["events"]) == 8

    recorder.clear()
    assert recorder.events() == []
    # Context is per-run too: a relaunch must re-stamp its own.
    recorder.record("window", window=0)
    fresh = recorder.dump(str(tmp_path / "fresh.json"), "fresh", None)
    assert json.load(open(fresh))["context"] == {}


def test_validate_flight_record_names_each_problem():
    assert flightrec.validate_flight_record([]) != []
    good = {
        "version": 1,
        "reason": "r",
        "exit_code": 88,
        "unix_time": 1.0,
        "context": {},
        "events": [{"seq": 1, "unix_time": 1.0, "kind": "window"}],
    }
    assert flightrec.validate_flight_record(good) == []
    assert any(
        "version" in p
        for p in flightrec.validate_flight_record({**good, "version": 2})
    )
    assert any(
        "reason" in p for p in flightrec.validate_flight_record({**good, "reason": ""})
    )
    assert any(
        "exit_code" in p
        for p in flightrec.validate_flight_record({**good, "exit_code": "87"})
    )
    assert any(
        "events" in p
        for p in flightrec.validate_flight_record({**good, "events": "nope"})
    )
    bad_event = {**good, "events": [{"seq": 1, "unix_time": 1.0, "kind": "a"},
                                    {"seq": 1, "unix_time": 1.0, "kind": "b"}]}
    assert any(
        "strictly increasing" in p for p in flightrec.validate_flight_record(bad_event)
    )
    missing_kind = {**good, "events": [{"seq": 1, "unix_time": 1.0}]}
    assert any(
        "kind" in p for p in flightrec.validate_flight_record(missing_kind)
    )


def test_rc88_quarantine_leaves_schema_valid_flight_record(tmp_path):
    recorder = flightrec.get_flight_recorder()
    recorder.set_context(architecture="anakin", system="ff_ppo")
    recorder.record("window", window=2, step=1024)
    settings = integrity.IntegritySettings(
        enabled=True,
        determinism_probe_interval=0,
        quarantine_file=str(tmp_path / "quarantine.json"),
    )
    sentinel = integrity.StateIntegritySentinel(settings)
    err = StateCorruptionError(
        kind="replica_mismatch",
        groups=["params"],
        devices=[3],
        processes=[0],
        window=3,
        step=1536,
        detail="device 3 fingerprint deviates",
    )
    sentinel._record_quarantine(err)

    assert os.path.isfile(tmp_path / "quarantine.json")
    record = json.load(open(tmp_path / "flight_record.json"))
    assert flightrec.validate_flight_record(record) == []
    assert record["exit_code"] == EXIT_CODE_STATE_CORRUPTION
    assert "state corruption" in record["reason"]
    assert record["context"]["system"] == "ff_ppo"
    kinds = [e["kind"] for e in record["events"]]
    # The ring ends with the verdict itself, after the run's window records.
    assert kinds[0] == "window" and kinds[-1] == "quarantine"
    assert record["events"][-1]["devices"] == [3]


def test_rc87_fleet_excepthook_leaves_schema_valid_flight_record(
    tmp_path, monkeypatch
):
    exits = []
    monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
    # Earlier fleet tests may leak their coordinators' excepthooks (harmless
    # in production, where os._exit never returns and the chain is dead code
    # — but with _exit stubbed every leaked hook would unwind and append its
    # own 87). Re-base on the interpreter default so exactly ONE hook fires.
    monkeypatch.setattr(sys, "excepthook", sys.__excepthook__)
    settings = fleet.FleetSettings(
        enabled=True,
        heartbeat_interval_s=0.05,
        heartbeat_timeout_s=30.0,
        monitor_poll_s=0.05,
        barrier_deadline_s=5.0,
        skew_warn_ratio=2.0,
        exit_grace_s=0.0,
        emergency_dir=str(tmp_path / "fleet_emergency"),
    )
    store = fleet.FakeFleetStore(2)
    coordinator = fleet.FleetCoordinator(
        settings, backend=store.view(0), interrupt_on_partition=False
    )
    coordinator.start()
    try:
        flightrec.get_flight_recorder().set_context(architecture="anakin")
        error = coordinator._declare_partition(
            [1], 30.0, detail="injected for the rc-87 dump pin"
        )
        assert isinstance(error, FleetPartitionError)
        # Declaration alone records the ring event but dumps NO file — a
        # handled partition in a unit test must not litter the worktree.
        assert not os.path.exists(tmp_path / "fleet_emergency" / "flight_record.json")
        # The uncaught-error path (the excepthook start() installed) dumps
        # next to the emergency rescue artifacts, then exits 87.
        sys.excepthook(type(error), error, None)
    finally:
        coordinator.stop()
        coordinator._restore_excepthook()
    assert exits == [EXIT_CODE_FLEET_PARTITION]
    record = json.load(open(tmp_path / "fleet_emergency" / "flight_record.json"))
    assert flightrec.validate_flight_record(record) == []
    assert record["exit_code"] == EXIT_CODE_FLEET_PARTITION
    assert "fleet partition" in record["reason"]
    partition_events = [e for e in record["events"] if e["kind"] == "fleet_partition"]
    assert partition_events and partition_events[0]["missing"] == [1]


def test_rc86_watchdog_hard_exit_leaves_flight_record(tmp_path, monkeypatch):
    exits = []
    monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
    monkeypatch.chdir(tmp_path)  # the rc-86 dump lands under ./checkpoints
    flightrec.get_flight_recorder().record("window", window=0)
    dog = watchdog.Watchdog("first_window", deadline_s=600.0, hard_exit_grace_s=0.01)
    dog._hard_exit()
    assert exits == [EXIT_CODE_STALL]
    record = json.load(open(tmp_path / "checkpoints" / "flight_record.json"))
    assert flightrec.validate_flight_record(record) == []
    assert record["exit_code"] == EXIT_CODE_STALL
    assert "first_window" in record["reason"]


# ------------------------------------------------------------- goodput ledger


def test_goodput_ledger_residual_fractions_and_export():
    registry = MetricsRegistry()
    ledger = goodput.GoodputLedger(registry=registry).start()
    ledger.note("compile", 1.0)
    ledger.note("eval", 0.5)
    ledger.note("stall", 0.25)
    ledger.note("recovery", 0.125)
    ledger.note("eval", -4.0)  # clamped: negative time never un-attributes
    with pytest.raises(ValueError):
        ledger.note("daydreaming", 1.0)
    report = ledger.finalize(wall_s=4.0)
    assert report["wall_s"] == 4.0
    # Residual wall time is compute: 4.0 - 1.875 attributed.
    assert report["seconds"]["compute"] == pytest.approx(2.125)
    assert report["stall_s"] == 0.25 and report["recovery_s"] == 0.125
    assert set(report["fractions"]) == set(goodput.PHASES)
    assert sum(report["fractions"].values()) == pytest.approx(1.0, abs=1e-9)
    assert report["fraction"] == pytest.approx(2.125 / 4.0)
    # Exported: the counter carries per-phase seconds, the gauge the fraction.
    counter = registry.counter("stoix_tpu_goodput_seconds_total")
    assert counter.value({"phase": "compile"}) == 1.0
    assert registry.gauge("stoix_tpu_goodput_fraction").value() == pytest.approx(
        report["fraction"]
    )


def test_goodput_setup_is_booked_whole_and_is_not_compute():
    """Between begin_setup and end_setup steady-state notes are dropped (the
    first window's waits are set-up's) and compile, stall and recovery kept;
    end_setup books what is left of set-up's wall as `setup`, once."""
    ledger = goodput.GoodputLedger(registry=MetricsRegistry()).start()
    ledger.begin_setup()
    ledger.note("compile", 5.0)
    ledger.note("recovery", 1.0)
    ledger.note("fetch_wait", 7.0)  # window 0's wait: dropped
    ledger.note("compute", 0.5)  # window 0's dispatches: dropped
    ledger.end_setup(20.0)
    ledger.end_setup(99.0)  # a run's finally closes the clock again: no effect
    ledger.note("fetch_wait", 2.0)
    report = ledger.finalize(wall_s=30.0)
    assert report["seconds"]["setup"] == pytest.approx(14.0)
    assert report["seconds"]["compile"] == 5.0 and report["seconds"]["recovery"] == 1.0
    assert report["seconds"]["fetch_wait"] == 2.0
    assert report["seconds"]["compute"] == pytest.approx(30.0 - 20.0 - 2.0)  # the residual
    assert sum(report["fractions"].values()) == pytest.approx(1.0, abs=1e-9)
    assert set(report["fractions"]) == set(goodput.PHASES) and len(goodput.PHASES) == 10


def test_goodput_overattribution_clamps_to_attributed_wall():
    ledger = goodput.GoodputLedger(registry=MetricsRegistry()).start()
    ledger.note("compute", 2.0)
    report = ledger.finalize(wall_s=1.0)  # timers over-covered the wall
    assert report["wall_s"] == 2.0
    assert sum(report["fractions"].values()) == pytest.approx(1.0, abs=1e-9)
    assert report["fraction"] == pytest.approx(1.0)


def test_goodput_phase_maps_and_note_phases():
    assert set(goodput.RUNNER_PHASE_MAP.values()) <= set(goodput.PHASES)
    assert set(goodput.SEBULBA_PHASE_MAP.values()) <= set(goodput.PHASES)
    ledger = goodput.GoodputLedger(registry=MetricsRegistry()).start()
    ledger.note_phases(
        {"compile_s": 1.0, "learn_s": 2.0, "eval_s": 0.5, "fetch_s": 0.25,
         "ckpt_s": 0.125, "gossip_s": 0.0625}
    )
    seconds = ledger.seconds()
    assert seconds["compile"] == 1.0 and seconds["compute"] == 2.0
    assert seconds["fetch_wait"] == 0.25 and seconds["gossip"] == 0.0625
    # Sebulba keys route through their own map (ingest == queue_wait).
    ledger.note_phases({"rollout_get": 1.0, "ingest": 1.0},
                       mapping=goodput.SEBULBA_PHASE_MAP)
    assert ledger.seconds()["queue_wait"] == 2.0
    with pytest.raises(ValueError):
        ledger.note_phases({"mystery_s": 1.0})  # unmapped keys refuse loudly


def test_goodput_module_level_sites_and_disabled_report():
    ledger = goodput.GoodputLedger(registry=MetricsRegistry()).start()
    goodput.set_active(ledger)
    try:
        goodput.note_stall(0.5)
        goodput.note_recovery(0.25)
    finally:
        goodput.set_active(None)
    assert ledger.seconds()["stall"] == 0.5
    assert ledger.seconds()["recovery"] == 0.25
    goodput.note_stall(99.0)  # no active ledger: silently dropped
    assert ledger.seconds()["stall"] == 0.5
    # The disabled report is schema-complete (bench payloads for workloads
    # that never run a ledger carry the same keys, zeroed).
    live = ledger.finalize(wall_s=1.0)
    disabled = goodput.disabled_report()
    assert set(disabled) == set(live)
    assert set(disabled["fractions"]) == set(goodput.PHASES)
    assert all(v == 0.0 for v in disabled["fractions"].values())
    assert disabled["fraction"] == 0.0


# -------------------------------------------------------- fleet metrics fold


def test_fleet_aggregator_folds_hosts_with_labels_and_skips_torn_blobs():
    store = fleet.FakeFleetStore(2)
    reg0, reg1 = MetricsRegistry(), MetricsRegistry()
    reg0.counter("stoix_tpu_unit_fleet_total", "fold unit").inc(1.0)
    reg1.counter("stoix_tpu_unit_fleet_total", "fold unit").inc(2.0)
    reg1.histogram("stoix_tpu_unit_fleet_seconds", buckets=(0.1, 1.0)).observe(0.5)
    agg0 = FleetMetricsAggregator(store.view(0), 0, 2, registry=reg0, interval_s=60.0)
    agg1 = FleetMetricsAggregator(store.view(1), 1, 2, registry=reg1, interval_s=60.0)
    agg1.publish_once()
    text = agg0.render()  # host 0 renders its own live snapshot + peers' blobs
    assert 'stoix_tpu_unit_fleet_total{host="0"} 1.0' in text
    assert 'stoix_tpu_unit_fleet_total{host="1"} 2.0' in text
    # Histogram buckets survive the KV round trip, +Inf bound included.
    assert 'stoix_tpu_unit_fleet_seconds_bucket{host="1",le="+Inf"} 1' in text
    assert text.count("# TYPE stoix_tpu_unit_fleet_total") == 1
    for line in text.rstrip("\n").splitlines():
        if not line.startswith("#"):
            assert _SAMPLE.match(line), f"unparseable fleet line: {line!r}"
    # The encode/decode pair is the publish transport.
    snap = decode_snapshot(encode_snapshot(reg1.snapshot()))
    series = snap["stoix_tpu_unit_fleet_seconds"]["series"][0]
    assert series["buckets"][float("inf")] == 1
    # A torn blob degrades to this-peer-missing, never a render crash.
    store.put("ometrics/1", "{definitely not json")
    text = agg0.render()
    assert 'host="0"' in text and 'host="1"' not in text

    # /metrics/fleet serves the fold once an aggregator is attached.
    server = OpsServer().start()
    try:
        server.set_aggregator(agg0)
        code, body, ctype = _http_get(server.port, "/metrics/fleet")
        assert code == 200 and "version=0.0.4" in ctype
        assert 'stoix_tpu_unit_fleet_total{host="0"} 1.0' in body
    finally:
        server.close()
    agg0.close()
    agg1.close()


# ------------------------------------------------- queue_stall /healthz (503)


def test_healthz_503_under_injected_queue_stall():
    faultinject.configure("queue_stall:3")
    monitor = get_health_monitor()
    board = HeartbeatBoard(registry=MetricsRegistry())
    monitor.register_board("sebulba-pipeline", board, stale_after_s=0.15)
    board.beat("actor-0")
    ledger = goodput.GoodputLedger(registry=MetricsRegistry()).start()
    goodput.set_active(ledger)
    server = OpsServer().start()
    abort = threading.Event()
    wedged = threading.Thread(
        target=faultinject.maybe_stall_queue,
        args=(0, 3),
        kwargs={"should_abort": abort.is_set},
        daemon=True,
    )
    try:
        assert _http_get(server.port, "/healthz")[0] == 200
        wedged.start()
        # Non-matching actors/rollouts pass straight through (no wedge).
        faultinject.maybe_stall_queue(1, 3, should_abort=lambda: True)
        time.sleep(0.35)  # actor-0 is wedged, its beats have stopped
        code, body, _ = _http_get(server.port, "/healthz")
        assert code == 503
        assert "sebulba-pipeline" in body
    finally:
        abort.set()
        wedged.join(timeout=5.0)
        server.close()
        monitor.unregister("sebulba-pipeline")
    # The wedge seconds are stall badput on the active ledger, and the
    # fault left its ring event for a later crash dump.
    assert ledger.seconds()["stall"] > 0.0
    events = flightrec.get_flight_recorder().events()
    assert any(e.get("fault") == "queue_stall" for e in events)


# ------------------------------------------------------- e2e: real tiny runs


def _tiny_run_config(tmp_path, extra_overrides=()):
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
            "logger.telemetry.enabled=False",
            f"logger.base_exp_path={tmp_path / 'results'}",
            *extra_overrides,
        ],
    )


def test_http_on_is_bit_identical_and_live_scrape_matches_registry(tmp_path):
    """The tentpole acceptance trio in one pair of runs: (1) http off vs on
    produces the exact same final eval performance (the endpoints are pure
    readers); (2) a LIVE mid-run scrape succeeds against the ephemeral port;
    (3) the post-run /metrics body is byte-identical to the registry
    exposition, and the run's goodput fractions sum to 1."""
    from stoix_tpu.systems import runner
    from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup

    obs.shutdown()
    result_off = runner.run_anakin_experiment(
        _tiny_run_config(tmp_path / "off"), learner_setup
    )

    scrapes = []
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            server = obs.get_ops_server()
            if server is not None:
                try:
                    scrapes.append(_http_get(server.port, "/metrics"))
                except OSError:
                    pass
            time.sleep(0.05)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        result_on = runner.run_anakin_experiment(
            _tiny_run_config(
                tmp_path / "on", ["logger.telemetry.http.enabled=True"]
            ),
            learner_setup,
        )
    finally:
        stop.set()
        poller.join(timeout=5.0)

    # Bit-identity: the ops plane is host-memory-only reads.
    assert result_on == result_off

    live = [s for s in scrapes if s[0] == 200 and "stoix_tpu_" in s[1]]
    assert live, "no successful live scrape landed during the run"

    # telemetry.enabled stays false, so no sink shut the server down: the
    # post-run page must match the registry byte for byte and parse clean.
    server = obs.get_ops_server()
    assert server is not None
    code, body, ctype = _http_get(server.port, "/metrics")
    assert code == 200 and ctype == "text/plain; version=0.0.4; charset=utf-8"
    assert body == exporters.to_prometheus_text(get_registry())
    for line in body.rstrip("\n").splitlines():
        if not line.startswith("#"):
            assert _SAMPLE.match(line), f"unparseable exposition line: {line!r}"
    assert "stoix_tpu_goodput_seconds_total{" in body

    code, page, _ = _http_get(server.port, "/statusz")
    assert code == 200 and "ff_ppo" in page and "goodput ledger" in page

    report = runner.LAST_RUN_STATS["goodput"]
    assert set(report["fractions"]) == set(goodput.PHASES)
    assert sum(report["fractions"].values()) == pytest.approx(1.0, abs=1e-6)
    assert report["wall_s"] > 0.0
    assert 0.0 <= report["fraction"] <= 1.0
    assert report["seconds"]["compile"] > 0.0  # AOT compile was attributed


@pytest.mark.slow
def test_healthz_503_under_injected_host_stall(tmp_path):
    """/healthz goes 503 while the injected host_stall wedges the window
    loop past stale_after_s, and the stalled second lands in the goodput
    ledger as badput — on a REAL pipelined ff_ppo run."""
    from stoix_tpu.systems import runner
    from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup

    codes = []
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            server = obs.get_ops_server()
            if server is not None:
                try:
                    codes.append(_http_get(server.port, "/healthz")[0])
                except OSError:
                    pass
            time.sleep(0.03)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        runner.run_anakin_experiment(
            _tiny_run_config(
                tmp_path,
                [
                    "logger.telemetry.http.enabled=True",
                    "logger.telemetry.http.stale_after_s=0.25",
                    "arch.num_evaluation=2",  # host_stall fires at window 1
                    "arch.fault_spec=host_stall:1",
                ],
            ),
            learner_setup,
        )
    finally:
        stop.set()
        poller.join(timeout=5.0)

    assert 200 in codes, "server never answered healthy"
    assert 503 in codes, "the injected stall never surfaced on /healthz"
    report = runner.LAST_RUN_STATS["goodput"]
    assert report["stall_s"] >= 0.9  # the injected 1s sleep, attributed
    assert sum(report["fractions"].values()) == pytest.approx(1.0, abs=1e-6)
    events = flightrec.get_flight_recorder().events()
    assert any(e.get("fault") == "host_stall" for e in events)
    assert any(e["kind"] == "window" for e in events)
