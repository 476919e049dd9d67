"""bench.py output-schema gate.

Runs `bench.py --smoke --cpu` in a subprocess (the bench contract is a
standalone process emitting JSON lines) and validates the payload schema,
including the per-phase host-loop breakdown added by the pipelined runner —
so bench output can never silently regress shape again.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASE_KEYS = (
    "compile_s", "learn_s", "snapshot_s", "eval_s", "fetch_dispatch_s", "fetch_s",
    "log_s", "host_s", "ckpt_s",
)

GOODPUT_KEYS = ("wall_s", "fraction", "stall_s", "recovery_s", "fractions")
GOODPUT_PHASES = {
    "setup", "compute", "eval", "checkpoint", "fetch_wait", "queue_wait",
    "gossip", "compile", "stall", "recovery",
}


def _assert_goodput_shape(payload, live: bool):
    """Goodput ledger fields (docs/DESIGN.md §2.13): first-class on every
    payload. Training probes report a live ledger whose fractions sum to 1;
    workloads that never run a ledger report the zeroed shape — the same
    keys either way, never a missing one."""
    goodput = payload["goodput"]
    assert set(goodput) == set(GOODPUT_KEYS), goodput
    assert set(goodput["fractions"]) == GOODPUT_PHASES, goodput
    assert goodput["stall_s"] >= 0.0 and goodput["recovery_s"] >= 0.0
    if live:
        assert goodput["wall_s"] > 0.0, goodput
        assert 0.0 <= goodput["fraction"] <= 1.0, goodput
        assert abs(sum(goodput["fractions"].values()) - 1.0) < 1e-6, goodput
    else:
        assert goodput["wall_s"] == 0.0 and goodput["fraction"] == 0.0
        assert all(v == 0.0 for v in goodput["fractions"].values()), goodput


@pytest.mark.slow
def test_bench_smoke_payload_schema():
    # Slow lane (tier-1 budget, PR 19): a whole bench subprocess incl. a
    # training probe (~23s); the serve payload schema below keeps a
    # not-slow subprocess pin, and --check gate semantics are covered
    # in-process by tests/test_bench_check.py.
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke", "--cpu"],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"bench.py --smoke failed:\n{proc.stdout}\n{proc.stderr}"

    json_lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, f"expected exactly one JSON line:\n{proc.stdout}"
    payload = json.loads(json_lines[0])

    # Core contract (BASELINE.md): one measurement per line.
    assert payload["metric"] == "anakin_ppo_ant_env_steps_per_sec"
    assert isinstance(payload["value"], (int, float)) and payload["value"] > 0, payload
    assert isinstance(payload["unit"], str) and "env_steps/sec" in payload["unit"]
    assert "vs_baseline" in payload

    # Bench trustworthiness (ROADMAP item 3): the steady-state window is
    # re-measured (--reps, default 3) and the dispersion rides the payload as
    # first-class fields, so a noisy number can never masquerade as a trend.
    assert payload["reps"] == 3, payload
    assert payload["min"] <= payload["median"] <= payload["max"], payload
    # `value` keeps its best-rep semantics: it IS the max-rate rep.
    assert abs(payload["value"] - payload["max"]) <= 0.11, payload
    assert payload["rel_spread"] >= 0.0, payload

    # Pipelined-runner phase attribution: all phases present, numeric, >= 0,
    # and the probe actually ran (no probe_error, nonzero compile).
    phases = payload["phase_breakdown"]
    assert "probe_error" not in phases, phases
    for key in PHASE_KEYS:
        assert isinstance(phases[key], (int, float)) and phases[key] >= 0.0, phases
    assert phases["compile_s"] > 0.0, phases
    assert phases["steady_state_sps"] > 0.0, phases

    # Telemetry self-check (the probe runs with logger.telemetry.enabled):
    # host spans were recorded, the registry carries series, and the exported
    # trace validates against the Chrome trace-event schema.
    telemetry = payload["telemetry"]
    assert telemetry["spans"] > 0, telemetry
    assert telemetry["metric_series"] > 0, telemetry
    assert telemetry["trace_valid"] is True, telemetry

    # Compile economy (docs/DESIGN.md §2.7): the warmup call's wall time and
    # the persistent-cache hits absorbed during this workload are first-class
    # payload fields (the cache is always on; a warm checkout reports hits).
    assert isinstance(payload["compile_s"], (int, float)) and payload["compile_s"] > 0.0
    assert isinstance(payload["cache_hits"], int) and payload["cache_hits"] >= 0, payload

    # Resilience self-check (docs/DESIGN.md §2.3): the bench records whether
    # divergence guards were active for this number, how many updates were
    # skipped, and whether the config could emergency-resume on preemption.
    resilience = payload["resilience"]
    assert resilience["update_guard"] == "off", resilience
    assert resilience["skipped_updates"] == 0, resilience
    assert isinstance(resilience["resume_capable"], bool), resilience

    # State-integrity fields (docs/DESIGN.md §2.9): first-class on every
    # payload so an armed sentinel can never tax a number invisibly — and a
    # disabled one reports the zeroed shape, never a missing key.
    integrity = payload["integrity"]
    assert integrity["enabled"] is False, integrity
    assert integrity["fingerprint_checks"] == 0, integrity
    assert integrity["overhead_s"] == 0.0, integrity
    assert integrity["probe_runs"] == 0, integrity

    # Launch-hardening field (docs/DESIGN.md §2.4): an explicit --cpu run
    # needed no probe. There is no fallback posture to report — a run that
    # cannot reach its backend prints no payload at all.
    assert payload["probe_attempts"] == 0, payload
    assert "fallback" not in payload and "fallback_reason" not in payload
    # Every line says what it ran on; a CPU number is labelled CPU and is
    # not compared with the v5e baseline.
    assert payload["device"]["platform"] == "cpu" and payload["device"]["count"] >= 1
    assert payload["vs_baseline"] is None, payload

    # Goodput ledger of the probe run (docs/DESIGN.md §2.13): the fractions
    # partition the probe's wall clock, and an AOT compile really happened.
    _assert_goodput_shape(payload, live=True)
    assert payload["goodput"]["fractions"]["compile"] > 0.0, payload["goodput"]


def _load_bench_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_rep_stats_and_reps_parsing():
    bench = _load_bench_module()
    # Single rep: today's shape plus the new fields, degenerate dispersion.
    stats = bench._rep_stats([100.0])
    assert stats == {
        "reps": 1, "median": 100.0, "min": 100.0, "max": 100.0, "rel_spread": 0.0
    }
    stats = bench._rep_stats([100.0, 50.0, 80.0])
    assert stats["reps"] == 3
    assert (stats["min"], stats["median"], stats["max"]) == (50.0, 80.0, 100.0)
    assert stats["rel_spread"] == round(50.0 / 80.0, 4)
    # --reps parsing: absent -> None (workload defaults apply), explicit wins.
    assert bench._parse_reps(["--smoke"]) is None
    assert bench._parse_reps(["--smoke", "--reps", "5"]) == 5


def test_bench_serve_payload_schema():
    """`bench.py --serve` (docs/DESIGN.md §2.8): the latency-shaped payload
    is schema-complete — direction=lower_is_better (so --check inverts its
    comparison), value = the BEST (minimum) p99 rep, the full percentile
    ladder, offered/achieved QPS, batch-fill ratio, shed and hot-swap
    counts — alongside the standard rep-dispersion fields."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--serve", "--smoke", "--cpu", "--reps", "2",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"bench.py --serve failed:\n{proc.stdout}\n{proc.stderr}"
    json_lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, f"expected exactly one JSON line:\n{proc.stdout}"
    payload = json.loads(json_lines[0])

    assert payload["metric"] == "serve_ppo_identity_game_p99_latency_ms"
    # Every line says what it ran on: a --cpu number is labelled CPU.
    assert payload["device"]["platform"] == "cpu", payload
    assert payload["direction"] == "lower_is_better"
    assert isinstance(payload["value"], (int, float)) and payload["value"] > 0
    assert "p99" in payload["unit"] and "ms" in payload["unit"]
    assert payload["vs_baseline"] is None  # no latency baseline tracked yet

    # Rep dispersion (same contract as the throughput payloads), with the
    # best-rep semantics MIRRORED: value is the fastest (minimum) p99.
    assert payload["reps"] == 2
    assert payload["min"] <= payload["median"] <= payload["max"]
    assert abs(payload["value"] - payload["min"]) <= 0.11, payload
    assert payload["rel_spread"] >= 0.0

    # The latency body: percentile ladder ordered, occupancy in (0, 1],
    # graceful-degradation counters present.
    latency = payload["latency_ms"]
    assert 0 < latency["p50"] <= latency["p95"] <= latency["p99"] <= latency["max"]
    assert payload["offered_qps"] > 0 and payload["achieved_qps"] > 0
    assert payload["requests"] > 0
    assert payload["shed"] >= 0 and payload["errors"] == 0
    assert 0.0 < payload["batch_fill_ratio"] <= 1.0
    assert payload["hot_swaps"] >= 0
    # Every bucket compiled exactly once (the no-recompile probe rides the
    # payload as compile_count).
    assert payload["compile_count"] >= 1

    # Launch-hardening posture fields are universal across workloads.
    # Serving never opens a training ledger: zeroed shape, never missing.
    _assert_goodput_shape(payload, live=False)


@pytest.mark.slow
def test_bench_sebulba_payload_schema():
    """`bench.py --sebulba`: whole-run env-steps/sec (FPS) is a FIRST-CLASS
    payload field (ROADMAP item-1 leftover) — value + rep dispersion —
    alongside the steady-state `value` the workload always carried.

    Slow lane (the PR 14 budget discipline): a whole-experiment subprocess
    rides outside the 870s tier-1 window; the in-process fps computation is
    covered not-slow via LAST_RUN_STATS in tests/test_integrity.py's
    Sebulba eval-boundary run."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--sebulba", "--smoke", "--cpu",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=600,
        # Strip the conftest 8-virtual-device fan-out: a standalone bench run
        # sees the real device count, and the smoke Sebulba split (actors on
        # device 0, learner on the rest) sizes its env chunks for that.
        env={
            **{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
            "JAX_PLATFORMS": "cpu",
        },
    )
    assert proc.returncode == 0, f"bench.py --sebulba failed:\n{proc.stdout}\n{proc.stderr}"
    json_lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, f"expected exactly one JSON line:\n{proc.stdout}"
    payload = json.loads(json_lines[0])

    assert payload["metric"] == "sebulba_ppo_cartpole_env_steps_per_sec"
    assert payload["value"] > 0 and "steady-state" in payload["unit"]
    # FPS: total env steps over the FULL learner-loop wall (incl. the
    # first-rollout compile the steady window excludes) — so fps is always
    # below the steady rate on a short smoke run, never above it.
    fps = payload["fps"]
    assert fps["value"] > 0, payload
    assert fps["reps"] == payload["reps"] == 1
    assert fps["min"] <= fps["median"] <= fps["max"]
    assert fps["rel_spread"] >= 0.0
    assert fps["value"] <= payload["value"], (fps, payload["value"])
    # The Sebulba learner loop runs a live ledger (queue_wait vs compute).
    _assert_goodput_shape(payload, live=True)


@pytest.mark.slow
def test_bench_population_payload_schema():
    """`bench.py --population` (docs/DESIGN.md §2.11): TWO payload lines —
    P=1 (the bit-identity anchor) and P=8 with live PBT — each carrying
    aggregate env-steps/sec with standard rep dispersion, per-member fitness
    dispersion, and the PBT exploit count; numeric `value` + `median` +
    `rel_spread` keep the lines --check-composable."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--population", "--smoke", "--cpu",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (
        f"bench.py --population failed:\n{proc.stdout}\n{proc.stderr}"
    )
    json_lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(json_lines) == 2, f"expected two JSON lines (P=1, P=8):\n{proc.stdout}"
    p1, p8 = (json.loads(ln) for ln in json_lines)

    assert p1["metric"] == "population_ppo_identity_game_p1_env_steps_per_sec"
    assert p8["metric"] == "population_ppo_identity_game_p8_env_steps_per_sec"
    for payload, pop_size in ((p1, 1), (p8, 8)):
        assert payload["value"] > 0 and "aggregate env_steps/sec" in payload["unit"]
        assert payload["population_size"] == pop_size
        assert payload["reps"] == 1
        assert payload["min"] <= payload["median"] <= payload["max"]
        assert payload["rel_spread"] >= 0.0
        dispersion = payload["member_fitness_dispersion"]
        assert dispersion["members"] == pop_size
        assert dispersion["min"] <= dispersion["median"] <= dispersion["max"]
        assert isinstance(payload["pbt_exploits"], int)
        assert payload["compile_s"] > 0.0  # AOT warmup is real (not degraded)
        # Universal posture fields, like every other workload payload.
        assert "resilience" in payload and "integrity" in payload
    # P=1 never exploits; P=8 runs live truncation selection every window.
    assert p1["pbt_enabled"] is False and p1["pbt_exploits"] == 0
    assert p8["pbt_enabled"] is True and p8["pbt_exploits"] > 0


@pytest.mark.slow
def test_bench_gossip_payload_schema():
    """`bench.py --gossip` (docs/DESIGN.md §2.12): TWO payload lines —
    G=1 (lockstep, the bit-identity anchor: zero gossip rounds) and G=2
    (ring gossip) — each measuring a clean steady-state rate PLUS a twin
    run under an injected `host_stall` straggler, with the retained-
    throughput ratio riding the payload; numeric `value` + `median` +
    `rel_spread` keep the lines --check-composable."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--gossip", "--smoke", "--cpu",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (
        f"bench.py --gossip failed:\n{proc.stdout}\n{proc.stderr}"
    )
    json_lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(json_lines) == 2, f"expected two JSON lines (G=1, G=2):\n{proc.stdout}"
    g1, g2 = (json.loads(ln) for ln in json_lines)

    assert g1["metric"] == "gossip_ppo_identity_game_lockstep_env_steps_per_sec"
    assert g2["metric"] == "gossip_ppo_identity_game_g2_env_steps_per_sec"
    for payload, num_groups in ((g1, 1), (g2, 2)):
        assert payload["value"] > 0 and "env_steps/sec" in payload["unit"]
        assert payload["num_groups"] == num_groups
        assert payload["topology"] == "ring"
        assert payload["gossip_interval"] >= 1
        assert payload["min"] <= payload["median"] <= payload["max"]
        assert payload["rel_spread"] >= 0.0
        # The straggler twin: an injected host_stall ran to completion and
        # produced a comparable rate; retained = stalled / clean best.
        assert payload["stall_s"] >= 1
        assert payload["stalled_env_steps_per_sec"] > 0, payload
        assert 0.0 < payload["throughput_retained"], payload
        # Universal posture fields, like every other workload payload.
        assert "resilience" in payload
    # G=1 is lockstep: the dense pmean spans every device, no gossip ever
    # fires. G=2 averaged across groups at each window boundary.
    assert g1["gossip_rounds"] == 0
    assert g2["gossip_rounds"] > 0


@pytest.mark.slow
def test_bench_elastic_payload_schema():
    """`bench.py --elastic` (docs/DESIGN.md §2.14): the recovery-shaped
    payload is schema-complete — direction=lower_is_better (so --check
    inverts its comparison), value = the BEST (minimum) recovery-wall rep,
    recovery_wall_s dispersion over the relaunch reps, and the
    cycles_survived contract counter that keeps a fast-but-broken relaunch
    from publishing as a win. Slow lane: each cycle is four real training
    subprocesses (two incarnations per leg)."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--elastic", "--smoke", "--cpu",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"bench.py --elastic failed:\n{proc.stdout}\n{proc.stderr}"
    json_lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, f"expected exactly one JSON line:\n{proc.stdout}"
    payload = json.loads(json_lines[0])

    assert payload["metric"] == "elastic_recovery_wall_s"
    assert payload["direction"] == "lower_is_better"
    assert isinstance(payload["value"], (int, float)) and payload["value"] > 0
    assert "recovery wall" in payload["unit"]
    assert payload["vs_baseline"] is None  # no recovery baseline tracked yet

    # Rep dispersion with best-rep semantics MIRRORED for a lower-is-better
    # metric: value is the fastest (minimum) recovery wall.
    assert payload["reps"] >= 2  # one cycle = shrink + grow relaunches
    assert payload["min"] <= payload["median"] <= payload["max"]
    assert payload["value"] == payload["min"], payload
    assert payload["rel_spread"] >= 0.0

    # The contract counter: every cycle upheld §2.14 (consumed request,
    # schema-valid flight record, digest-identical survivors, recovery-phase
    # attribution) — a failing cycle must be visible next to the number.
    assert payload["cycles"] == 1
    assert payload["cycles_survived"] == 1, payload
    legs = payload["legs"]
    assert [leg["action"] for leg in legs] == ["shrink", "grow"], legs
    for leg in legs:
        assert leg["rc"] == 0 and leg["problems"] == [], leg
        assert leg["recovery_wall_s"] > 0.0, leg
    assert legs[0]["from_devices"] == legs[1]["to_devices"] == 8
    assert legs[0]["to_devices"] == legs[1]["from_devices"] == 4

    # Universal posture fields; the goodput is the completing incarnation's
    # live ledger (its recovery phase is what the headline measures).
    _assert_goodput_shape(payload, live=True)
    assert payload["goodput"]["recovery_s"] > 0.0, payload["goodput"]


def test_bench_backend_wedge_aborts_typed_within_deadline():
    # Acceptance pin (docs/DESIGN.md §2.4): with the probe subprocess wedged
    # (backend_wedge chaos fault — the child sleeps before touching jax),
    # bench.py must abort within the configured budget — never hang — with a
    # NON-ZERO exit, the typed BACKEND UNAVAILABLE reason (naming the attempt
    # count) on stderr, and NO result line: nothing is re-run on the CPU and
    # no number appears under the device metric's name.
    import time

    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "STOIX_TPU_FAULT": "backend_wedge",
            "STOIX_BENCH_PROBE_TIMEOUT": "2",
            "STOIX_BENCH_PROBE_ATTEMPTS": "2",
        },
    )
    elapsed = time.monotonic() - start
    assert proc.returncode != 0, f"an unavailable backend must exit non-zero:\n{proc.stdout}"
    assert elapsed < 90.0, f"wedged-backend abort took {elapsed:.0f}s — must not hang"
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")], proc.stdout
    failure_lines = [ln for ln in proc.stderr.splitlines() if ln.startswith("{")]
    assert len(failure_lines) == 1, proc.stderr
    failure = json.loads(failure_lines[0])
    assert failure["metric"] == "anakin_ppo_ant_env_steps_per_sec"
    assert "BACKEND UNAVAILABLE" in failure["error"], failure
    assert failure["probe_attempts"] == 2, failure
    assert "value" not in failure


def test_bench_loop_refuses_composition():
    """`--loop` is its own closed-loop workload (docs/DESIGN.md §2.15): it
    already CONTAINS serving and replay, so composing it with --serve /
    --replay / --integrity / --all must refuse fast with a clear message
    (argument validation, no training run)."""
    for extra in ("--serve", "--replay", "--integrity", "--all"):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "--loop", extra],
            capture_output=True,
            text=True,
            cwd=REPO,
            timeout=60,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode != 0, f"--loop {extra} must refuse"
        out = proc.stdout + proc.stderr
        assert "does not compose" in out, out


@pytest.mark.slow
def test_bench_loop_payload_schema():
    """`bench.py --loop` (docs/DESIGN.md §2.15): the policy-improvement
    payload is schema-complete — end-return delta (live chaos-drill arm vs
    frozen control, higher_is_better) plus the full resilience ledger. The
    workload itself HARD-FAILS on silent drops, a drill with no failover, or
    no canary rollback, so a passing run proves the self-healing contract.
    Slow lane: two closed-loop arms plus a training run in a subprocess."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--loop", "--smoke", "--cpu",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"bench.py --loop failed:\n{proc.stdout}\n{proc.stderr}"
    json_lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, f"expected exactly one JSON line:\n{proc.stdout}"
    payload = json.loads(json_lines[0])

    assert payload["metric"] == "loop_policy_improvement_return_delta"
    assert payload["direction"] == "higher_is_better"
    assert isinstance(payload["value"], (int, float))
    assert "end-return delta" in payload["unit"]
    assert payload["vs_baseline"] is None

    # Dispersion fields are inline full-precision (return deltas live on an
    # ~O(1) scale; _rep_stats' 0.1 rounding would crush them).
    assert payload["reps"] >= 1
    assert payload["min"] <= payload["median"] <= payload["max"]
    assert payload["value"] == payload["max"], payload  # best-delta rep

    # The live-vs-frozen pair behind the delta.
    assert payload["live_return"] is not None
    assert payload["frozen_return"] is not None
    assert round(
        payload["live_return"] - payload["frozen_return"], 4
    ) == payload["value"], payload

    # The resilience ledger: the drill really ran and the contract held.
    assert payload["fault_spec"] == "replica_kill:1,replica_slow:2,feedback_stall:3,swap_poison"
    assert payload["silent_drops"] == 0
    assert payload["accepted"] == payload["completed"] + payload["typed_failures"]
    assert payload["failovers"] >= 1
    assert payload["ejections"] >= 1
    assert payload["replica_kills"] == 1
    assert payload["replica_restarts"] >= 1
    assert payload["canary_rollbacks"] >= 1
    assert payload["publishes"] >= 1
    assert payload["learner_updates"] > 0
    assert payload["episodes"] > 0
    assert payload["p99_latency_ms"] > 0
    assert payload["experience_dropped"] >= 0

    # Universal posture fields: no training sentinel, no run ledger.
    integrity = payload["integrity"]
    assert integrity["enabled"] is False
    _assert_goodput_shape(payload, live=False)


def test_bench_replay_payload_schema():
    """`bench.py --replay` (docs/DESIGN.md §2.10): the transport-shaped
    payload is schema-complete — sampled items/sec headline with standard
    rep dispersion, add/sample throughput, the per-shard occupancy and
    priority-mass vectors, and the transport ledger proving the
    samples-not-experience claim: sampled_bytes_crossed strictly below
    ingested_bytes_total."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--replay", "--smoke", "--cpu", "--reps", "2",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"bench.py --replay failed:\n{proc.stdout}\n{proc.stderr}"
    json_lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, f"expected exactly one JSON line:\n{proc.stdout}"
    payload = json.loads(json_lines[0])

    assert payload["metric"] == "replay_sharded_sample_items_per_sec"
    assert isinstance(payload["value"], (int, float)) and payload["value"] > 0
    assert "transitions/sec" in payload["unit"]
    assert payload["vs_baseline"] is None

    # Rep dispersion, best-rep semantics (max rate, like throughput payloads).
    assert payload["reps"] == 2
    assert payload["min"] <= payload["median"] <= payload["max"]
    assert abs(payload["value"] - payload["max"]) <= 0.11, payload
    assert payload["rel_spread"] >= 0.0

    # The replay body: both throughputs, the CPU harness's 8 virtual shards,
    # per-shard vectors sized to the mesh.
    assert payload["add_items_per_sec"] > 0
    assert payload["sample_items_per_sec"] == payload["value"]
    assert payload["shards"] == 8
    assert len(payload["occupancy"]) == 8
    assert len(payload["priority_mass"]) == 8
    assert all(m > 0 for m in payload["priority_mass"])

    # The measured samples-not-experience claim (ISSUE acceptance): only
    # sampled minibatches cross the interconnect, and they are strictly
    # smaller than what was ingested.
    assert payload["ingested_bytes_total"] > 0
    assert payload["sampled_bytes_crossed"] > 0
    assert payload["sampled_bytes_crossed"] < payload["ingested_bytes_total"]
    assert 0.0 < payload["sampled_to_ingested_ratio"] < 1.0

    # Universal posture fields.
    integrity = payload["integrity"]
    assert integrity["enabled"] is False
    # The replay microbench drives the service directly — no run ledger.
    _assert_goodput_shape(payload, live=False)
