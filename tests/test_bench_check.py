"""`bench.py --check` — the variance-aware regression gate's contract.

Exit semantics for CI / fleet prologs: 0 = every compared metric within its
variance band, 1 = regression / failed workload, 2 = usage or file errors.
The gate never imports jax (subprocess tests assert it stays fast enough for
a prolog).
"""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_check_under_test", os.path.join(REPO, "bench.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _payload(metric="anakin_ppo_ant_env_steps_per_sec", median=10000.0, **over):
    return {
        "metric": metric, "value": median * 1.02, "median": median,
        "rel_spread": 0.05, **over,
    }


# ---- check_payloads unit semantics ------------------------------------------


def test_within_band_jitter_passes():
    bench = _bench()
    code, verdicts = bench.check_payloads(
        [_payload(rel_spread=0.08)], [_payload(median=9300.0, rel_spread=0.02)]
    )
    assert code == 0 and verdicts[0]["status"] == "pass", verdicts


def test_regression_beyond_band_fails():
    bench = _bench()
    code, verdicts = bench.check_payloads(
        [_payload(rel_spread=0.08)], [_payload(median=4296.0, rel_spread=0.01)]
    )
    assert code == 1 and verdicts[0]["status"] == "fail"
    assert "regression" in verdicts[0]["reason"]


def test_band_is_max_of_spreads_and_threshold():
    bench = _bench()
    # candidate spread wider than baseline's: a drop inside ITS spread passes.
    code, verdicts = bench.check_payloads(
        [_payload(rel_spread=0.0)], [_payload(median=8000.0, rel_spread=0.25)]
    )
    assert code == 0, verdicts
    # both spreads tiny: the floor threshold governs.
    code, verdicts = bench.check_payloads(
        [_payload(rel_spread=0.0)],
        [_payload(median=9800.0, rel_spread=0.0)],
        threshold=0.05,
    )
    assert code == 0 and verdicts[0]["band"] == 0.05
    code, _ = bench.check_payloads(
        [_payload(rel_spread=0.0)],
        [_payload(median=9300.0, rel_spread=0.0)],
        threshold=0.05,
    )
    assert code == 1


def test_lower_is_better_latency_rise_fails_drop_passes():
    """Serve payloads carry direction=lower_is_better (docs/DESIGN.md §2.8):
    a latency RISE beyond the band is the regression, a drop never is —
    the exact mirror of the throughput rule."""
    bench = _bench()
    base = _payload(
        metric="serve_ppo_identity_game_p99_latency_ms",
        median=3.0, rel_spread=0.05, direction="lower_is_better",
    )
    # Rise beyond the band: fail.
    code, verdicts = bench.check_payloads(
        [base],
        [_payload(
            metric="serve_ppo_identity_game_p99_latency_ms",
            median=4.0, rel_spread=0.02, direction="lower_is_better",
        )],
    )
    assert code == 1 and verdicts[0]["status"] == "fail", verdicts
    assert "lower is better" in verdicts[0]["reason"]
    assert verdicts[0]["direction"] == "lower_is_better"
    # A big latency DROP (would fail the throughput rule) passes.
    code, verdicts = bench.check_payloads(
        [base],
        [_payload(
            metric="serve_ppo_identity_game_p99_latency_ms",
            median=1.0, rel_spread=0.02, direction="lower_is_better",
        )],
    )
    assert code == 0 and verdicts[0]["status"] == "pass", verdicts
    # Rise INSIDE the band is jitter, not a regression.
    code, verdicts = bench.check_payloads(
        [base],
        [_payload(
            metric="serve_ppo_identity_game_p99_latency_ms",
            median=3.1, rel_spread=0.02, direction="lower_is_better",
        )],
    )
    assert code == 0 and verdicts[0]["status"] == "pass", verdicts


def test_lower_is_better_direction_taken_from_baseline_on_disagreement():
    """The BASELINE's direction defines the metric: a candidate missing the
    field still gates the right way up (and vice versa a candidate-only
    direction is honored for fresh metrics)."""
    bench = _bench()
    base = _payload(metric="m_lat", median=3.0, direction="lower_is_better")
    cand = _payload(metric="m_lat", median=10.0)  # no direction field
    code, verdicts = bench.check_payloads([base], [cand])
    assert code == 1 and verdicts[0]["status"] == "fail", verdicts
    # Candidate-only direction (baseline predates the field).
    base = _payload(metric="m_lat2", median=3.0)
    cand = _payload(metric="m_lat2", median=1.0, direction="lower_is_better")
    code, verdicts = bench.check_payloads([base], [cand])
    assert code == 0 and verdicts[0]["status"] == "pass", verdicts


def test_improvement_never_fails():
    bench = _bench()
    code, verdicts = bench.check_payloads(
        [_payload()], [_payload(median=50000.0)]
    )
    assert code == 0, verdicts


def test_failed_workload_line_fails():
    bench = _bench()
    code, verdicts = bench.check_payloads(
        [_payload()], [_payload(median=0.0, value=0.0)]
    )
    assert code == 1 and "failed workload" in verdicts[0]["reason"]


def test_baseline_only_metrics_get_visible_skip_and_require_all_fails():
    bench = _bench()
    baselines = [_payload(), _payload(metric="anakin_sac_ant_env_steps_per_sec")]
    # A candidate that measured only a subset (e.g. the run was killed after
    # the first workload) must not clear the gate SILENTLY: the uncovered
    # metric carries a visible skip verdict, and --check-require-all turns
    # it into a failure.
    code, verdicts = bench.check_payloads(baselines, [_payload(median=9800.0)])
    assert code == 0
    skips = [v for v in verdicts if v["status"] == "skip"]
    assert len(skips) == 1 and "absent from the candidate" in skips[0]["reason"]
    code, verdicts = bench.check_payloads(
        baselines, [_payload(median=9800.0)], require_all=True
    )
    assert code == 1
    assert any(
        v["status"] == "fail" and "absent from the candidate" in v["reason"]
        for v in verdicts
    )


def test_empty_intersection_is_loud_failure():
    bench = _bench()
    code, verdicts = bench.check_payloads(
        [_payload()], [_payload(metric="some_other_metric")]
    )
    assert code == 1
    assert any("no candidate metric" in v["reason"] for v in verdicts), verdicts


def test_pre_reps_payload_falls_back_to_value():
    bench = _bench()
    old_style = {"metric": "anakin_ppo_ant_env_steps_per_sec", "value": 10000.0}
    code, verdicts = bench.check_payloads([old_style], [_payload(median=9700.0)])
    assert code == 0 and verdicts[0]["baseline_median"] == 10000.0


def test_baseline_json_published_mapping_format(tmp_path):
    bench = _bench()
    path = tmp_path / "BASELINE.json"
    path.write_text(
        json.dumps(
            {
                "metric": "env steps/sec/chip",
                "published": {
                    "anakin_ppo_ant_env_steps_per_sec": {
                        "value": 10000.0, "median": 10000.0, "rel_spread": 0.05
                    }
                },
            }
        )
    )
    payloads = bench._load_baseline_payloads(str(path))
    assert payloads == [
        {
            "metric": "anakin_ppo_ant_env_steps_per_sec",
            "value": 10000.0, "median": 10000.0, "rel_spread": 0.05,
        }
    ]


# ---- scaling_bench / MULTICHIP wiring (ROADMAP item 4 slice) -----------------


def _scaling_summary(effs=(1.0, 0.92), sps0=10000.0):
    records = []
    for i, eff in enumerate(effs):
        n = 2**i
        records.append(
            {
                "devices": n,
                "env_steps_per_sec": round(sps0 * n * eff, 1),
                "per_device": round(sps0 * eff, 1),
                "efficiency_vs_smallest": eff,
            }
        )
    return {"scaling": records}


def test_scaling_summary_loads_as_baseline_payloads(tmp_path):
    """A scaling_bench.py summary is a first-class --check baseline: per-size
    throughput metrics plus efficiency-vs-smallest as its OWN metric for
    every size past the smallest (the >=80% weak-scaling efficiency claim
    becomes a number the gate holds a band around)."""
    bench = _bench()
    path = tmp_path / "SCALING.json"
    path.write_text(json.dumps(_scaling_summary()))
    payloads = bench._load_baseline_payloads(str(path))
    metrics = [p["metric"] for p in payloads]
    assert metrics == [
        "scaling_ppo_weak_d1_env_steps_per_sec",
        "scaling_ppo_weak_d2_env_steps_per_sec",
        "scaling_ppo_weak_eff_d2",
    ]
    eff = payloads[-1]
    assert eff["median"] == 0.92 and eff["rel_spread"] == 0.0
    # Every converted line is immediately gate-composable.
    code, verdicts = bench.check_payloads(payloads, payloads)
    assert code == 0, verdicts


def test_scaling_efficiency_regression_fails_the_gate(tmp_path):
    """An efficiency collapse (0.92 -> 0.60 at d2) is a regression verdict on
    the eff metric even though absolute throughput grew — the exact failure
    mode a raw steps/sec comparison would wave through."""
    bench = _bench()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_scaling_summary(effs=(1.0, 0.92))))
    baselines = bench._load_baseline_payloads(str(base))
    cand_text = json.dumps(_scaling_summary(effs=(1.0, 0.60), sps0=20000.0))
    code, verdicts = bench.check_payloads(
        baselines, bench._parse_payload_lines(cand_text)
    )
    assert code == 1
    by_metric = {v["metric"]: v for v in verdicts}
    assert by_metric["scaling_ppo_weak_eff_d2"]["status"] == "fail"
    assert "regression" in by_metric["scaling_ppo_weak_eff_d2"]["reason"]
    # Throughput itself improved and passes.
    assert by_metric["scaling_ppo_weak_d2_env_steps_per_sec"]["status"] == "pass"


def test_scaling_stdout_pipes_as_candidate_without_double_counting():
    """scaling_bench stdout = payload-shaped per-size lines + the trailing
    summary. The line parser must keep ONE payload per metric (first wins)
    and still pick up the eff metrics only the summary carries."""
    bench = _bench()
    summary = _scaling_summary()
    lines = [json.dumps({**rec, "metric": f"scaling_ppo_weak_d{rec['devices']}_env_steps_per_sec", "value": rec["env_steps_per_sec"], "median": rec["env_steps_per_sec"], "rel_spread": 0.0}) for rec in summary["scaling"]]
    lines.append(json.dumps(summary))
    payloads = bench._parse_payload_lines("\n".join(lines))
    metrics = [p["metric"] for p in payloads]
    assert len(metrics) == len(set(metrics)) == 3, metrics
    assert "scaling_ppo_weak_eff_d2" in metrics


def test_multichip_record_converts_and_gates(tmp_path):
    """MULTICHIP_r*.json rides the same gate: ok -> 1.0 median (passes
    against an ok baseline), ok=false -> 0.0 median -> the failed-workload
    verdict; a skipped record is no measurement at all."""
    bench = _bench()
    ok_path = tmp_path / "MULTICHIP_ok.json"
    ok_path.write_text(
        json.dumps({"n_devices": 8, "rc": 0, "ok": True, "skipped": False})
    )
    baselines = bench._load_baseline_payloads(str(ok_path))
    assert baselines == [
        {
            "metric": "multichip_dryrun_ok_d8", "value": 1.0, "median": 1.0,
            "rel_spread": 0.0, "unit": "dry-run success (1.0 = ok)",
            "rc": 0,
        }
    ]
    # ok vs ok: pass.
    code, verdicts = bench.check_payloads(baselines, baselines)
    assert code == 0, verdicts
    # A broken dry run (rc=124 timeout)
    # is a zero-median candidate -> loud failed-workload verdict.
    broken = bench._parse_payload_lines(
        json.dumps({"n_devices": 8, "rc": 124, "ok": False, "skipped": False})
    )
    code, verdicts = bench.check_payloads(baselines, broken)
    assert code == 1 and "failed workload" in verdicts[0]["reason"]
    # skipped -> no payload.
    assert bench._parse_payload_lines(
        json.dumps({"n_devices": 16, "rc": 0, "ok": False, "skipped": True})
    ) == []


def test_multichip_cli_end_to_end(tmp_path):
    """Subprocess contract: the real-file shapes flow through run_check with
    no jax import (same prolog guarantee as every other --check path)."""
    base = tmp_path / "MULTICHIP_base.json"
    cand = tmp_path / "MULTICHIP_cand.json"
    base.write_text(json.dumps({"n_devices": 8, "rc": 0, "ok": True}))
    cand.write_text(json.dumps({"n_devices": 8, "rc": 0, "ok": True}))
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--check", str(base), "--candidate", str(cand),
        ],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[0])
    assert verdict["metric"] == "multichip_dryrun_ok_d8"
    assert verdict["status"] == "pass"


# ---- CLI contract (subprocess; no jax import on this path) -------------------


def _run_check(tmp_path, baseline_lines, candidate_lines, extra=()):
    base = tmp_path / "base.json"
    cand = tmp_path / "cand.json"
    base.write_text("\n".join(json.dumps(p) for p in baseline_lines))
    cand.write_text("\n".join(json.dumps(p) for p in candidate_lines))
    return subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--check", str(base), "--candidate", str(cand), *extra,
        ],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )


def test_cli_regression_exits_one_jitter_exits_zero(tmp_path):
    proc = _run_check(tmp_path, [_payload()], [_payload(median=9700.0)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[0])
    assert verdict["status"] == "pass"

    proc = _run_check(tmp_path, [_payload()], [_payload(median=4296.0)])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[0])
    assert verdict["status"] == "fail" and "regression" in verdict["reason"]


def test_cli_never_imports_jax(tmp_path):
    # A prolog gate must not drag a multi-second accelerator runtime import;
    # poisoning jax proves --check never touches it.
    poison = tmp_path / "jax"
    poison.mkdir()
    (poison / "__init__.py").write_text("raise ImportError('gate imported jax')")
    base = tmp_path / "b.json"
    cand = tmp_path / "c.json"
    base.write_text(json.dumps(_payload()))
    cand.write_text(json.dumps(_payload(median=9700.0)))
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--check", str(base), "--candidate", str(cand),
        ],
        capture_output=True, text=True, cwd=REPO, timeout=60,
        env={**os.environ, "PYTHONPATH": str(tmp_path)},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_usage_errors_exit_two(tmp_path):
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--check", str(tmp_path / "missing.json"),
            "--candidate", str(tmp_path / "also_missing.json"),
        ],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert proc.returncode == 2, proc.stdout
    assert "error" in json.loads(proc.stdout.strip().splitlines()[0])

    empty = tmp_path / "empty.json"
    empty.write_text("")
    proc = _run_check(tmp_path, [], [_payload()])
    assert proc.returncode == 2, proc.stdout
