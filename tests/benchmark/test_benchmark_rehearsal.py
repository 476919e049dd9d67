"""Rehearsals: each cell's driver end to end at tiny size on the CPU backend,
through the test-only entry tests/benchmark/rehearse.py (the command itself
has no CPU mode — the last test shows that). Minutes of CPU compile, so the
end-to-end ones are `slow`; run them by hand before any chip call:

    python -m pytest tests/benchmark/test_benchmark_rehearsal.py -m slow
"""

import json
import os
import subprocess
import sys

import pytest

import _paths
from benchmarks.harness import loader

TINY = {
    "anakin": ["arch.total_num_envs=16", "arch.num_eval_episodes=4"],
    "sebulba": ["arch.total_num_envs=64", "arch.num_eval_episodes=4"],
}
CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]


def _clean_env():
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = _paths.ROOT
    return env


@pytest.mark.slow
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end_on_virtual_devices(cell, tmp_path):
    loaded = loader.load_cell(cell)
    run = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "tests", "benchmark", "rehearse.py"),
         "--workload", cell, "--seconds", "6", "--scratch", str(tmp_path)] + TINY[loaded.driver],
        capture_output=True, text=True, timeout=900, env=_clean_env(), cwd=_paths.ROOT,
    )
    assert run.returncode == 0, run.stderr[-3000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert result["correct"], result["problems"]
    assert result["device"]["count"] == loaded.chips and result["device"]["platform"] == "cpu"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"}
    assert result["detail"]["compiles_in_interval"] == 0
    assert result["detail"]["exit_after_interval_s"] < 30.0


@pytest.mark.slow
def test_a_run_that_drifts_from_the_stated_configuration_is_not_correct(tmp_path):
    """Overrides that leave the widths and loop counts the configuration
    file states: the run works, and `correct` is false."""
    run = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "tests", "benchmark", "rehearse.py"),
         "--workload", "anakin_ppo_ant_1chip", "--seconds", "4", "--scratch", str(tmp_path)]
        + TINY["anakin"] + ["network.actor_network.pre_torso.layer_sizes=[128,128]", "system.epochs=2"],
        capture_output=True, text=True, timeout=900, env=_clean_env(), cwd=_paths.ROOT,
    )
    assert run.returncode == 0, run.stderr[-3000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert not result["correct"]
    assert any("actor kernels [(27, 128)" in p for p in result["problems"]), result["problems"]
    assert "epochs resolved to 2, stated 4" in result["problems"]


def test_the_command_has_no_cpu_mode():
    """Under JAX_PLATFORMS=cpu the command exits non-zero, fast, and prints
    no result line."""
    env = _clean_env()
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env, cwd=_paths.ROOT,
    )
    assert run.returncode != 0
    assert run.stdout.strip() == ""
    assert "no fallback" in run.stderr
