"""Pipelined-vs-synchronous Anakin host loop equivalence.

The pipelined dispatcher (systems/runner.py) overlaps host work with device
compute by taking on-device snapshots before the next donated learn() call.
These tests pin its core invariant: the TRAINING TRAJECTORY — the learner
params after every learn window — is bit-identical to the synchronous loop's,
with buffer donation on AND off (the snapshot-vs-donation invariant,
systems/anakin.py shardmap_learner docstring), and with async checkpointing
saving from the snapshot copy.
"""

import os

import jax
import numpy as np
import pytest

from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup
from stoix_tpu.systems.runner import run_anakin_experiment
from stoix_tpu.utils import config as config_lib

BASE_OVERRIDES = [
    "env=identity_game",
    "arch.total_num_envs=16",
    "arch.num_updates=6",
    "arch.total_timesteps=~",
    "arch.num_evaluation=3",
    "arch.num_eval_episodes=8",
    "arch.absolute_metric=False",
    "system.rollout_length=4",
    "system.epochs=1",
    "system.num_minibatches=2",
    "logger.use_console=False",
]


def _make_config(extra):
    return config_lib.compose(
        config_lib.default_config_dir(),
        "default/anakin/default_ff_ppo.yaml",
        BASE_OVERRIDES + list(extra),
    )


def _run_recorded(extra):
    """Run ff_ppo through the shared runner, recording the host-materialized
    params tree after EVERY learn window (the trajectory the pipeline must
    preserve). Returns (trajectory, final_return)."""
    trajectory = []

    def recording_setup(env, config, mesh, key):
        setup = learner_setup(env, config, mesh, key)
        inner = setup.learn

        def recording_learn(state):
            out = inner(state)
            # Materializing the OUTPUT params here is donation-safe (the
            # runner donates them only at the NEXT learn dispatch) and forces
            # a host copy before the pipeline runs ahead.
            trajectory.append(jax.tree.map(np.asarray, out.learner_state.params))
            return out

        return setup._replace(learn=recording_learn)

    final_return = run_anakin_experiment(_make_config(extra), recording_setup)
    return trajectory, final_return


def _assert_trajectories_identical(traj_a, traj_b):
    assert len(traj_a) == len(traj_b) and traj_a, (len(traj_a), len(traj_b))
    for step, (ta, tb) in enumerate(zip(traj_a, traj_b)):
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                a, b, err_msg=f"trajectory diverged at window {step}"
            ),
            ta,
            tb,
        )


def test_pipelined_trajectory_bit_identical_to_sync(devices):
    pipelined, _ = _run_recorded([])
    sync, _ = _run_recorded(["arch.pipelined_loop=False"])
    _assert_trajectories_identical(pipelined, sync)


def test_pipelined_trajectory_bit_identical_without_donation(devices, monkeypatch):
    # STOIX_TPU_NO_DONATE is read at shardmap_learner build time: setting it
    # here exercises the pipeline with XLA free to NOT reuse state buffers —
    # the snapshot logic must be correct in both regimes.
    monkeypatch.setenv("STOIX_TPU_NO_DONATE", "1")
    pipelined, _ = _run_recorded([])
    sync, _ = _run_recorded(["arch.pipelined_loop=False"])
    _assert_trajectories_identical(pipelined, sync)


def test_fused_eval_runs_and_matches_returns(devices):
    # arch.fused_eval folds the FF evaluator into the learn program; the
    # learner math is untouched, so eval returns must agree with the
    # snapshot-overlap path (same per-window eval key split order).
    from stoix_tpu.systems.ppo.anakin.ff_ppo import run_experiment

    fused = run_experiment(_make_config(["arch.fused_eval=True"]))
    plain = run_experiment(_make_config([]))
    # Not exact equality: fusing re-compiles learn+eval as ONE program, and
    # XLA may order float ops differently than the two separate programs.
    np.testing.assert_allclose(fused, plain, rtol=1e-6)


def test_async_checkpoint_saves_from_snapshot(devices, tmp_path, monkeypatch):
    # Checkpointing rides the pipeline without wait(): the save consumes the
    # on-device snapshot, so enabling it must not perturb training, and the
    # checkpoint must land on disk by close().
    monkeypatch.chdir(tmp_path)
    baseline, _ = _run_recorded([])
    ckpt, _ = _run_recorded(
        [
            "logger.checkpointing.save_model=True",
            "logger.checkpointing.save_args.checkpoint_uid=pipeline-test",
        ]
    )
    _assert_trajectories_identical(baseline, ckpt)
    ckpt_dir = tmp_path / "checkpoints" / "pipeline-test"
    saved = [p for p in ckpt_dir.rglob("*") if p.is_file()]
    assert saved, f"no checkpoint files under {ckpt_dir}"


RUNNER_PHASES = (
    "compile_s", "learn_s", "snapshot_s", "eval_s", "fetch_dispatch_s", "fetch_s",
    "log_s", "host_s", "ckpt_s",
)


@pytest.fixture(scope="module")
def pipelined_run_stats(devices):
    """LAST_RUN_STATS of one tiny pipelined ff_ppo run (six windows)."""
    from stoix_tpu.systems import runner

    _run_recorded(["arch.num_updates=12", "arch.num_evaluation=6"])
    return dict(runner.LAST_RUN_STATS)


@pytest.mark.parametrize("phase", RUNNER_PHASES)
def test_runner_reports_phase_breakdown(pipelined_run_stats, phase):
    phases = pipelined_run_stats["phase_breakdown"]
    assert set(phases) == set(RUNNER_PHASES)  # gossip_s only in a gossip run
    assert isinstance(phases[phase], float) and phases[phase] >= 0.0, phases
    assert pipelined_run_stats["steady_state_sps"] > 0.0
    assert pipelined_run_stats["pipelined"] is True


def test_runner_phases_cover_the_loop_wall(pipelined_run_stats):
    """The clock covers the wall: every statement of the main thread between
    two window completions runs inside a span with a phase, so the phases
    of the loop (all but the AOT compile, which is before it) sum to at
    least 95% of the loop's wall — and cannot exceed it, being disjoint."""
    phases = pipelined_run_stats["phase_breakdown"]
    in_loop = sum(v for k, v in phases.items() if k != "compile_s")
    wall = pipelined_run_stats["loop_wall_s"]
    assert 0.95 * wall <= in_loop <= wall * 1.001, (in_loop, wall, phases)


def test_fetch_materialize_is_its_own_phase(devices, monkeypatch):
    """`fetch_s` is the blocked wait for a window's metrics alone: the
    seconds the fetch_materialize spans fed the clock, and nothing of the
    fetch's dispatch (fetch_dispatch_s)."""
    from stoix_tpu.systems import runner

    fed = {}
    record = runner._PhaseClock.record

    def recording(self, name, seconds):
        fed.setdefault(name, []).append(seconds)
        return record(self, name, seconds)

    monkeypatch.setattr(runner._PhaseClock, "record", recording)
    _run_recorded([])
    phases = runner.LAST_RUN_STATS["phase_breakdown"]
    assert len(fed["fetch_s"]) == 3 and len(fed["fetch_dispatch_s"]) == 3  # 3 windows
    assert phases["fetch_s"] == pytest.approx(sum(fed["fetch_s"]), abs=1e-5)
    assert phases["fetch_dispatch_s"] == pytest.approx(sum(fed["fetch_dispatch_s"]), abs=1e-5)
    # Set-up is split the same way, once a run.
    setup = runner.LAST_RUN_STATS["setup_phases"]
    assert set(setup) == {
        "mesh_build", "env_build", "rng_key", "learner_setup", "evaluator_setup",
        "logger_build", "aot_warmup", "first_tick", "unspanned",
    }
    assert setup["aot_warmup"] == pytest.approx(phases["compile_s"], abs=1e-5)
    assert setup["first_tick"] > 0.0


@pytest.mark.slow
@pytest.mark.skipif(
    os.environ.get("STOIX_TPU_PROFILE_DIR") is not None,
    reason="external profiling already active",
)
def test_profile_dir_hook_writes_trace(devices, tmp_path, monkeypatch):
    # Slow lane (tier-1 budget, PR 19): a full recorded run under the JAX
    # profiler (~13s); the pipelined-runner contracts stay not-slow above —
    # this pins only the optional trace-artifact side effect.
    monkeypatch.setenv("STOIX_TPU_PROFILE_DIR", str(tmp_path / "profile"))
    _run_recorded([])
    traced = list((tmp_path / "profile").rglob("*"))
    assert traced, "STOIX_TPU_PROFILE_DIR set but no trace artifacts written"
