"""Fault-tolerance layer end-to-end (stoix_tpu/resilience, DESIGN.md §2.3).

Every recovery path is proven under an INJECTED fault (resilience/faultinject):

  * nan_loss   -> update_guard=skip finishes with finite params and a nonzero
                  skipped-update counter; halt raises DivergenceError; off
                  demonstrably poisons params (the motivating failure mode)
  * sigterm    -> graceful stop, emergency checkpoint, clean return, and a
                  resumed run whose continued trajectory is BIT-IDENTICAL to
                  an uninterrupted run's
  * ckpt_corrupt -> restore falls back to the newest VALID checkpoint
  * actor_crash -> supervised restart completes the Sebulba run; with the
                  restart budget exhausted (or a wedge) a typed
                  ComponentFailure fails the learner fast
  * backend_wedge -> the subprocess backend probe times out every attempt and
                  raises BackendUnavailableError within the configured
                  deadline — the parent process never hangs (DESIGN.md §2.4)
  * slow_compile -> the first-compile watchdog dumps thread stacks and raises
                  CompileStallError instead of stalling indefinitely

Plus the bit-identity pin: with everything at defaults the resilience layer
adds zero ops and zero metrics — training trajectories are unchanged.
"""

import os
import signal
import threading
import time

import jax
import numpy as np
import pytest

from stoix_tpu.resilience import (
    CheckpointIntegrityError,
    ComponentFailure,
    DivergenceError,
    EvaluatorStallError,
    faultinject,
    guards,
)
from stoix_tpu.utils import config as config_lib

BASE_OVERRIDES = [
    "env=identity_game",
    "arch.total_num_envs=16",
    "arch.num_updates=4",
    "arch.total_timesteps=~",
    "arch.num_evaluation=2",
    "arch.num_eval_episodes=8",
    "arch.absolute_metric=False",
    "system.rollout_length=4",
    "system.epochs=1",
    "system.num_minibatches=2",
    "logger.use_console=False",
]


@pytest.fixture(autouse=True)
def _no_fault_leakage():
    """One-shot fault state must never leak across tests: a plan armed via
    env var in one test would otherwise keep firing at direct-call injection
    points (Checkpointer.save) in later ones."""
    yield
    faultinject.reset()


def _anakin_config(extra):
    return config_lib.compose(
        config_lib.default_config_dir(),
        "default/anakin/default_ff_ppo.yaml",
        BASE_OVERRIDES + list(extra),
    )


def _run_recorded(extra):
    """ff_ppo through the shared runner, recording host-materialized params
    after every learn window. Returns (trajectory, final_return)."""
    from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup
    from stoix_tpu.systems.runner import run_anakin_experiment

    trajectory = []

    def recording_setup(env, config, mesh, key):
        setup = learner_setup(env, config, mesh, key)
        inner = setup.learn

        def recording_learn(state):
            out = inner(state)
            trajectory.append(jax.tree.map(np.asarray, out.learner_state.params))
            return out

        return setup._replace(learn=recording_learn)

    final_return = run_anakin_experiment(_anakin_config(extra), recording_setup)
    return trajectory, final_return


def _assert_identical(traj_a, traj_b):
    assert len(traj_a) == len(traj_b) and traj_a, (len(traj_a), len(traj_b))
    for step, (ta, tb) in enumerate(zip(traj_a, traj_b)):
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                a, b, err_msg=f"trajectory diverged at window {step}"
            ),
            ta, tb,
        )


def _all_finite(tree) -> bool:
    return all(np.isfinite(leaf).all() for leaf in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Pillar 1: divergence guards
# ---------------------------------------------------------------------------


def test_guard_off_is_a_literal_no_op():
    # The bit-identity guarantee rests on this: with mode=off and no fault
    # armed, guard_update returns the `new` carry UNTOUCHED (the same object,
    # zero ops traced) and adds no metrics keys to the train tree.
    new = ({"w": np.ones(3)}, {"count": np.zeros(())})
    old = ({"w": np.zeros(3)}, {"count": np.zeros(())})
    out, metrics = guards.guard_update(
        "off", new=new, old=old, loss=np.float32(1.0), grads=new[0], opt_state=None
    )
    assert out is new
    assert metrics == {}
    assert guards.publish_guard_metrics("off", {"loss": 1.0}, 0) == 0.0


def test_defaults_trajectory_identical_and_skip_transparent(devices):
    default_traj, _ = _run_recorded([])
    off_traj, _ = _run_recorded(["system.update_guard=off"])
    _assert_identical(default_traj, off_traj)
    # skip with NO faults must be a numeric no-op (the where-select keeps the
    # new carry everywhere); bitwise equality is not guaranteed — selection
    # changes the XLA program, which may reassociate float ops.
    skip_traj, _ = _run_recorded(["system.update_guard=skip"])
    assert len(skip_traj) == len(default_traj)
    for ta, tb in zip(default_traj, skip_traj):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6), ta, tb
        )
    from stoix_tpu.systems.runner import LAST_RUN_STATS

    assert LAST_RUN_STATS["resilience"]["skipped_updates"] == 0.0


def test_resolve_mode_rejects_unknown():
    cfg = config_lib.Config.from_dict({"system": {"update_guard": "explode"}})
    with pytest.raises(ValueError, match="update_guard"):
        guards.resolve_mode(cfg)


def test_nan_loss_skip_finishes_finite_with_counter(devices, monkeypatch):
    monkeypatch.setenv("STOIX_TPU_FAULT", "nan_loss:2")
    traj, ret = _run_recorded(["system.update_guard=skip"])
    assert _all_finite(traj[-1]), "skip mode must keep params finite"
    assert np.isfinite(ret)
    from stoix_tpu.systems.runner import LAST_RUN_STATS

    resilience = LAST_RUN_STATS["resilience"]
    assert resilience["update_guard"] == "skip"
    assert resilience["skipped_updates"] >= 1.0, resilience


def test_nan_loss_skip_counter_exact_with_update_batch(devices, monkeypatch):
    # The [U] update-batch replicas are grad-synced, so their guard verdicts
    # are identical AND each emits a metrics entry: the counter must report
    # ONE skip for one skipped update, not U (the flag is pre-divided by the
    # "batch" axis size in guards.guard_update).
    monkeypatch.setenv("STOIX_TPU_FAULT", "nan_loss:2")
    traj, _ = _run_recorded(
        ["system.update_guard=skip", "arch.update_batch_size=2"]
    )
    assert _all_finite(traj[-1])
    from stoix_tpu.systems.runner import LAST_RUN_STATS

    np.testing.assert_allclose(
        LAST_RUN_STATS["resilience"]["skipped_updates"], 1.0, atol=1e-6
    )


def test_nan_loss_halt_raises_divergence_error(devices, monkeypatch):
    monkeypatch.setenv("STOIX_TPU_FAULT", "nan_loss:2")
    with pytest.raises(DivergenceError) as excinfo:
        _run_recorded(["system.update_guard=halt"])
    err = excinfo.value
    assert err.metric in ("loss", "grad_norm")
    assert not np.isfinite(err.loss)
    assert err.step > 0


def test_nan_loss_with_guard_off_poisons_params(devices, monkeypatch):
    # The motivating failure mode: without a guard, one non-finite update
    # poisons the params forever — and the run happily "completes".
    monkeypatch.setenv("STOIX_TPU_FAULT", "nan_loss:2")
    traj, _ = _run_recorded([])
    assert not _all_finite(traj[-1])


# ---------------------------------------------------------------------------
# Pillar 2: preemption-safe stop + validated resume
# ---------------------------------------------------------------------------


def test_sigterm_emergency_checkpoint_and_bit_identical_resume(
    devices, tmp_path, monkeypatch
):
    monkeypatch.chdir(tmp_path)
    six_windows = ["arch.num_updates=6", "arch.num_evaluation=6"]
    # save_interval far beyond the run so the ONLY on-disk state at the stop
    # step can come from the preemption handler's forced emergency save.
    save = [
        "logger.checkpointing.save_model=True",
        "logger.checkpointing.save_args.checkpoint_uid=sigterm-test",
        "logger.checkpointing.save_args.save_interval_steps=1000000",
        "logger.checkpointing.save_args.max_to_keep=3",
    ]
    monkeypatch.setenv("STOIX_TPU_FAULT", "sigterm:1")
    interrupted, _ = _run_recorded(six_windows + save)  # returns = clean exit
    monkeypatch.delenv("STOIX_TPU_FAULT")
    from stoix_tpu.systems.runner import LAST_RUN_STATS

    assert LAST_RUN_STATS["resilience"]["preempted"] is True
    assert 0 < len(interrupted) < 6, "SIGTERM must stop the run mid-way"
    assert (tmp_path / "checkpoints" / "sigterm-test" / "ff_ppo").is_dir()

    uninterrupted, _ = _run_recorded(six_windows)
    _assert_identical(interrupted, uninterrupted[: len(interrupted)])

    resumed, _ = _run_recorded(
        six_windows
        + [
            "logger.checkpointing.load_model=True",
            "logger.checkpointing.load_args.checkpoint_uid=sigterm-test",
        ]
    )
    # The continued trajectory must be bit-identical to the uninterrupted
    # run's windows past the preemption point: the emergency checkpoint
    # captured the EXACT learner state (params, opt, keys, env state).
    k = len(interrupted)
    tail = uninterrupted[k:]
    _assert_identical(tail, resumed[: len(tail)])


def test_preemption_handler_flags_and_restores(monkeypatch):
    from stoix_tpu.resilience import PreemptionHandler

    before = signal.getsignal(signal.SIGTERM)
    with PreemptionHandler() as handler:
        assert not handler.stop_requested()
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.monotonic() + 2.0
        while not handler.stop_requested() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert handler.stop_requested()
        assert handler.signal_name == "SIGTERM"
    assert signal.getsignal(signal.SIGTERM) is before


# ---------------------------------------------------------------------------
# Pillar 2b: checkpoint integrity validation + fallback
# ---------------------------------------------------------------------------


def _make_store(tmp_path, name, states):
    from stoix_tpu.utils.checkpointing import Checkpointer

    ck = Checkpointer(
        model_name=name, rel_dir=str(tmp_path / "ck"), checkpoint_uid="u",
        max_to_keep=5,
    )
    for step, state in states:
        assert ck.save(step, state)
    ck.close()
    return Checkpointer(
        model_name=name, rel_dir=str(tmp_path / "ck"), checkpoint_uid="u",
        max_to_keep=5,
    )


def test_restore_falls_back_past_corrupt_checkpoint(tmp_path):
    import jax.numpy as jnp

    good = {"w": jnp.arange(6.0).reshape(2, 3)}
    newer = {"w": jnp.arange(6.0).reshape(2, 3) * 2}
    loader = _make_store(tmp_path, "m", [(1, good), (2, newer)])
    assert loader.all_steps() == [1, 2]
    faultinject.corrupt_checkpoint_files(os.path.join(loader.directory, "2"))
    template = jax.tree.map(jnp.zeros_like, good)
    restored, step = loader.restore(template)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(good["w"]))
    loader.close()


def test_restore_rejects_nonfinite_and_all_corrupt_raises(tmp_path):
    import jax.numpy as jnp

    good = {"w": jnp.arange(6.0).reshape(2, 3)}
    poisoned = {"w": jnp.full((2, 3), jnp.nan)}
    loader = _make_store(tmp_path, "n", [(1, good), (2, poisoned)])
    template = jax.tree.map(jnp.zeros_like, good)
    # Finiteness spot-check rejects step 2 (template is finite there) and
    # falls back to step 1.
    restored, step = loader.restore(template)
    assert step == 1
    # With every candidate unusable the typed integrity error surfaces.
    faultinject.corrupt_checkpoint_files(os.path.join(loader.directory, "1"))
    faultinject.corrupt_checkpoint_files(os.path.join(loader.directory, "2"))
    with pytest.raises(CheckpointIntegrityError):
        loader.restore(template)
    loader.close()


def test_restore_rejects_nonfinite_bf16(tmp_path):
    # bfloat16 (the common TPU param dtype) is an ml_dtypes float that numpy
    # does not classify under np.floating — the finiteness gate must still
    # validate it, not silently skip it.
    import jax.numpy as jnp

    good = {"w": jnp.arange(6.0, dtype=jnp.bfloat16)}
    poisoned = {"w": jnp.full((6,), jnp.nan, dtype=jnp.bfloat16)}
    loader = _make_store(tmp_path, "bf", [(1, good), (2, poisoned)])
    restored, step = loader.restore(jax.tree.map(jnp.zeros_like, good))
    assert step == 1
    loader.close()


def test_restore_falls_back_past_truncated_checkpoint(tmp_path):
    # A save killed mid-serialization leaves MISSING payload files (orbax
    # raises FileNotFoundError, not a parse error) — fallback must cover that
    # class too, not just overwritten bytes.
    import jax.numpy as jnp

    good = {"w": jnp.arange(6.0)}
    loader = _make_store(tmp_path, "t", [(1, good), (2, good)])
    step2 = os.path.join(loader.directory, "2")
    for root, _dirs, files in os.walk(step2):
        if "metrics" in root:
            continue
        for name in files:
            if name != "_CHECKPOINT_METADATA":
                os.remove(os.path.join(root, name))
    restored, step = loader.restore(jax.tree.map(jnp.zeros_like, good))
    assert step == 1
    loader.close()


def test_restore_missing_explicit_timestep_lists_available(tmp_path):
    import jax.numpy as jnp

    good = {"w": jnp.arange(4.0)}
    loader = _make_store(tmp_path, "o", [(3, good), (7, good)])
    template = jax.tree.map(jnp.zeros_like, good)
    with pytest.raises(FileNotFoundError, match=r"available steps: \[3, 7\]"):
        loader.restore(template, timestep=5)
    restored, step = loader.restore(template, timestep=3)
    assert step == 3
    loader.close()


def test_env_driven_ckpt_corrupt_fires_once_on_save(tmp_path, monkeypatch):
    import jax.numpy as jnp

    from stoix_tpu.utils.checkpointing import Checkpointer

    monkeypatch.setenv("STOIX_TPU_FAULT", "ckpt_corrupt")
    faultinject.configure()
    ck = Checkpointer(
        model_name="p", rel_dir=str(tmp_path / "ck"), checkpoint_uid="u",
        max_to_keep=5,
    )
    state = {"w": jnp.arange(4.0)}
    ck.save(1, state)  # one-shot corruption consumes here
    ck.save(2, state)
    ck.close()
    template = jax.tree.map(jnp.zeros_like, state)
    loader = Checkpointer(
        model_name="p", rel_dir=str(tmp_path / "ck"), checkpoint_uid="u",
        max_to_keep=5,
    )
    restored, step = loader.restore(template)
    assert step == 2, "step 1 was corrupted by the armed fault; 2 is intact"
    loader.close()


# ---------------------------------------------------------------------------
# Pillar 3: Sebulba supervision
# ---------------------------------------------------------------------------

SEBULBA_OVERRIDES = [
    "env=identity_game",
    "arch.total_num_envs=8",
    "arch.num_updates=4",
    "arch.total_timesteps=~",
    "arch.num_evaluation=1",
    "arch.num_eval_episodes=4",
    "system.rollout_length=8",
    "system.num_minibatches=2",
    "logger.use_console=False",
    "arch.actor.device_ids=[0]",
    "arch.actor.actor_per_device=1",
    "arch.learner.device_ids=[1]",
    "arch.evaluator_device_id=0",
    "arch.supervision.backoff_base_s=0.05",
]


def _sebulba_config(extra):
    return config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_ppo.yaml",
        SEBULBA_OVERRIDES + list(extra),
    )


def test_actor_crash_supervised_restart_completes_run(devices, monkeypatch):
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    monkeypatch.setenv("STOIX_TPU_FAULT", "actor_crash:1")
    ret = ff_ppo.run_experiment(_sebulba_config([]))
    assert np.isfinite(ret)
    resilience = ff_ppo.LAST_RUN_STATS["resilience"]
    assert resilience["actor_restarts"] == 1, resilience


def test_actor_crash_past_budget_fails_fast(devices, monkeypatch):
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    monkeypatch.setenv("STOIX_TPU_FAULT", "actor_crash:1")
    start = time.monotonic()
    with pytest.raises(ComponentFailure, match="actor-0"):
        ff_ppo.run_experiment(_sebulba_config(["arch.supervision.max_restarts=0"]))
    # Fail FAST: the poison-pill must beat the 180s collect timeout by far.
    assert time.monotonic() - start < 120.0


def test_actor_wedge_detected_by_heartbeat_watchdog(devices, monkeypatch):
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    monkeypatch.setenv("STOIX_TPU_FAULT", "queue_stall:1")
    with pytest.raises(ComponentFailure, match="wedged"):
        ff_ppo.run_experiment(
            _sebulba_config(["arch.supervision.wedge_timeout_s=3"])
        )


def test_pipeline_poison_pill_and_param_server_units():
    from stoix_tpu.sebulba.core import OnPolicyPipeline, ParameterServer

    pipeline = OnPolicyPipeline(num_actors=2)
    failure = ComponentFailure("actor-1", "unit test")
    pipeline.send_rollout(0, "payload")
    pipeline.fail(1, failure)
    with pytest.raises(ComponentFailure, match="actor-1"):
        pipeline.collect_rollouts(timeout=5.0)

    server = ParameterServer(jax.devices("cpu")[:1], 1)
    assert server.reprime(0) is False  # nothing distributed yet
    server.distribute_params({"w": np.ones(2)})
    assert server.get_params_versioned(0, timeout=1.0).params["w"].shape == (2,)
    assert server.reprime(0) is True  # replacement actor gets latest params
    assert server.get_params_versioned(0, timeout=1.0).params["w"].shape == (2,)
    server.fail(ComponentFailure("actor-0", "wedged (unit test)"), actor_id=0)
    with pytest.raises(ComponentFailure, match="actor-0"):
        server.get_params_versioned(0, timeout=1.0)


def test_async_evaluator_stall_raises_named_error():
    from stoix_tpu.sebulba.core import AsyncEvaluator, ThreadLifetime

    lifetime = ThreadLifetime()
    release = threading.Event()

    def slow_eval(params, key):
        release.wait(timeout=10.0)
        return {"episode_return": np.zeros(1)}

    evaluator = AsyncEvaluator(slow_eval, lifetime, lambda *a: None)
    evaluator.thread.start()
    evaluator.submit({"p": 1}, jax.random.PRNGKey(0), 0)
    with pytest.raises(EvaluatorStallError) as excinfo:
        evaluator.wait_until_idle(timeout=0.3)
    assert excinfo.value.pending >= 0
    release.set()
    evaluator.wait_until_idle(timeout=10.0)  # clean path still returns
    lifetime.stop()


def test_async_evaluator_failed_eval_fails_the_run():
    # A raised evaluation leaves the thread alive (the next request is still
    # served) and is counted, but it must not be survivable: once idle,
    # wait_until_idle raises, so run_experiment cannot return as if evaluated.
    from stoix_tpu.observability import get_registry
    from stoix_tpu.sebulba.core import AsyncEvaluator, ThreadLifetime

    counter = get_registry().counter(
        "stoix_tpu_sebulba_evaluator_errors_total",
        "Async evaluation requests that raised",
    )
    errors_before = counter.value()
    lifetime = ThreadLifetime()
    served = []

    def flaky_eval(params, key):
        if params["p"] == 0:
            raise ValueError("eval blew up (unit test)")
        return {"episode_return": np.zeros(1)}

    evaluator = AsyncEvaluator(flaky_eval, lifetime, lambda m, p, t: served.append(t))
    evaluator.thread.start()
    evaluator.submit({"p": 0}, jax.random.PRNGKey(0), 0)
    evaluator.submit({"p": 1}, jax.random.PRNGKey(0), 1)
    with pytest.raises(ComponentFailure, match="async-evaluator") as excinfo:
        evaluator.wait_until_idle(timeout=10.0)
    assert isinstance(excinfo.value.__cause__, ValueError)
    assert served == [1] and evaluator.thread.is_alive()
    assert counter.value() == errors_before + 1
    lifetime.stop()
    evaluator.thread.join(timeout=10.0)
    assert not evaluator.thread.is_alive()


# ---------------------------------------------------------------------------
# Pillar 5: launch hardening (preflight + watchdogs, DESIGN.md §2.4)
# ---------------------------------------------------------------------------


def test_probe_backend_healthy_cpu():
    from stoix_tpu.resilience import preflight

    probe = preflight.probe_backend(timeout_s=120.0, attempts=1)
    assert probe.platform == "cpu"
    assert probe.device_count >= 1
    assert probe.attempts == 1
    assert probe.process_count == 1


def test_backend_wedge_aborts_within_deadline(monkeypatch):
    # The acceptance pin: a wedged backend (every probe child sleeps forever
    # before touching jax) must abort with the TYPED error within the
    # configured budget — attempts * timeout + backoffs — never hang.
    from stoix_tpu.resilience import BackendUnavailableError, preflight

    monkeypatch.setenv("STOIX_TPU_FAULT", "backend_wedge")
    start = time.monotonic()
    with pytest.raises(BackendUnavailableError) as excinfo:
        preflight.probe_backend(
            timeout_s=2.0, attempts=2, backoff_base_s=0.1, backoff_max_s=0.2
        )
    elapsed = time.monotonic() - start
    assert elapsed < 20.0, f"abort took {elapsed:.1f}s — the parent must not hang"
    assert excinfo.value.attempts == 2
    assert excinfo.value.timeout_s == 2.0
    assert "timed out" in excinfo.value.last_error


def test_validate_config_collects_all_findings():
    from stoix_tpu.resilience import ConfigValidationError, preflight

    bad = _anakin_config(
        ["arch.total_num_envs=7", "arch.update_batch_size=3",
         "system.update_guard=explode"]
    )
    with pytest.raises(ConfigValidationError) as excinfo:
        preflight.validate_config(bad, device_count=1)
    findings = excinfo.value.findings
    assert len(findings) >= 2, findings  # divisibility AND guard mode, at once
    assert any("total_num_envs" in f for f in findings), findings
    assert any("update_guard" in f for f in findings), findings

    good = _anakin_config([])
    preflight.validate_config(good, device_count=8)  # must not raise


def test_validate_config_sebulba_device_split():
    from stoix_tpu.resilience import ConfigValidationError, preflight

    bad = _sebulba_config(["arch.learner.device_ids=[99]"])
    with pytest.raises(ConfigValidationError, match="out of range"):
        preflight.validate_config(bad, device_count=2)
    good = _sebulba_config([])
    preflight.validate_config(good, device_count=2)


def test_watchdog_stall_dumps_and_raises():
    from stoix_tpu.resilience import CompileStallError, Watchdog

    with pytest.raises(CompileStallError) as excinfo:
        with Watchdog("unit_stage", deadline_s=0.2):
            time.sleep(10.0)  # interrupt_main breaks this sleep
    err = excinfo.value
    assert err.stage == "unit_stage"
    assert err.dump is not None and "thread" in err.dump
    assert "registry snapshot" in err.dump


def test_watchdog_clean_section_is_transparent():
    from stoix_tpu.resilience import Watchdog

    with Watchdog("unit_ok", deadline_s=30.0) as dog:
        value = 1 + 1
    assert value == 2 and not dog.stalled


def test_slow_compile_trips_first_compile_watchdog(devices, monkeypatch):
    # End-to-end through the Anakin runner: preflight on, a 1s compile
    # deadline, and an injected 10s compile delay -> CompileStallError from
    # the first_compile stage, not a 10s-later success or a hang.
    from stoix_tpu.resilience import CompileStallError

    monkeypatch.setenv("STOIX_TPU_FAULT", "slow_compile:10")
    with pytest.raises(CompileStallError, match="first_compile"):
        _run_recorded(
            ["arch.preflight.enabled=True",
             "arch.preflight.compile_deadline_s=1.0",
             "arch.preflight.probe_timeout_s=120"]
        )


def test_preflight_on_trajectory_identical(devices):
    # arch.preflight only ADDS checks (probe subprocess, validation, one
    # block_until_ready on window 0): the dispatched program sequence — and
    # hence the training trajectory — must be bit-identical to preflight off.
    off_traj, _ = _run_recorded([])
    on_traj, _ = _run_recorded(
        ["arch.preflight.enabled=True", "arch.preflight.probe_timeout_s=120"]
    )
    _assert_identical(off_traj, on_traj)
    from stoix_tpu.systems.runner import LAST_RUN_STATS

    assert LAST_RUN_STATS["resilience"]["preflight"] is True


def test_memory_gate_passes_and_estimates():
    import jax.numpy as jnp

    from stoix_tpu.resilience import preflight

    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((64, 64))).compile()
    estimate = preflight.estimate_compiled_memory(compiled)
    assert estimate is not None and estimate["predicted_bytes"] >= 0
    # CPU exposes no bytes_limit: the gate logs and passes (returns estimate).
    assert preflight.check_device_memory(compiled, headroom=0.9) is not None
    # Non-compiled callables (no memory_analysis to read) skip.
    assert preflight.estimate_compiled_memory(lambda x: x) is None


def test_memory_gate_rejects_predicted_oom():
    import jax.numpy as jnp

    from stoix_tpu.resilience import ResourcePreflightError, preflight

    class FakeDevice:
        device_kind = "FakeTPU v9"
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_limit": 1024}  # 1 KiB of "HBM"

    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((64, 64))).compile()
    with pytest.raises(ResourcePreflightError) as excinfo:
        preflight.check_device_memory(compiled, headroom=0.9, device=FakeDevice())
    assert excinfo.value.limit_bytes == 1024
    assert excinfo.value.predicted_bytes > 1024


def test_run_preflight_report_renders_and_gates():
    from stoix_tpu.resilience import preflight

    report = preflight.run_preflight(
        [("good", _anakin_config([])), ("bad", _anakin_config(["arch.total_num_envs=7"]))]
    )
    text = report.render()
    assert not report.ok
    assert "backend_probe" in text and "config[bad]" in text
    assert "overall: FAIL" in text


# ---------------------------------------------------------------------------
# Pillar 4: fault injector mechanics
# ---------------------------------------------------------------------------


def test_fault_spec_parsing_and_one_shot_consumption():
    plan = faultinject.parse_spec("actor_crash:3, nan_loss:50 ,ckpt_corrupt")
    assert plan.arg("actor_crash") == 3
    assert plan.arg("nan_loss") == 50
    assert plan.arg("ckpt_corrupt") == 0
    assert plan.arg("sigterm") is None
    assert plan.consume("actor_crash") is True
    assert plan.consume("actor_crash") is False  # one-shot
    assert plan.consume("sigterm") is False  # not armed
    # Mapping form (arch.fault_spec=nan_loss:3 parses to a dict via YAML).
    plan = faultinject.parse_spec({"nan_loss": 3})
    assert plan.arg("nan_loss") == 3
    assert faultinject.parse_spec("") is None
    assert faultinject.parse_spec(None) is None
    with pytest.raises(ValueError, match="unknown fault"):
        faultinject.parse_spec("explode_chip:1")


def test_injection_points_are_noops_without_a_plan():
    faultinject.reset()
    assert faultinject.get_plan() is None
    faultinject.maybe_crash_actor(0, 0)
    faultinject.maybe_stall_queue(0, 0)
    faultinject.maybe_sigterm(0)
    assert faultinject.poison_step() is None
    assert faultinject.ckpt_corrupt_armed() is False


def test_grow_resize_fault_fires_once_at_its_window():
    faultinject.configure("grow:2")
    assert faultinject.maybe_resize(1) is None  # not its window yet
    assert faultinject.maybe_resize(2) == "grow"
    assert faultinject.maybe_resize(2) is None  # one-shot


def test_replica_slow_straggles_replica_zero_only(monkeypatch):
    sleeps = []
    monkeypatch.setattr(faultinject.time, "sleep", lambda s: sleeps.append(s))
    faultinject.configure("replica_slow:40")
    faultinject.maybe_slow_replica(1)
    assert sleeps == []  # only replica 0 is the straggler
    faultinject.maybe_slow_replica(0)
    faultinject.maybe_slow_replica(0)  # sustained, not one-shot
    assert sleeps == [0.04, 0.04]


def test_find_step_count_locates_optax_counter():
    import jax.numpy as jnp
    import optax

    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-3))
    state = opt.init({"w": jnp.ones(3)})
    count = guards.find_step_count(state)
    assert count is not None and int(count) == 0
    assert guards.find_step_count({"no": "counter"}) is None
