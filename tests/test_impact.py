"""IMPACT stale-trajectory reuse (arXiv:1912.00167, docs/DESIGN.md §2.12).

Pins, in order of importance:
  * the disabled path IS the on-policy path — impact_settings_from_config
    returns None on the default config, and impact_loss with target ==
    behavior reduces BITWISE to ppo_clip_loss (test_sebulba.py additionally
    asserts LAST_RUN_STATS["impact"] is None after a plain Sebulba run);
  * ParameterServer versioning: monotone versions travel WITH the params
    through the actor queues; get_params stays version-free (back-compat);
  * ImpactIngest scheduling: fresh full sets preferred, bounded reuse of the
    newest buffered batch when fresh data is late, over-stale batches
    dropped, blocking only when there is nothing at all to chew on;
  * end-to-end (slow): a Sebulba run with a WEDGED actor keeps stepping,
    reports per-update staleness > 0, reuses buffered batches, refreshes the
    target network, and keeps system.update_guard wired.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu.observability import get_registry
from stoix_tpu.ops import losses
from stoix_tpu.resilience import faultinject
from stoix_tpu.utils import config as config_lib

BASE = [
    "env=identity_game",
    "arch.total_num_envs=8",
    "arch.total_timesteps=2048",
    "arch.num_evaluation=1",
    "arch.num_eval_episodes=8",
    "system.rollout_length=8",
    "logger.use_console=False",
]


def _compose(extra):
    return config_lib.compose(
        config_lib.default_config_dir(), "default/sebulba/default_ff_ppo.yaml", extra
    )


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.reset()
    yield
    faultinject.reset()


# --------------------------------------------------------------------------- #
# impact_loss
# --------------------------------------------------------------------------- #


def test_impact_loss_reduces_to_ppo_clip_bitwise():
    """target == behavior and rho_clip >= 1 make the IS ratio exactly 1.0 —
    the surrogate must be BITWISE equal to ppo_clip_loss (this is the math
    half of the enabled=false identity pin)."""
    rng = np.random.default_rng(0)
    log_prob = jnp.asarray(rng.normal(-1.0, 0.5, 64), jnp.float32)
    old_log_prob = jnp.asarray(rng.normal(-1.0, 0.5, 64), jnp.float32)
    advantage = jnp.asarray(rng.normal(0.0, 1.0, 64), jnp.float32)
    impact = losses.impact_loss(
        log_prob, old_log_prob, old_log_prob, advantage, epsilon=0.2, rho_clip=2.0
    )
    ppo = losses.ppo_clip_loss(log_prob, old_log_prob, advantage, epsilon=0.2)
    assert jnp.array_equal(impact, ppo)


def test_impact_loss_clips_is_ratio():
    """A behavior policy far LESS likely than the target would make the IS
    ratio explode; rho_clip bounds it. Check against the hand-written
    formula, including the clip actually binding."""
    log_prob = jnp.asarray([0.0, -0.5], jnp.float32)
    target_lp = jnp.asarray([-0.1, -0.4], jnp.float32)
    behavior_lp = jnp.asarray([-5.0, -0.4], jnp.float32)  # first: rho >> clip
    advantage = jnp.asarray([1.0, -2.0], jnp.float32)
    eps, rho_clip = 0.2, 2.0

    rho = np.minimum(np.exp(np.asarray(target_lp) - np.asarray(behavior_lp)), rho_clip)
    assert rho[0] == rho_clip  # the clip must actually bind in this fixture
    ratio = np.exp(np.asarray(log_prob) - np.asarray(target_lp))
    expected = -np.mean(
        np.minimum(
            rho * ratio * np.asarray(advantage),
            rho * np.clip(ratio, 1 - eps, 1 + eps) * np.asarray(advantage),
        )
    )
    got = losses.impact_loss(log_prob, behavior_lp, target_lp, advantage, eps, rho_clip)
    np.testing.assert_allclose(float(got), expected, rtol=1e-6)


# --------------------------------------------------------------------------- #
# ParameterServer versioning
# --------------------------------------------------------------------------- #


def test_param_server_versions_are_monotone_and_back_compat(devices):
    from stoix_tpu.sebulba.core import ParameterServer, VersionedParams

    server = ParameterServer([devices[0]], actors_per_device=2)
    assert server.version == 0

    server.distribute_params({"w": jnp.ones((2,), jnp.float32)})
    assert server.version == 1
    got = server.get_params_versioned(0, timeout=2.0)
    assert isinstance(got, VersionedParams)
    assert got.version == 1
    # Both actors on the device are fed the same version.
    assert server.get_params_versioned(1, timeout=2.0).params["w"].shape == (2,)

    server.distribute_params({"w": jnp.zeros((2,), jnp.float32)})
    assert server.version == 2
    assert server.get_params_versioned(0, timeout=2.0).version == 2

    # reprime re-feeds the LATEST version, version intact.
    assert server.reprime(1)
    reprimed = server.get_params_versioned(1, timeout=2.0)
    assert reprimed.version == 2
    server.shutdown()
    assert server.get_params_versioned(0, timeout=2.0) is None


# --------------------------------------------------------------------------- #
# Settings gating
# --------------------------------------------------------------------------- #


def test_impact_disabled_by_default_and_refusals():
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    cfg = _compose(BASE)
    assert ff_ppo.impact_settings_from_config(cfg) is None

    enabled = _compose(BASE + ["system.impact.enabled=true"])
    settings = ff_ppo.impact_settings_from_config(enabled)
    assert settings is not None and settings.rho_clip >= 1.0

    with pytest.raises(ValueError, match="rho_clip"):
        ff_ppo.impact_settings_from_config(
            _compose(BASE + ["system.impact.enabled=true", "system.impact.rho_clip=0.5"])
        )
    with pytest.raises(ValueError, match="target_update_interval"):
        ff_ppo.impact_settings_from_config(
            _compose(
                BASE
                + [
                    "system.impact.enabled=true",
                    "system.impact.target_update_interval=0",
                ]
            )
        )
    with pytest.raises(ValueError, match="max_staleness"):
        ff_ppo.impact_settings_from_config(
            _compose(
                BASE
                + ["system.impact.enabled=true", "system.impact.max_staleness=0"]
            )
        )


def test_impact_rejects_custom_learn_step_builder():
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    cfg = _compose(BASE + ["system.impact.enabled=true"])
    with pytest.raises(ValueError, match="learn_step_builder"):
        ff_ppo.run_experiment(cfg, learn_step_builder=lambda *a: None)


# --------------------------------------------------------------------------- #
# ImpactIngest scheduling (fake pipeline — deterministic)
# --------------------------------------------------------------------------- #


class _ScriptedPipe:
    """Feeds scripted (actor_id, (version, payload)) batches, one list per
    poll call; wait_for_data fails the test instead of blocking forever."""

    def __init__(self, scripted):
        self.scripted = list(scripted)

    def poll(self, max_items=64, timeout=0.0):
        return self.scripted.pop(0) if self.scripted else []

    def wait_for_data(self, timeout=180.0):
        items = self.poll()
        assert items, "learner blocked in wait_for_data with no scripted data"
        return items


def _settings(**over):
    from stoix_tpu.sebulba.sources import ImpactSettings

    base = dict(
        target_update_interval=1, rho_clip=2.0, max_staleness=3, max_reuse=2,
        buffer_size=2,
    )
    base.update(over)
    return ImpactSettings(**base)


def _assemble(payloads):
    return tuple(payloads)


def test_impact_ingest_reuses_stale_when_fresh_is_late():
    from stoix_tpu.sebulba.sources import ImpactIngest

    pipe = _ScriptedPipe(
        [
            [(0, (1, "a0")), (1, (1, "b0"))],  # warmup: full fresh set @v1
            [], [], [],                        # fresh late for three updates
            [(0, (4, "a1")), (1, (4, "b1"))],  # fresh again @v4
        ]
    )
    ingest = ImpactIngest(pipe, need=2, settings=_settings())

    first = ingest.next_batch(_assemble, current_version=1)
    assert first.fresh and first.behavior_version == 1
    assert first.batch == ("a0", "b0")

    # Fresh late -> re-step the buffered batch, twice (max_reuse=2), with the
    # SAME assembled batch object and a growing staleness window.
    second = ingest.next_batch(_assemble, current_version=2)
    assert not second.fresh and second.batch is first.batch
    assert second.behavior_version == 1
    third = ingest.next_batch(_assemble, current_version=3)
    assert not third.fresh and third.batch is first.batch

    # Reuse budget exhausted -> block for fresh data and step on it.
    fourth = ingest.next_batch(_assemble, current_version=4)
    assert fourth.fresh and fourth.behavior_version == 4
    assert fourth.batch == ("a1", "b1")

    reused = get_registry().counter("stoix_tpu_impact_reused_batches_total")
    assert reused.value() >= 2


def test_impact_ingest_drops_overstale_buffered_batches():
    from stoix_tpu.sebulba.sources import ImpactIngest

    dropped = get_registry().counter("stoix_tpu_impact_dropped_batches_total")
    before = dropped.value()
    pipe = _ScriptedPipe(
        [
            [(0, (1, "old"))],
            [],                  # poll empty at the stale check
            [(0, (9, "new"))],   # arrives via wait_for_data after the drop
        ]
    )
    ingest = ImpactIngest(pipe, need=1, settings=_settings(max_staleness=2, max_reuse=5))

    first = ingest.next_batch(_assemble, current_version=1)
    assert first.fresh and first.behavior_version == 1

    # Nine versions later the buffered batch exceeds max_staleness: it must
    # be DROPPED (never re-stepped) and the learner must wait for fresh data.
    second = ingest.next_batch(_assemble, current_version=10)
    assert second.fresh and second.behavior_version == 9
    assert dropped.value() - before == 1


def test_impact_ingest_mixed_actor_payloads_form_full_set():
    """Any `need` payloads tile to the full batch shape — two payloads from
    the SAME healthy actor are a valid fresh set (this is what keeps the
    learner fed while another actor is wedged)."""
    from stoix_tpu.sebulba.sources import ImpactIngest

    pipe = _ScriptedPipe([[(1, (2, "b0")), (1, (3, "b1"))]])
    ingest = ImpactIngest(pipe, need=2, settings=_settings())
    got = ingest.next_batch(_assemble, current_version=3)
    assert got.fresh and got.batch == ("b0", "b1")
    # Oldest behavior version in the set defines the batch's staleness.
    assert got.behavior_version == 2


# --------------------------------------------------------------------------- #
# End-to-end (slow)
# --------------------------------------------------------------------------- #


@pytest.mark.slow
def test_sebulba_impact_keeps_stepping_under_wedged_actor(devices):
    """ISSUE acceptance: with one actor WEDGED mid-run (queue_stall fault),
    the IMPACT learner keeps stepping — re-using buffered stale trajectories
    and assembling fresh sets from the healthy actor — finishes all updates,
    reports per-update staleness > 0, refreshes the target network, and
    keeps system.update_guard wired."""
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    injected = get_registry().counter("stoix_tpu_resilience_faults_injected_total")
    injected_before = injected.value(labels={"fault": "queue_stall"})

    cfg = _compose(
        BASE
        + [
            "arch.actor.device_ids=[0]",
            "arch.actor.actor_per_device=2",
            "arch.learner.device_ids=[1]",
            "arch.evaluator_device_id=2",
            "system.num_minibatches=2",
            "system.update_guard=skip",
            "system.impact.enabled=true",
            "system.impact.target_update_interval=2",
            "system.impact.max_staleness=8",
            "arch.fault_spec=queue_stall:2",
        ]
    )
    ret = ff_ppo.run_experiment(cfg)
    assert np.isfinite(ret)
    assert injected.value(labels={"fault": "queue_stall"}) - injected_before == 1

    stats = ff_ppo.LAST_RUN_STATS["impact"]
    assert stats is not None
    num_updates = int(cfg.arch.num_updates)
    assert stats["updates"] == num_updates
    assert stats["fresh_updates"] + stats["reused_updates"] == num_updates
    assert stats["fresh_updates"] >= 1
    # The wedged actor makes fresh sets late: stale batches must have been
    # re-stepped, and the staleness metric must have seen real lag.
    assert stats["reused_updates"] >= 1
    assert stats["mean_staleness"] > 0
    assert stats["max_staleness_seen"] >= 1
    assert stats["target_refreshes"] >= 1
    # update_guard stays wired on the IMPACT path.
    assert ff_ppo.LAST_RUN_STATS["resilience"]["update_guard"] == "skip"
    assert ff_ppo.LAST_RUN_STATS["resilience"]["skipped_updates"] >= 0


@pytest.mark.slow
def test_sebulba_impact_healthy_run_staleness_from_pipelining(devices):
    """No faults: actors still run one-to-two versions behind the learner
    (the skip-fetch pipelining), so staleness is naturally >= 0 and the run
    matches the on-policy budget accounting exactly."""
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    cfg = _compose(
        BASE
        + [
            "arch.actor.device_ids=[0,1]",
            "arch.learner.device_ids=[2,3]",
            "arch.evaluator_device_id=4",
            "system.num_minibatches=2",
            "system.impact.enabled=true",
        ]
    )
    ret = ff_ppo.run_experiment(cfg)
    assert np.isfinite(ret)
    stats = ff_ppo.LAST_RUN_STATS["impact"]
    assert stats is not None
    assert stats["updates"] == int(cfg.arch.num_updates)
    assert stats["mean_staleness"] >= 0
    assert ff_ppo.LAST_RUN_STATS["total_env_steps"] > 0
