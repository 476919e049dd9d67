"""Device-resident sharded replay service (docs/DESIGN.md §2.10).

Equivalence contracts: on a 1-shard mesh the sharded sampler is BITWISE
equal to the single-device reference; on 8 shards sampling frequencies match
priorities within statistical tolerance and set_priorities round-trips
through global indices across shard boundaries. Plus the off-policy-core
dispatch pin (replay.impl=local bit-identical to the pre-dispatch path), the
Sebulba off-policy ingestion end-to-end, and OffPolicyPipeline semantics.
"""

import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stoix_tpu.replay import (
    ShardedReplayService,
    make_reference_replay,
    make_sharded_replay,
)
from stoix_tpu.utils import config as config_lib

ITEM = {"x": jnp.zeros((3,), jnp.float32), "a": jnp.zeros((), jnp.int32)}


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


def _service(n_shards, capacity=64, batch=16, **kw):
    return ShardedReplayService(
        _mesh(n_shards), ITEM, capacity_per_shard=capacity,
        sample_batch_size=batch, **kw,
    )


def _chunk(n, value):
    return {
        "x": jnp.full((n, 3), float(value), jnp.float32),
        "a": jnp.full((n,), int(value), jnp.int32),
    }


def _sharded_put(mesh, tree):
    return jax.device_put(tree, NamedSharding(mesh, P("data")))


# -- 1-shard bitwise equivalence ---------------------------------------------

@pytest.mark.parametrize("prioritized", [False, True])
def test_one_shard_bitwise_equals_reference(devices, prioritized):
    svc = _service(1, prioritized=prioritized)
    ref = make_reference_replay(64, 16, prioritized=prioritized)
    rstate = ref.init(ITEM)
    for i in range(5):
        svc.add(_chunk(8, i))
        rstate = ref.add(rstate, _chunk(8, i))
    key = jax.random.PRNGKey(3)
    ours = svc.sample(key)
    theirs = ref.sample(rstate, key)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # set_priorities round-trips identically through the collective path.
    svc.set_priorities(ours.indices, ours.probabilities + 1.0)
    rstate = ref.set_priorities(rstate, theirs.indices, theirs.probabilities + 1.0)
    key2 = jax.random.PRNGKey(4)
    np.testing.assert_array_equal(
        np.asarray(svc.sample(key2).indices), np.asarray(ref.sample(rstate, key2).indices)
    )


# -- 8-shard statistical equivalence ----------------------------------------

def test_eight_shard_frequencies_match_priorities(devices):
    n_items, batch = 64, 8192
    svc = _service(8, capacity=8, batch=batch, prioritized=True,
                   priority_exponent=1.0)
    svc.add(_chunk(n_items, 0))
    mesh = svc.mesh
    # Priority of global item g proportional to g (item 0 never drawn).
    idx = jnp.tile(jnp.arange(n_items, dtype=jnp.int32), batch // n_items)
    prio = idx.astype(jnp.float32)
    svc.set_priorities(_sharded_put(mesh, idx), _sharded_put(mesh, prio))

    # Identify drawn items by their global index.
    drawn = svc.sample(jax.random.PRNGKey(0))
    g_idx = np.asarray(drawn.indices)
    counts = np.bincount(g_idx, minlength=n_items).astype(float)
    weights = np.arange(n_items, dtype=float)
    expected = weights / weights.sum() * batch
    # Total-variation distance between empirical and target distributions.
    tv = 0.5 * np.abs(counts - expected).sum() / batch
    assert tv < 0.05, (tv, counts[:8], expected[:8])
    assert counts[0] == 0  # zero-priority item is never sampled

    # Probabilities are normalized by the GLOBAL mass, not per shard.
    np.testing.assert_allclose(
        np.asarray(drawn.probabilities), g_idx / weights.sum(), rtol=1e-4
    )


def test_set_priorities_roundtrips_across_shard_boundaries(devices):
    capacity = 8
    svc = _service(8, capacity=capacity, batch=64, prioritized=True,
                   priority_exponent=1.0)
    svc.add(_chunk(64, 7))
    mesh = svc.mesh
    # Concentrate ALL mass on boundary slots of different shards: the last
    # slot of shard 0 (global 7), the first of shard 1 (global 8), and the
    # last of shard 7 (global 63).
    hot = [7, 8, 63]
    zero_idx = jnp.arange(64, dtype=jnp.int32)
    svc.set_priorities(
        _sharded_put(mesh, zero_idx),
        _sharded_put(mesh, jnp.zeros((64,), jnp.float32) - 1e-6),
    )
    idx = jnp.asarray((hot * 22)[:64], jnp.int32)
    svc.set_priorities(
        _sharded_put(mesh, idx), _sharded_put(mesh, jnp.ones((64,)) * 5.0)
    )
    drawn = svc.sample(jax.random.PRNGKey(1))
    got = set(np.asarray(drawn.indices).tolist())
    assert got.issubset(set(hot)), got
    assert got == set(hot), got  # every boundary slot is reachable


def test_uniform_sampling_covers_all_shards(devices):
    svc = _service(8, capacity=8, batch=1024, prioritized=False)
    svc.add(_chunk(64, 1))
    drawn = svc.sample(jax.random.PRNGKey(2))
    owners = set((np.asarray(drawn.indices) // 8).tolist())
    assert owners == set(range(8)), owners


def test_add_wraps_per_shard_ring(devices):
    svc = _service(8, capacity=4, batch=64)
    for i in range(3):  # 3 x 32 global items into 8 x 4 slots -> wraps
        svc.add(_chunk(32, i))
    occ = svc.observe()["occupancy"]
    assert occ == [4] * 8
    drawn = svc.sample(jax.random.PRNGKey(5))
    # Only the freshest writes survive the ring.
    assert set(np.asarray(drawn.experience["a"]).tolist()).issubset({1, 2})


def test_transport_ledger_counts_samples_not_experience(devices):
    svc = _service(8, capacity=64, batch=16)
    base = svc.stats()
    for i in range(4):
        svc.add(_chunk(32, i))
    svc.sample(jax.random.PRNGKey(6))
    stats = svc.stats()
    ingested = stats["ingested_bytes_total"] - base["ingested_bytes_total"]
    crossed = stats["sampled_bytes_crossed"] - base["sampled_bytes_crossed"]
    assert ingested == 4 * 32 * (3 * 4 + 4)  # x[3]f32 + a i32 per row
    assert crossed == 16 * (3 * 4 + 4 + 8)  # rows + int32 index + f32 prob
    assert crossed < ingested


def test_sample_batch_must_divide_over_shards():
    with pytest.raises(ValueError, match="divide evenly"):
        make_sharded_replay(capacity=8, sample_batch_size=9, num_shards=8)


# -- off_policy_core dispatch ------------------------------------------------

def _dqn_config(extra):
    return config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_dqn.yaml",
        [
            "env=identity_game", "arch.total_num_envs=16",
            "arch.total_timesteps=512", "arch.num_evaluation=1",
            "arch.num_eval_episodes=8", "arch.absolute_metric=False",
            "system.rollout_length=8", "system.total_buffer_size=2048",
            "system.total_batch_size=64", "system.warmup_steps=8",
            # Tiny torso: these tests pin DISPATCH behavior, not capacity —
            # smaller XLA programs keep the not-slow lane cheap.
            "network.actor_network.pre_torso.layer_sizes=[32]",
            "logger.use_console=False",
        ] + extra,
    )


def _dqn_params_after_one_window(config):
    from stoix_tpu import envs
    from stoix_tpu.parallel import create_mesh
    from stoix_tpu.systems.q_learning.ff_dqn import dqn_loss
    from stoix_tpu.systems.q_learning.q_family import q_learner_setup
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    mesh = create_mesh({"data": -1})
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, _ = envs.make(config)
    setup, warmup = q_learner_setup(
        env, config, mesh, jax.random.PRNGKey(0), dqn_loss
    )
    state = warmup(setup.learner_state)
    out = setup.learn(state)
    return jax.tree.map(np.asarray, out.learner_state.params)


def test_replay_impl_local_is_bit_identical_to_pre_dispatch(devices):
    """`system.replay.impl=local` must route through EXACTLY the pre-service
    item buffer: a config carrying the key and one with the replay subtree
    absent entirely produce bitwise-identical params after a real warmup +
    learn window."""
    with_key = _dqn_params_after_one_window(_dqn_config(["system.replay.impl=local"]))
    cfg = _dqn_config([])
    del cfg.system["replay"]  # the pre-PR config shape
    without_key = _dqn_params_after_one_window(cfg)
    for a, b in zip(jax.tree.leaves(with_key), jax.tree.leaves(without_key)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_replay_impl_sharded_trains_anakin_dqn(devices):
    # Slow lane: the sharded sampler's math is covered by the not-slow
    # equivalence suite; this drives the full Anakin dispatch end-to-end.
    from stoix_tpu.systems.q_learning import ff_dqn

    ret = ff_dqn.run_experiment(_dqn_config(["system.replay.impl=sharded"]))
    assert np.isfinite(ret)


def test_replay_impl_unknown_rejected(devices):
    from stoix_tpu.systems.q_learning import ff_dqn

    with pytest.raises(ValueError, match="replay.impl"):
        ff_dqn.run_experiment(_dqn_config(["system.replay.impl=hbm2"]))


def test_anakin_prioritized_refused_not_silently_uniform(devices):
    # The ItemBuffer interface has no set_priorities seam: accepting
    # replay.prioritized here would freeze priorities at the insert value
    # and silently sample uniform — refuse instead.
    from stoix_tpu.systems.q_learning import ff_dqn

    with pytest.raises(ValueError, match="set_priorities"):
        ff_dqn.run_experiment(
            _dqn_config(
                ["system.replay.impl=sharded", "system.replay.prioritized=True"]
            )
        )


def test_sample_never_returns_unwritten_slot_on_partial_fill(devices):
    # Draws are clipped into the WRITTEN prefix of each ring: even the
    # f32-rounding sliver at the top of a shard's ownership range (where
    # searchsorted lands one past the last written slot) must resolve to a
    # written slot, never a zero row with probability 0.
    svc = _service(8, capacity=8, batch=2048, prioritized=False)
    svc.add(_chunk(16, 5))  # 2 of 8 slots written per shard
    drawn = svc.sample(jax.random.PRNGKey(9))
    slots = np.asarray(drawn.indices) % 8
    assert slots.max() <= 1, slots.max()
    np.testing.assert_array_equal(np.asarray(drawn.experience["a"]), 5)
    assert (np.asarray(drawn.probabilities) > 0).all()


# -- Sebulba off-policy ingestion -------------------------------------------

SEBULBA_BASE = [
    "env=identity_game", "arch.total_num_envs=8",
    "arch.total_timesteps=1024", "arch.num_evaluation=1",
    "arch.num_eval_episodes=8", "system.rollout_length=8",
    "system.total_buffer_size=4096", "system.total_batch_size=64",
    "system.replay.min_fill=128", "arch.actor.device_ids=[0]",
    "arch.actor.actor_per_device=2", "arch.learner.device_ids=[1,2]",
    "arch.evaluator_device_id=3", "logger.use_console=False",
]


def _sebulba_config(extra):
    return config_lib.compose(
        config_lib.default_config_dir(), "default/sebulba/default_ff_dqn.yaml",
        SEBULBA_BASE + extra,
    )


@pytest.fixture(scope="module")
def sebulba_dqn_run(devices):
    """ONE end-to-end drive of Sebulba ff_dqn with an actor crash injected
    mid-run: its return, LAST_RUN_STATS and the MISC events it logged."""
    from stoix_tpu.systems.q_learning.sebulba import ff_dqn
    from stoix_tpu.utils.logger import LogEvent, StoixLogger

    misc = {}
    original = StoixLogger.log

    def log(self, metrics, t, t_eval, event):
        if event == LogEvent.MISC:
            misc.update(metrics)
        return original(self, metrics, t, t_eval, event)

    patch = pytest.MonkeyPatch()
    patch.setenv("STOIX_TPU_FAULT", "actor_crash:2")
    patch.setattr(StoixLogger, "log", log)
    try:
        ret = ff_dqn.run_experiment(_sebulba_config([]))
    finally:
        patch.undo()
    return {"return": ret, "stats": dict(ff_dqn.LAST_RUN_STATS), "misc": misc}


def test_sebulba_dqn_trains_and_actor_crash_never_deadlocks(sebulba_dqn_run):
    """Both acceptance criteria on the one drive: ff_dqn trains
    through the OffPolicyPipeline + sharded replay service (replay ledger
    populated), AND an injected actor crash mid-run is supervised-restarted
    while the SAMPLING learner keeps going — no lockstep collect to
    deadlock on."""
    assert np.isfinite(sebulba_dqn_run["return"])
    stats = sebulba_dqn_run["stats"]
    assert stats["replay"]["added_items"] > 0
    assert stats["replay"]["sampled_items"] > 0
    assert stats["replay"]["sampled_bytes_crossed"] > 0
    assert stats["resilience"]["actor_restarts"] >= 1


@pytest.mark.parametrize(
    "what", ["setup_phases", "actor_step_timers", "prepare_data", "learner_timers", "stats"]
)
def test_sebulba_dqn_reports_what_the_one_runner_reports(sebulba_dqn_run, what):
    """The DQN system runs the Sebulba runner's loop (sebulba/runner.py), so
    it reports what PPO does: the set-up phases, the span-fed per-step actor
    timers, the hand-off's percentiles, and the whole-run stats."""
    stats, misc = sebulba_dqn_run["stats"], sebulba_dqn_run["misc"]
    if what == "setup_phases":
        assert set(stats["setup_phases"]) == {
            "mesh_build", "env_build", "network_init", "learner_setup", "evaluator_setup",
            "logger_build", "first_tick", "unspanned",
        }
        assert all(seconds > 0.0 for seconds in stats["setup_phases"].values())
    elif what == "actor_step_timers":
        step = misc["actor0_rollout_time"] / 8
        split = misc["actor0_inference_time"] + misc["actor0_env_step_time"]
        assert 0.0 < split <= step * 1.001, (split, step)
    elif what == "prepare_data":
        assert misc["actor0_prepare_data_p50"] > 0.0 and "actor0_queue_put_time" in misc
    elif what == "learner_timers":
        assert {"learner_ingest_time", "learner_learn_time", "learner_learn_p50"} <= set(misc)
    else:
        assert stats["resilience"]["fleet"] is False and stats["integrity"]["enabled"] is False
        assert stats["total_env_steps"] == stats["replay"]["added_items"] > 0
        assert stats["fps"] > 0.0 and "impact" not in stats


@pytest.mark.slow
def test_sebulba_dqn_prioritized_replay(devices):
    # Slow lane: the prioritized MATH is covered by the not-slow sampler
    # equivalence suite above; this drives the full Sebulba PER wiring
    # (per-TD priorities + importance weights) end-to-end.
    from stoix_tpu.systems.q_learning.sebulba import ff_dqn

    ret = ff_dqn.run_experiment(
        _sebulba_config(["system.replay.prioritized=True"])
    )
    assert np.isfinite(ret)


def test_sebulba_dqn_requires_sharded_impl(devices):
    from stoix_tpu.systems.q_learning.sebulba import ff_dqn

    with pytest.raises(ValueError, match="sharded"):
        ff_dqn.run_experiment(_sebulba_config(["system.replay.impl=local"]))


# -- OffPolicyPipeline semantics ---------------------------------------------

def test_offpolicy_pipeline_poll_never_lockstep():
    from stoix_tpu.sebulba.core import OffPolicyPipeline

    pipe = OffPolicyPipeline(num_actors=3)
    pipe.push(0, "a0")
    pipe.push(2, "c0")
    # Two of three actors contributed; poll returns both without waiting
    # for actor 1 (the on-policy collect would block on it).
    items = pipe.poll(timeout=0.0)
    assert [a for a, _ in items] == [0, 2]
    assert pipe.poll(timeout=0.0) == []


def test_offpolicy_pipeline_poison_pill_raises_typed():
    from stoix_tpu.resilience.errors import ComponentFailure
    from stoix_tpu.sebulba.core import OffPolicyPipeline

    pipe = OffPolicyPipeline(num_actors=2)
    failure = ComponentFailure("actor-1", "budget exhausted", None)
    pipe.fail(1, failure)
    with pytest.raises(ComponentFailure):
        pipe.poll(timeout=0.0)


def test_offpolicy_pipeline_starvation_names_stalest_actor():
    from stoix_tpu.observability import ActorStarvationError
    from stoix_tpu.sebulba.core import OffPolicyPipeline

    pipe = OffPolicyPipeline(num_actors=2)
    pipe.heartbeats.beat("actor-0")  # actor-1 never beat -> stalest
    with pytest.raises(ActorStarvationError) as err:
        pipe.wait_for_data(timeout=0.05)
    assert err.value.actor_id == 1


def test_offpolicy_pipeline_backpressure_bounded():
    from stoix_tpu.sebulba.core import OffPolicyPipeline

    pipe = OffPolicyPipeline(num_actors=1, depth_per_actor=1)
    pipe.push(0, "p0")
    with pytest.raises(queue.Full):
        pipe.push(0, "p1", timeout=0.05)
    assert pipe.drain(timeout=0.05) == 1


# -- trajectory assembly (parallel.assemble_global_array) --------------------

def test_assemble_global_array_env_axis(devices):
    """array_axis=1: [T, E/n] per-device trajectory shards assemble into a
    [T, E] global sharded on the ENV axis — device d's columns are its own
    slice (assembling on the leading axis would tile devices along TIME and
    let GAE bootstrap across the device seam)."""
    from stoix_tpu.parallel import assemble_global_array

    mesh = _mesh(2)
    t_len, env_half = 4, 3
    shards = [
        jax.device_put(
            jnp.arange(t_len * env_half, dtype=jnp.float32).reshape(t_len, env_half)
            + 100.0 * d,
            mesh.devices.flatten()[d],
        )
        for d in range(2)
    ]
    out = assemble_global_array(shards, mesh, axis="data", array_axis=1)
    assert out.shape == (t_len, 2 * env_half)
    expected = np.concatenate([np.asarray(s) for s in shards], axis=1)
    np.testing.assert_array_equal(np.asarray(out), expected)
    spec = out.sharding.spec
    assert spec == P(None, "data"), spec


def test_assemble_global_array_leading_axis_default(devices):
    from stoix_tpu.parallel import assemble_global_array

    mesh = _mesh(2)
    shards = [
        jax.device_put(jnp.full((5,), float(d)), mesh.devices.flatten()[d])
        for d in range(2)
    ]
    out = assemble_global_array(shards, mesh, axis="data")
    assert out.shape == (10,)
    np.testing.assert_array_equal(np.asarray(out), [0.0] * 5 + [1.0] * 5)
