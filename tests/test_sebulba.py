"""Sebulba architecture tests: threads/queues/param-server end-to-end on a
multi-device split, plus the native C++ env pool."""

import jax

import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.slow
def test_sebulba_ppo_multi_device_split(devices):
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    cfg = _compose(
        BASE
        + [
            "arch.actor.device_ids=[0,1]",
            "arch.learner.device_ids=[2,3]",
            "arch.evaluator_device_id=4",
            "system.num_minibatches=2",
        ]
    )
    ret = ff_ppo.run_experiment(cfg)
    assert np.isfinite(ret)
    # IMPACT disabled-path pin (docs/DESIGN.md §2.12): the default config
    # runs the untouched on-policy pipeline and reports no impact stats.
    assert ff_ppo.LAST_RUN_STATS["impact"] is None


@pytest.mark.slow
def test_sebulba_impala_runs(devices):
    from stoix_tpu.systems.impala.sebulba import ff_impala

    cfg = config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_impala.yaml",
        BASE
        + [
            "arch.actor.device_ids=[0]",
            "arch.actor.actor_per_device=2",
            "arch.learner.device_ids=[1]",
            "arch.evaluator_device_id=0",
        ],
    )
    ret = ff_impala.run_experiment(cfg)
    assert np.isfinite(ret)


def test_native_cvec_pool_matches_python_dynamics():
    # The C++ CartPole must produce identical trajectories to the Python env
    # under identical states/actions.
    from stoix_tpu.envs.classic import CartPole
    from stoix_tpu.envs.cvec import CVecCartPole

    cpp = CVecCartPole(1, seed=123)
    ts = cpp.reset()
    state0 = np.asarray(ts.observation.agent_view[0])

    py = CartPole()
    from stoix_tpu.envs.classic import PhysicsState

    py_state = PhysicsState(
        key=jax.random.PRNGKey(0),
        physics=jnp.asarray(state0),
        step_count=jnp.zeros((), jnp.int32),
    )
    actions = [1, 0, 1, 1, 0, 1, 0, 0]
    for a in actions:
        ts_cpp = cpp.step(np.asarray([a], np.int32))
        py_state, ts_py = py.step(py_state, jnp.asarray(a))
        np.testing.assert_allclose(
            ts_cpp.extras["next_obs"].agent_view[0],
            np.asarray(ts_py.observation.agent_view),
            rtol=1e-5,
        )
        assert bool(ts_cpp.discount[0] == 0.0) == bool(ts_py.discount == 0.0)


@pytest.mark.slow
def test_sebulba_ppo_continuous_on_native_pool(devices):
    """Continuous control end-to-end through the Sebulba stack on the C++
    pool: Pendulum-v1 with float actions via cvec_step_cont, TanhNormal head
    inferred from the pool's Box action space."""
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    cfg = _compose(
        [
            "env=pendulum",
            "env.backend=cvec",
            "env.kwargs.max_steps=200",
            "network=mlp_continuous",
            "arch.total_num_envs=8",
            "arch.total_timesteps=2048",
            "arch.num_evaluation=1",
            "arch.num_eval_episodes=4",
            "system.rollout_length=8",
            "system.num_minibatches=2",
            "logger.use_console=False",
            "arch.actor.device_ids=[0]",
            "arch.actor.actor_per_device=1",
            "arch.learner.device_ids=[1]",
            "arch.evaluator_device_id=0",
        ]
    )
    ret = ff_ppo.run_experiment(cfg)
    assert np.isfinite(ret)
    assert ret < 0.0  # pendulum returns are negative costs


def test_impala_reward_normalization_is_shard_invariant(devices):
    """maybe_normalize_rewards must produce the GLOBAL-batch normalization
    regardless of how envs are split across data shards (the pmean over
    "data"): per-shard stats would make gradients depend on device count."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from stoix_tpu.base_types import PPOTransition
    from stoix_tpu.systems.impala.sebulba.ff_impala import maybe_normalize_rewards
    from stoix_tpu.utils import config as config_lib

    cfg = config_lib.Config.from_dict(
        {"system": {"normalize_rewards": True, "reward_scale": 1.0, "reward_eps": 1e-8}}
    )
    rng = np.random.default_rng(0)
    rewards = jnp.asarray(rng.normal(3.0, 2.0, size=(4, 8)), jnp.float32)  # [T, E]
    zeros = jnp.zeros_like(rewards)
    traj = PPOTransition(
        done=zeros, truncated=zeros, action=zeros, value=zeros,
        reward=rewards, log_prob=zeros, obs=zeros, next_obs=zeros, info={},
    )

    def per_shard(tr):
        return maybe_normalize_rewards(tr, cfg).reward

    for n_shards in (1, 2, 4):
        mesh = Mesh(np.asarray(jax.devices("cpu")[:n_shards]), ("data",))
        out = jax.jit(
            jax.shard_map(
                per_shard, mesh=mesh,
                in_specs=(PPOTransition(*([P(None, "data")] * 9)),),
                out_specs=P(None, "data"),
            )
        )(traj)
        expected = (rewards - rewards.mean()) / (rewards.std() + 1e-8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)


def test_param_server_places_once_per_device_and_reprime_reuses(devices):
    """Satellite (docs/DESIGN.md §2.10): distribute_params device_puts each
    version once per DEVICE, not once per actor — actors sharing a device
    receive the same placed copy — and reprime reuses it (zero transfers)."""
    from stoix_tpu.observability import get_registry
    from stoix_tpu.sebulba.core import ParameterServer

    hist = get_registry().histogram("stoix_tpu_sebulba_param_transfer_seconds")
    dev_a, dev_b = devices[0], devices[1]

    def transfers():
        return sum(
            int(hist.summary({"queue": "params", "device": str(d)}).get("count", 0))
            for d in (dev_a, dev_b)
        )

    server = ParameterServer([dev_a, dev_b], actors_per_device=3)
    before = transfers()
    server.distribute_params({"w": jnp.ones((4,), jnp.float32)})
    assert transfers() - before == 2, "one device_put per device, not per actor"

    got = [server.get_params_versioned(actor_id, timeout=2.0).params for actor_id in range(6)]
    # Actors 0-2 share dev_a and must hold the SAME placed copy (identity,
    # not equality); likewise 3-5 on dev_b.
    assert got[0] is got[1] is got[2]
    assert got[3] is got[4] is got[5]
    assert got[0] is not got[3]

    # reprime re-feeds the placed copy without a new transfer.
    before = transfers()
    assert server.reprime(2)
    assert transfers() == before
    assert server.get_params_versioned(2, timeout=2.0).params is got[0]


def test_native_pool_is_built_from_source_by_content_hash(tmp_path, monkeypatch):
    # The library that loads is named by the hash of the source that is read
    # (not by an mtime, which a copy to another machine makes arbitrary); a
    # changed source with no compiler is a loud error, never a stale pool.
    import hashlib
    import shutil

    from stoix_tpu.envs import cvec

    with open(cvec._SOURCE, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert cvec._ensure_built().endswith(f"libcvec-{digest}.so")

    edited = tmp_path / "cvec.cpp"
    shutil.copy(cvec._SOURCE, edited)
    with open(edited, "a") as f:
        f.write("\n// edited\n")
    monkeypatch.setattr(cvec, "_SOURCE", str(edited))
    monkeypatch.setattr(cvec, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(shutil, "which", lambda name: None)
    with pytest.raises(RuntimeError, match="needs g\\+\\+"):
        cvec._ensure_built()
    assert not list(tmp_path.glob("*.so"))


def test_jax_env_twin_needs_the_cpu_backend_and_says_so(monkeypatch):
    # Sebulba's pure-JAX env twin lives on the host CPU beside the
    # accelerator; when the CPU backend is not in the process
    # (JAX_PLATFORMS=tpu) the failure names the variable and the way out.
    from stoix_tpu.envs import factory

    def no_cpu_backend(backend=None):
        raise RuntimeError(f"Unknown backend {backend}")

    monkeypatch.setattr(factory.jax, "devices", no_cpu_backend)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS='tpu' excludes it"):
        factory.JaxEnvFactory("CartPole-v1")
