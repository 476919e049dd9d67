"""The MLP systems' initial learner state is the output of ONE jitted program
on its shardings (`anakin.build_learner_state`; the Sebulba actor-critic
systems' `init_networks` / `setup_learner`): the same values as the eager
op-by-op construction it replaced, every leaf where its field's spec puts it,
and no eager op left in set-up to be compiled one at a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams
from stoix_tpu.ops import running_statistics
from stoix_tpu.sebulba import actor_critic
from stoix_tpu.systems import anakin
from stoix_tpu.systems.ppo.anakin import ff_ppo, ff_ppo_continuous
from stoix_tpu.utils import compilecache, config as config_lib

ANAKIN_SYSTEMS = {
    "ff_ppo": (ff_ppo, "default_ff_ppo", "identity_game"),
    "ff_ppo_continuous": (ff_ppo_continuous, "default_ff_ppo_continuous", "ant"),
}

# The layout of systems/anakin.py's docstring, a field of PPOLearnerState each.
STATE_SPECS = ff_ppo.PPOLearnerState(
    params=P(), opt_states=P(), key=P("data"), env_state=P(None, "data"),
    timestep=P(None, "data"), obs_stats=P(), kl_beta=P(),
)


def anakin_config(system, update_batch=1, extra=()):
    _, yaml, env_name = ANAKIN_SYSTEMS[system]
    return config_lib.compose(
        config_lib.default_config_dir(), f"default/anakin/{yaml}.yaml",
        [
            f"env={env_name}", "arch.total_num_envs=16", f"arch.update_batch_size={update_batch}",
            "system.rollout_length=4", "system.num_minibatches=2", "logger.use_console=False",
            *extra,
        ],
    )


def eager_ppo_state(env, config, mesh, keys):
    """The construction `ff_ppo.learner_setup` had before it was one program:
    every op dispatched on its own, out of the same helpers and the same key."""
    actor_network, critic_network = ff_ppo.build_networks(env, config)
    actor_optim = actor_critic._adam(float(config.system.actor_lr), config)
    critic_optim = actor_critic._adam(float(config.system.critic_lr), config)
    key, actor_key, critic_key, env_key = jax.random.split(keys, 4)
    dummy_obs = jax.tree.map(lambda x: x[None], env.observation_value())
    actor_params = actor_network.init(actor_key, dummy_obs)
    critic_params = critic_network.init(critic_key, dummy_obs)
    update_batch = int(config.arch.get("update_batch_size", 1))
    env_state, timestep = anakin.reset_envs_for_anakin(env, config, env_key)
    obs_stats = running_statistics.init_state(env.observation_value().agent_view)
    broadcast = anakin.broadcast_to_update_batch
    return ff_ppo.PPOLearnerState(
        params=broadcast(ActorCriticParams(actor_params, critic_params), update_batch),
        opt_states=broadcast(
            ActorCriticOptStates(actor_optim.init(actor_params), critic_optim.init(critic_params)),
            update_batch,
        ),
        key=anakin.make_step_keys(key, mesh, config),
        env_state=env_state,
        timestep=timestep,
        obs_stats=broadcast(obs_stats, update_batch),
        kl_beta=broadcast(jnp.asarray(float(config.system.get("kl_beta", 3.0))), update_batch),
    )


def differing_leaves(expected, got, max_ulp=0):
    """[(path, largest distance in units in the last place)] of the leaves of
    `got` that are not `expected`'s: another dtype or shape, or further than
    `max_ulp` from it."""
    assert jax.tree.structure(expected) == jax.tree.structure(got)
    differing = []
    for (path, want), have in zip(
        jax.tree_util.tree_leaves_with_path(expected), jax.tree.leaves(got)
    ):
        want, have = np.asarray(want), np.asarray(have)
        if want.dtype != have.dtype or want.shape != have.shape:
            differing.append((jax.tree_util.keystr(path), None))
        elif not np.array_equal(want, have, equal_nan=True):
            ulps = float("inf")  # an integer, a key or a flag is equal or it is not
            if np.issubdtype(want.dtype, np.floating):
                spacing = np.spacing(np.maximum(np.abs(want), np.abs(have)))
                ulps = float(np.max(np.abs(want - have) / spacing))
            if ulps > max_ulp:
                differing.append((jax.tree_util.keystr(path), ulps))
    return differing


# XLA's algebraic simplifier folds the reset noise's scale into the sampler's
# own constant (`c * (sqrt(2) * erf_inv(u))` is one product under jit, two
# eagerly), so the float leaves an env's reset computes from noise may differ
# in the last places. Everything else — parameters, optimiser state, keys,
# statistics, every integer — is the eager construction's to the last bit.
RESET_MAX_ULP = 4


@pytest.mark.parametrize("update_batch", [1, 2])
@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("system", sorted(ANAKIN_SYSTEMS))
def test_the_jitted_state_is_the_eager_one_on_its_shardings(
    devices, system, n_devices, update_batch
):
    module = ANAKIN_SYSTEMS[system][0]
    config = anakin_config(system, update_batch)
    mesh = Mesh(np.array(devices[:n_devices]), ("data",))
    env, _ = envs.make(config)
    key = jax.random.PRNGKey(46)

    state = module.learner_setup(env, config, mesh, key).learner_state
    expected = eager_ppo_state(env, config, mesh, key)

    reset = lambda s: (s.env_state, s.timestep)
    rest = lambda s: s._replace(env_state=None, timestep=None)
    assert differing_leaves(rest(expected), rest(state)) == []
    assert differing_leaves(reset(expected), reset(state), max_ulp=RESET_MAX_ULP) == []
    if system == "ff_ppo":  # no noise in this env's reset
        assert differing_leaves(reset(expected), reset(state)) == []
    for field, spec, subtree in zip(state._fields, STATE_SPECS, state):
        for leaf in jax.tree.leaves(subtree):
            assert leaf.sharding.is_equivalent_to(NamedSharding(mesh, spec), leaf.ndim), (
                field, leaf.shape, leaf.sharding,
            )


@pytest.mark.parametrize("n_learners", [1, 2])
def test_the_sebulba_state_is_the_eager_one_replicated_on_the_learner_mesh(devices, n_learners):
    from stoix_tpu.envs.factory import make_factory
    from stoix_tpu.systems.ppo.sebulba import ff_ppo as sebulba_ppo

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/sebulba/default_ff_ppo.yaml",
        ["env=cartpole", "arch.total_num_envs=8", "logger.use_console=False"],
    )
    probe_envs = make_factory(config)(1)
    config.system.action_dim = probe_envs.num_actions
    learner_mesh = Mesh(np.array(devices[:n_learners]), ("data",))
    system = actor_critic.actor_critic_system(
        sebulba_ppo._networks, sebulba_ppo.get_learn_step, sebulba_ppo.OnPolicySource
    )
    key0 = jax.random.PRNGKey(46)

    networks, key = system.init_networks(config, probe_envs, key0)
    learner, key = system.setup_learner(config, networks, key, learner_mesh)

    actor, critic = sebulba_ppo._networks(config, probe_envs)
    want_key, a_key, c_key = jax.random.split(key0, 3)
    obs0 = jax.tree.map(jnp.asarray, probe_envs.reset(seed=0).observation)
    params = ActorCriticParams(actor.init(a_key, obs0), critic.init(c_key, obs0))
    opt_states = ActorCriticOptStates(
        actor_critic._adam(float(config.system.actor_lr), config).init(params.actor_params),
        actor_critic._adam(float(config.system.critic_lr), config).init(params.critic_params),
    )
    want_key, learn_key = jax.random.split(want_key)
    obs_stats = running_statistics.init_state(obs0.agent_view[0])
    expected = actor_critic.CoreLearnerState(params, opt_states, learn_key, obs_stats)

    assert differing_leaves((expected, want_key), (learner.state, key)) == []
    for leaf in jax.tree.leaves(learner.state):
        assert leaf.sharding.is_equivalent_to(NamedSharding(learner_mesh, P()), leaf.ndim)


def test_learner_setup_compiles_a_state_program_and_no_eager_op(devices):
    """Nothing else in tier-1 would notice an eager op creeping back into
    set-up: each is one more compilation, and this counts them. The widths
    are no other test's, so that no eager op of these shapes is already in
    this process's jit cache."""
    compilecache.install_cache_metrics_listener()
    config = anakin_config(
        "ff_ppo_continuous",
        extra=[
            "arch.total_num_envs=12",
            "network.actor_network.pre_torso.layer_sizes=[24,40]",
            "network.critic_network.pre_torso.layer_sizes=[24,40]",
        ],
    )
    mesh = Mesh(np.array(devices[:4]), ("data",))
    env, _ = envs.make(config)
    key = jax.block_until_ready(jax.random.PRNGKey(46))

    programs_before, compilations_before = compilecache.compile_counts()
    ff_ppo_continuous.learner_setup(env, config, mesh, key)
    programs, compilations = compilecache.compile_counts()

    assert 1 <= compilations - compilations_before <= 3
    assert programs - programs_before <= 3
