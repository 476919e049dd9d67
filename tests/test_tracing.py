"""The tracing contract of docs/DESIGN.md §2.2 on the real programs: the
scope names of `observability.SCOPES` are in the learners' compiled HLO, the
Sebulba actor's `env_step` timer times a host pool alone, policy lag is
gauged for every consumed rollout, and every Sebulba thread marks its work
with spans. (The span primitive itself: tests/test_observability.py; the
Anakin runner's phase clock: tests/test_runner_pipeline.py.)"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from stoix_tpu import observability as obs
from stoix_tpu.base_types import (
    ActorCriticOptStates,
    ActorCriticParams,
    PPOTransition,
)
from stoix_tpu.observability import (
    BLOCK_SCOPES, DELTA_SCOPES, DIFFUSION_SCOPES, HYBRID_SCOPES, LATENT_SCOPES, PROMPT_SCOPES,
    SCOPES, WINDOW_SCOPES,
)
from stoix_tpu.utils import config as config_lib

SEBULBA_TINY = [
    "env=cartpole", "env.backend=cvec", "arch.total_num_envs=16",
    "arch.actor.device_ids=[0]", "arch.actor.actor_per_device=2",
    "arch.learner.device_ids=[0]", "arch.evaluator_device_id=0",
    "arch.total_timesteps=~", "arch.num_updates=6", "arch.num_evaluation=3",
    "arch.num_eval_episodes=4", "system.rollout_length=8", "system.epochs=2",
    "system.num_minibatches=2", "logger.use_console=False",
]


def _sebulba_config(extra=()):
    return config_lib.compose(
        config_lib.default_config_dir(), "default/sebulba/default_ff_ppo.yaml",
        SEBULBA_TINY + list(extra),
    )


def _scope_components(hlo_text):
    """Every component of every op's framework path in a compiled program,
    with the transform wrappers JAX puts around a scope entered directly
    under vmap/grad (`vmap(gae)`) taken off."""
    components = set()
    for path in re.findall(r'op_name="([^"]+)"', hlo_text):
        for part in path.split("/"):
            components.add(re.sub(r"^(?:\w+\()+|\)+$", "", part))
    return components


def _sebulba_networks(config):
    from stoix_tpu.envs.factory import make_factory
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    pool = make_factory(config)(1)
    config.system.action_dim = pool.num_actions
    actor, critic = ff_ppo._build_networks(config, pool.num_actions, None, env=pool)
    obs0 = jax.tree.map(jnp.asarray, pool.reset(seed=0).observation)
    key = jax.random.PRNGKey(0)
    params = ActorCriticParams(actor.init(key, obs0), critic.init(key, obs0))
    return actor, critic, params, obs0


@pytest.fixture(scope="module")
def program_scopes(devices):
    """{program: path components} of the tiny Anakin learner, the Sebulba
    learn step and the Sebulba actors' act_fn, compiled once."""
    from stoix_tpu import envs
    from stoix_tpu.ops import running_statistics
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.systems.ppo.anakin import ff_ppo as anakin_ppo
    from stoix_tpu.systems.ppo.sebulba import ff_ppo as sebulba_ppo
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    scopes = {}
    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_ppo.yaml",
        ["env=identity_game", "arch.total_num_envs=16", "arch.num_updates=2",
         "arch.total_timesteps=~", "arch.num_evaluation=1", "system.rollout_length=4",
         "system.epochs=1", "system.num_minibatches=2"],
    )
    mesh = MeshRoles.from_config(config).learn_mesh()
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, _ = envs.make(config)
    setup = anakin_ppo.learner_setup(env, config, mesh, jax.random.PRNGKey(0))
    compiled = setup.learn.lower(setup.learner_state).compile()
    scopes["anakin_learner"] = _scope_components(compiled.as_text())

    config = _sebulba_config()
    mesh = MeshRoles.from_config(config).learn_mesh()
    actor, critic, params, obs0 = _sebulba_networks(config)
    optim = optax.adam(1e-3)
    state = sebulba_ppo.CoreLearnerState(
        params,
        ActorCriticOptStates(
            optim.init(params.actor_params), optim.init(params.critic_params)
        ),
        jax.random.PRNGKey(1),
        running_statistics.init_state(obs0.agent_view[0]),
    )
    steps, n = 8, 16
    obs = jax.tree.map(lambda x: jnp.zeros((steps, n) + x.shape[1:], x.dtype), obs0)
    zeros = jnp.zeros((steps, n))
    traj = PPOTransition(
        done=zeros.astype(bool), truncated=zeros.astype(bool),
        action=zeros.astype(jnp.int32), value=zeros, reward=zeros, log_prob=zeros,
        obs=obs, next_obs=obs, info={},
    )
    learn = sebulba_ppo.get_learn_step(
        actor.apply, critic.apply, (optim.update, optim.update), config, mesh
    )
    scopes["sebulba_learner"] = _scope_components(
        learn.lower(state, traj).compile().as_text()
    )
    act_fn = sebulba_ppo.get_act_fn(actor.apply, critic.apply, False)
    lowered = act_fn.lower((params, state.obs_stats), obs0, jax.random.PRNGKey(2))
    scopes["sebulba_act_fn"] = _scope_components(lowered.compile().as_text())
    assert "jit(act_fn)" in lowered.as_text(debug_info=True)  # the program's name stays
    return scopes


@pytest.mark.parametrize(
    "program,scope",
    # (the token policies' scopes: tests/test_lm_ppo.py, tests/test_sdar_ppo.py and
    # tests/test_lfm2_ppo.py, tests/test_kanana2_ppo.py, tests/test_mellum2_ppo.py, each on
    # its own learner)
    [("anakin_learner", key) for key in sorted(SCOPES)
     if key not in BLOCK_SCOPES + DELTA_SCOPES + DIFFUSION_SCOPES + HYBRID_SCOPES + LATENT_SCOPES
     + PROMPT_SCOPES + WINDOW_SCOPES]
    + [("sebulba_learner", key)
       for key in ("gae", "update_epoch", "update_minibatch", "minibatch_shuffle")]
    + [("sebulba_act_fn", "rollout_policy")],
)
def test_compiled_programs_carry_the_scope_names(program_scopes, program, scope):
    """`annotate` scopes from the ONE name table, in both systems: rollout
    (rollout_policy, rollout_env), gae, the epoch/minibatch pair and the
    minibatch shuffle inside the epoch."""
    assert SCOPES[scope] in program_scopes[program], sorted(program_scopes[program])[:40]


def test_scope_names_do_not_collide_with_module_or_jit_names(program_scopes):
    """A scope name that was also a flax module's or a jitted helper's name
    would count that module's ops under the scope. Components that are not
    scopes: module names (torso, Dense_0, ...), jit(...) and primitives."""
    from stoix_tpu.networks import base, heads, torso

    taken = {name for module in (base, heads, torso) for name in dir(module)}
    taken |= {"torso", "action_head", "critic_head", "input_layer"}
    assert not (set(SCOPES.values()) & {t for t in taken} - {""})
    primitives = {c for s in program_scopes.values() for c in s if c in jax.lax.__dict__}
    assert not (set(SCOPES.values()) & primitives), set(SCOPES.values()) & primitives


class _AssertingPool:
    """A CartPole pool that refuses a device array: what the actor hands to
    `envs.step` inside its `env_step` timer must already be on the host."""

    takes_host_actions = True

    def __init__(self, pool, lifetime, steps):
        self._pool, self._lifetime, self._left = pool, lifetime, steps
        self.seen = []

    def __getattr__(self, name):
        return getattr(self._pool, name)

    def step(self, action):
        self.seen.append(type(action))
        assert isinstance(action, np.ndarray), type(action)
        self._left -= 1
        if self._left <= 0:
            self._lifetime.stop()
        return self._pool.step(action)


def test_actor_env_step_timer_is_given_host_actions(devices):
    """`inference` ends once the action is on the host and `env_step` times
    the pool alone: a stub pool sees numpy, never a jax Array — and the
    payload it sends is tagged with the params version it acted with."""
    import functools

    from stoix_tpu.envs.factory import make_factory
    from stoix_tpu.sebulba import actor_critic, runner
    from stoix_tpu.sebulba.core import ParameterServer, ThreadLifetime
    from stoix_tpu.sebulba.sources import OnPolicySource, SourceContext
    from stoix_tpu.utils.timing import TimingTracker

    config = _sebulba_config()
    config.arch.actor.envs_per_actor = 8
    actor, critic, params, obs0 = _sebulba_networks(config)
    device = jax.devices()[0]
    lifetime = ThreadLifetime()
    factory = make_factory(config)
    pools = []

    def stub_factory(num_envs):
        pools.append(_AssertingPool(factory(num_envs), lifetime, steps=8))
        return pools[-1]

    mesh = jax.sharding.Mesh(np.asarray([device]), ("data",))
    source = OnPolicySource(SourceContext(1, [device], mesh, None, None, None, 64))
    pipeline = source.pipeline
    server = ParameterServer([device], 1)
    server.distribute_params((params, None))
    timer, sink = TimingTracker(), __import__("queue").Queue()
    runner._rollout_body(
        0, device, stub_factory,
        functools.partial(actor_critic.get_act_fn, actor.apply, critic.apply, False),
        actor_critic.transition, source, server, [device], lifetime, 7, sink, 8, 8, timer,
    )
    assert len(pools[0].seen) == 8 and set(pools[0].seen) == {np.ndarray}
    (version, payload), = pipeline.collect_rollouts(timeout=5.0)
    assert version == 1 and payload.action[0].shape == (8, 8)
    means = timer.all_means(prefix="actor0_")
    assert {"actor0_rollout_time", "actor0_inference_time", "actor0_env_step_time"} <= set(means)
    # Rolling means per STEP over whole rollouts: together they cannot
    # exceed the rollout's own seconds a step.
    per_step = means["actor0_rollout_time"] / 8
    assert means["actor0_inference_time"] + means["actor0_env_step_time"] <= per_step * 1.001


@pytest.mark.parametrize("pushes_since", [0, 1, 3])
def test_policy_lag_is_newest_version_minus_the_rollouts(devices, pushes_since):
    """0 for a rollout consumed at the version it was made with, k after k
    more pushes; every observation lands in the histogram."""
    from stoix_tpu.sebulba.core import ParameterServer

    server = ParameterServer([jax.devices()[0]], 1)
    hist = obs.get_registry().histogram("stoix_tpu_sebulba_policy_lag_updates")
    before = hist.summary()
    server.distribute_params({"w": jnp.ones(2)})
    acted_with = server.get_params_versioned(0).version
    for _ in range(pushes_since):
        server.distribute_params({"w": jnp.ones(2)})
    assert server.observe_policy_lag(acted_with) == pushes_since
    after = hist.summary()
    assert after["count"] - before["count"] == 1
    assert after["sum"] - before["sum"] == pushes_since


@pytest.fixture(scope="module")
def traced_sebulba_run(devices, tmp_path_factory):
    """One tiny Sebulba PPO run with telemetry on: the recorder's events and
    thread names, the MISC timings, LAST_RUN_STATS and the lag histogram's
    movement."""
    from stoix_tpu.systems.ppo.sebulba import ff_ppo
    from stoix_tpu.utils.logger import LogEvent, StoixLogger

    hist = obs.get_registry().histogram("stoix_tpu_sebulba_policy_lag_updates")
    before = hist.summary()
    misc = {}
    original = StoixLogger.log

    def log(self, metrics, t, t_eval, event):
        if event == LogEvent.MISC:
            misc.update(metrics)
        return original(self, metrics, t, t_eval, event)

    StoixLogger.log = log
    try:
        base = tmp_path_factory.mktemp("sebulba_telemetry")
        ff_ppo.run_experiment(_sebulba_config(
            ["logger.telemetry.enabled=True", f"logger.base_exp_path={base}/results"]
        ))
    finally:
        StoixLogger.log = original
    recorder = obs.get_recorder()
    after = hist.summary()
    return {
        "events": recorder.events(), "threads": recorder.thread_names(), "misc": misc,
        "stats": dict(ff_ppo.LAST_RUN_STATS),
        "lag": (after["count"] - before["count"], after["sum"] - before["sum"]),
    }


@pytest.mark.parametrize(
    "thread_prefix,span_name",
    [("actor-", name) for name in obs.HOST_SPANS["sebulba_actor"]]
    + [("MainThread", name) for name in obs.HOST_SPANS["sebulba_learner"]]
    + [("async-evaluator", name) for name in obs.HOST_SPANS["sebulba_evaluator"]],
)
def test_every_sebulba_thread_marks_its_work_with_spans(
    traced_sebulba_run, thread_prefix, span_name
):
    threads = traced_sebulba_run["threads"]
    on = {
        threads[e["tid"]] for e in traced_sebulba_run["events"] if e["name"] == span_name
    }
    assert on and all(name.startswith(thread_prefix) for name in on), (span_name, on)


def test_sebulba_run_gauges_lag_splits_the_actor_step_and_setup(traced_sebulba_run):
    run = traced_sebulba_run
    # Two actors, six updates: twelve rollouts consumed, each observed once;
    # the skip-fetch pipelining keeps the mean lag between 0 and 2 updates.
    count, total = run["lag"]
    assert count == 12 and 0.0 <= total / count <= 2.0, run["lag"]
    for actor in (0, 1):
        step = run["misc"][f"actor{actor}_rollout_time"] / 8
        split = (run["misc"][f"actor{actor}_inference_time"]
                 + run["misc"][f"actor{actor}_env_step_time"])
        assert 0.0 < split <= step * 1.001, (split, step)
    setup = run["stats"]["setup_phases"]
    assert set(setup) == {
        "mesh_build", "env_build", "network_init", "learner_setup", "evaluator_setup",
        "logger_build", "first_tick", "unspanned",
    }
    assert all(seconds > 0.0 for seconds in setup.values()), setup
    gauge = obs.get_registry().gauge("stoix_tpu_setup_phase_seconds")
    assert gauge.value({"phase": "first_tick"}) == pytest.approx(setup["first_tick"], abs=1e-5)
