"""A Sebulba actor stores a rollout once, where it is read
(stoix_tpu/sebulba/rollout_storage.py, docs/DESIGN.md §3): what the
pipeline carries is, leaf by leaf and bit for bit, what the stack / split /
device_put of the per-step leaves built before, for a host pool and for a
pure-JAX env twin, for one and two learner devices; the two sets of host
rows never alias a payload in flight; nothing compiles after the second
rollout."""

import functools
import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu.base_types import ActorCriticParams, PPOTransition
from stoix_tpu.envs.factory import make_factory
from stoix_tpu.envs.types import Observation, TimeStep
from stoix_tpu.sebulba import actor_critic, runner
from stoix_tpu.sebulba.core import ParameterServer, ThreadLifetime
from stoix_tpu.sebulba.sources import OnPolicySource, SourceContext
from stoix_tpu.systems.ppo.sebulba import ff_ppo
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.timing import TimingTracker

ENVS, LENGTH, SEED = 16, 8, 11


def _config(backend):
    return config_lib.compose(
        config_lib.default_config_dir(), "default/sebulba/default_ff_ppo.yaml",
        ["env=cartpole", f"env.backend={backend}", f"arch.total_num_envs={ENVS}",
         "arch.actor.device_ids=[0]", "arch.actor.actor_per_device=1",
         "arch.learner.device_ids=[0]", "arch.evaluator_device_id=0",
         f"system.rollout_length={LENGTH}", "logger.use_console=False"],
    )


class _StubPool:
    """A host pool with CartPole's shapes whose outputs are drawn from a
    seeded generator — terminations, truncations, and a float64 reward, which
    the payload carries as float32 as `jnp.stack` made it — and differ at
    every step. Fresh arrays every step, as the C++ pool returns copies."""

    takes_host_actions = True
    num_actions = 2

    def __init__(self, num_envs, seed):
        self._n, self._rng = num_envs, np.random.default_rng(seed)

    def _observation(self):
        return Observation(
            agent_view=self._rng.standard_normal((self._n, 4)).astype(np.float32),
            action_mask=np.ones((self._n, 2), np.float32),
            step_count=self._rng.integers(0, 500, self._n).astype(np.int32),
        )

    def _timestep(self, first):
        done = self._rng.random(self._n) < 0.25
        truncated = ~done & (self._rng.random(self._n) < 0.25)
        last = done | truncated
        return TimeStep(
            step_type=(
                np.zeros(self._n, np.int8) if first else np.where(last, np.int8(2), np.int8(1))
            ),
            reward=self._rng.random(self._n),
            discount=np.where(done, 0.0, 1.0).astype(np.float32),
            observation=self._observation(),
            extras={
                "next_obs": self._observation(),
                "truncation": truncated,
                "episode_metrics": {
                    "episode_return": self._rng.random(self._n).astype(np.float32),
                    "episode_length": self._rng.integers(0, 500, self._n).astype(np.int32),
                    "is_terminal_step": last,
                },
            },
        )

    def reset(self, *, seed=None):
        return self._timestep(first=True)

    def step(self, action):
        assert isinstance(action, np.ndarray), type(action)
        return self._timestep(first=False)


class _Recording:
    """Keeps every timestep an env returned, and ends the actor's loop after
    `rollouts` whole rollouts."""

    def __init__(self, env, lifetime, rollouts):
        self._env, self._lifetime, self._left = env, lifetime, rollouts * LENGTH
        self.timesteps = []

    def __getattr__(self, name):
        return getattr(self._env, name)

    def reset(self, *, seed=None):
        self.timesteps.append(self._env.reset(seed=seed))
        return self.timesteps[-1]

    def step(self, action):
        self.timesteps.append(self._env.step(action))
        self._left -= 1
        if self._left <= 0:
            self._lifetime.stop()
        return self.timesteps[-1]


class _EveryRolloutParams:
    """A parameter source that never blocks: the same bundle, a version up
    at every fetch."""

    def __init__(self, bundle):
        self._bundle, self.version = bundle, 0

    def get_params_versioned(self, actor_id, timeout=None):
        self.version += 1
        return self.version, self._bundle


class _ListPipeline:
    """Keeps what the actor sends, with the number of backend compilations
    the process had made by then."""

    def __init__(self, compilations):
        self.sent, self.compilations_at_send, self._compilations = [], [], compilations

    def send_rollout(self, actor_id, tagged, timeout=None):
        self.sent.append(tagged)
        self.compilations_at_send.append(len(self._compilations))


def _networks(config):
    pool = make_factory(config)(1)
    config.system.action_dim = pool.num_actions
    actor, critic = ff_ppo._build_networks(config, pool.num_actions, None, env=pool)
    obs0 = jax.tree.map(jnp.asarray, pool.reset(seed=0).observation)
    key = jax.random.PRNGKey(0)
    return actor, critic, ActorCriticParams(actor.init(key, obs0), critic.init(key, obs0))


def _parent_payloads(timesteps, actor, critic, bundle, actor_device, learner_devices):
    """What the actor built before PR 28, replayed from the recorded env
    outputs with the actor's own key stream: a list of per-step transitions,
    `truncated` by an eager `jnp.logical_and`, then `jnp.stack`, `jnp.split`
    along the env axis and a `device_put` a slice."""
    act_fn = ff_ppo.get_act_fn(actor.apply, critic.apply, False)
    payloads, traj = [], []
    with jax.default_device(actor_device):
        key = jax.random.PRNGKey(SEED)
        timestep = timesteps[0]
        for next_timestep in timesteps[1:]:
            key, act_key = jax.random.split(key)
            obs_local = jax.device_put(timestep.observation, actor_device)
            action, log_prob, value = act_fn(bundle, obs_local, act_key)
            traj.append(PPOTransition(
                done=next_timestep.discount == 0.0,
                truncated=jnp.logical_and(next_timestep.last(), next_timestep.discount != 0.0),
                action=action, value=value, reward=next_timestep.reward, log_prob=log_prob,
                obs=obs_local, next_obs=next_timestep.extras["next_obs"],
                info=next_timestep.extras["episode_metrics"],
            ))
            timestep = next_timestep
            if len(traj) == LENGTH:
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *traj)
                payloads.append(jax.tree.map(
                    lambda x: [
                        jax.device_put(s, d)
                        for s, d in zip(jnp.split(x, len(learner_devices), axis=1), learner_devices)
                    ],
                    stacked,
                ))
                traj = []
    return payloads


def _run_actor(backend, n_learners, rollouts, pipeline=None):
    """`rollouts` rollouts of one actor on device 3 for learners on devices
    1..n, through `_rollout_body` itself: one rollout with the real parameter
    server, more with a source that does not wait for a learner."""
    devices = jax.devices()
    actor_device, learner_devices = devices[3], devices[1:1 + n_learners]
    config = _config(backend)
    actor, critic, params = _networks(config)
    lifetime, sink, timer = ThreadLifetime(), queue.Queue(), TimingTracker()
    recorded = []

    def factory(num_envs):
        env = _StubPool(num_envs, SEED) if backend == "cvec" else make_factory(config)(num_envs)
        recorded.append(_Recording(env, lifetime, rollouts))
        return recorded[-1]

    mesh = jax.sharding.Mesh(np.asarray(learner_devices), ("data",))
    source = OnPolicySource(
        SourceContext(1, learner_devices, mesh, None, None, None, ENVS * LENGTH)
    )
    if pipeline is not None:
        source.pipeline = pipeline
    bundle = jax.device_put((params, None), actor_device)
    if rollouts == 1:
        params_source = ParameterServer([actor_device], 1)
        params_source.distribute_params((params, None))
    else:
        params_source = _EveryRolloutParams(bundle)
    make_act_fn = functools.partial(ff_ppo.get_act_fn, actor.apply, critic.apply, False)
    runner._rollout_body(
        0, actor_device, factory, make_act_fn, actor_critic.transition, source, params_source,
        learner_devices, lifetime, SEED, sink, ENVS, LENGTH, timer,
    )
    expected = _parent_payloads(
        recorded[0].timesteps, actor, critic, bundle, actor_device, learner_devices
    )
    return {
        "pipeline": source.pipeline, "sink": sink, "expected": expected,
        "learner_devices": learner_devices, "timesteps": recorded[0].timesteps,
    }


def _assert_same_payload(payload, expected, learner_devices, lead=None):
    lead = lead or (LENGTH, ENVS // len(learner_devices))
    is_shards = lambda x: isinstance(x, list)  # noqa: E731
    got_leaves, got_def = jax.tree.flatten(payload, is_leaf=is_shards)
    want_leaves, want_def = jax.tree.flatten(expected, is_leaf=is_shards)
    assert got_def == want_def
    for path, got, want in zip(
        [p for p, _ in jax.tree_util.tree_flatten_with_path(expected, is_leaf=is_shards)[0]],
        got_leaves, want_leaves,
    ):
        assert len(got) == len(want) == len(learner_devices), path
        for g, w, device in zip(got, want, learner_devices):
            assert isinstance(g, jax.Array) and g.devices() == w.devices() == {device}, path
            assert (g.shape, g.dtype) == (w.shape, w.dtype), (path, g.shape, g.dtype)
            assert g.shape[:len(lead)] == lead, path
            assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), path


@pytest.fixture(scope="module", params=[("cvec", 1), ("cvec", 2), ("jax", 1), ("jax", 2)],
                ids=lambda p: f"{'host_pool' if p[0] == 'cvec' else 'jax_twin'}-{p[1]}_learner")
def one_rollout(request, devices):
    backend, n_learners = request.param
    return {**_run_actor(backend, n_learners, rollouts=1), "backend": backend}


def test_payload_is_the_parents_stack_split_device_put_bit_for_bit(one_rollout):
    (version, payload), = one_rollout["pipeline"].collect_rollouts(timeout=5.0)
    assert version == 1
    _assert_same_payload(payload, one_rollout["expected"][0], one_rollout["learner_devices"])


def test_truncated_is_last_and_not_terminated_wherever_the_env_lives(one_rollout):
    """The operator form keeps a host pool's flags on the host and equals the
    eager `jnp.logical_and` it replaced; the stub pool draws both kinds of
    episode end."""
    steps = one_rollout["timesteps"][1:]
    for step in steps:
        ours = step.last() & (step.discount != 0.0)
        assert type(ours) is type(step.discount)  # numpy stays numpy, jax stays jax
        parents = jnp.logical_and(step.last(), step.discount != 0.0)
        assert np.array_equal(np.asarray(ours), np.asarray(parents))
    if one_rollout["backend"] == "cvec":
        truncated = np.stack([s.last() & (s.discount != 0.0) for s in steps])
        done = np.stack([s.discount == 0.0 for s in steps])
        assert truncated.any() and done.any() and not (truncated & done).any()


def test_episode_metrics_in_the_sink_are_the_envs_own(one_rollout):
    message = one_rollout["sink"].get_nowait()
    steps = one_rollout["timesteps"][1:]
    for name, got in message["episode_metrics"].items():
        want = np.stack([np.asarray(s.extras["episode_metrics"][name]) for s in steps])
        assert isinstance(got, np.ndarray) and got.shape == (LENGTH, ENVS), name
        assert got.dtype == want.dtype and np.array_equal(got, want), name
    assert "actor0_prepare_data_time" in message["timings"]


@pytest.fixture(scope="module")
def three_rollouts(devices):
    """Three rollouts of one actor over a host pool whose outputs differ at
    every step, checked only after all three were written."""
    import jax.monitoring
    from jax._src import monitoring

    compilations = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compilations.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        pipeline = _ListPipeline(compilations)
        run = _run_actor("cvec", 1, rollouts=3, pipeline=pipeline)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    return run


@pytest.mark.parametrize("k", [0, 1, 2])
def test_rollout_k_keeps_its_values_after_the_next_two_were_written(three_rollouts, k):
    """The actor alternates two sets of host rows: a payload must not share
    memory with rows that a later rollout writes (on the CPU backend
    `device_put` may take an aligned numpy buffer as the array's own)."""
    sent = three_rollouts["pipeline"].sent
    assert len(sent) == 3 and [version for version, _ in sent] == [1, 1, 2]
    _assert_same_payload(
        sent[k][1], three_rollouts["expected"][k], three_rollouts["learner_devices"]
    )
    others = [np.asarray(sent[j][1].reward[0]) for j in range(3) if j != k]
    assert all(not np.array_equal(np.asarray(sent[k][1].reward[0]), other) for other in others)


def test_nothing_compiles_after_the_second_rollout_and_the_timer_is_logged(three_rollouts):
    at_send = three_rollouts["pipeline"].compilations_at_send
    assert at_send[2] == at_send[1], at_send
    messages = [three_rollouts["sink"].get_nowait() for _ in range(3)]
    assert all("actor0_prepare_data_time" in m["timings"] for m in messages)
    # Each message holds its own rollout's episode metrics (copies, not
    # views of a set that was written again).
    returns = [m["episode_metrics"]["episode_return"] for m in messages]
    steps = three_rollouts["timesteps"][1:]
    for k, got in enumerate(returns):
        want = np.stack([s.extras["episode_metrics"]["episode_return"]
                         for s in steps[k * LENGTH:(k + 1) * LENGTH]])
        assert np.array_equal(got, want), k


def test_storage_refuses_a_short_rollout_and_a_leaf_that_changes_sides(devices):
    from stoix_tpu.sebulba.rollout_storage import RolloutStorage

    storage = RolloutStorage(2, [devices[0]])
    storage.add({"a": np.zeros(4, np.float32)})
    with pytest.raises(ValueError, match="finished after 1"):
        storage.finish()
    storage = RolloutStorage(2, [devices[0]])
    storage.add({"a": np.zeros(4, np.float32)})
    storage.add({"a": jnp.zeros(4, jnp.float32)})
    with pytest.raises(ValueError, match="at some steps"):
        storage.finish()


def _run_dqn_actor(backend, n_learners):
    """One rollout of a Sebulba DQN actor through `_rollout_body` and its
    `ReplaySource`, beside what the parent's actor built from the same env
    outputs: the per-step list stacked, `[T, E]` flattened to `[T*E]`, split
    along that axis and `device_put` a slice (the parent's three lines, kept
    here as the reference)."""
    from stoix_tpu.base_types import Transition
    from stoix_tpu.sebulba.sources import ReplaySource
    from stoix_tpu.systems.q_learning.q_family import build_q_network
    from stoix_tpu.systems.q_learning.sebulba import ff_dqn

    devices = jax.devices()
    actor_device, learner_devices = devices[3], devices[1:1 + n_learners]
    config = config_lib.compose(
        config_lib.default_config_dir(), "default/sebulba/default_ff_dqn.yaml",
        ["env=cartpole", f"env.backend={backend}", "logger.use_console=False"],
    )
    pool = make_factory(config)(1)
    q_network = build_q_network(config, pool.num_actions)
    obs0 = jax.tree.map(jnp.asarray, pool.reset(seed=0).observation)
    params = q_network.init(jax.random.PRNGKey(0), obs0)
    make_act_fn = functools.partial(ff_dqn.get_act_fn, q_network.apply, 0.3)
    lifetime, sink, recorded = ThreadLifetime(), queue.Queue(), []

    def factory(num_envs):
        env = _StubPool(num_envs, SEED) if backend == "cvec" else make_factory(config)(num_envs)
        recorded.append(_Recording(env, lifetime, 1))
        return recorded[-1]

    class _Service:
        def stats(self):
            return {}

    mesh = jax.sharding.Mesh(np.asarray(learner_devices), ("data",))
    source = ReplaySource(
        SourceContext(1, learner_devices, mesh, None, None, None, ENVS * LENGTH),
        service=_Service(), epochs=1, param_sync_interval=1,
    )
    server = ParameterServer([actor_device], 1)
    server.distribute_params(params)
    runner._rollout_body(
        0, actor_device, factory, make_act_fn, ff_dqn.transition, source, server,
        learner_devices, lifetime, SEED, sink, ENVS, LENGTH, TimingTracker(),
    )

    act_fn, traj = make_act_fn(), []
    placed = jax.device_put(params, actor_device)
    with jax.default_device(actor_device):
        key = jax.random.PRNGKey(SEED)
        timesteps = recorded[0].timesteps
        for timestep, next_timestep in zip(timesteps[:-1], timesteps[1:]):
            key, act_key = jax.random.split(key)
            obs_local = jax.device_put(timestep.observation, actor_device)
            (action,) = act_fn(placed, obs_local, act_key)
            traj.append(Transition(
                obs=obs_local, action=action, reward=next_timestep.reward,
                done=next_timestep.discount == 0.0, next_obs=next_timestep.extras["next_obs"],
                info={},
            ))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *traj)
        flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), stacked)
        expected = jax.tree.map(
            lambda x: [
                jax.device_put(s, d)
                for s, d in zip(jnp.split(x, n_learners, axis=0), learner_devices)
            ],
            flat,
        )
    return source.pipeline, expected, learner_devices, sink, timesteps[1:]


@pytest.mark.parametrize(
    "backend,n_learners", [("cvec", 1), ("cvec", 2), ("jax", 1), ("jax", 2)],
    ids=lambda p: {"cvec": "host_pool", "jax": "jax_twin"}.get(p, f"{p}_learner"),
)
def test_dqn_payload_is_the_parents_stack_reshape_split_device_put(devices, backend, n_learners):
    pipeline, expected, learner_devices, sink, steps = _run_dqn_actor(backend, n_learners)
    (actor_id, payload), = pipeline.poll(timeout=5.0)
    assert actor_id == 0 and payload.info == {}
    _assert_same_payload(
        payload, expected, learner_devices, lead=(LENGTH * ENVS // n_learners,)
    )
    # Episode metrics go to the sink, whole and in the env's order, and the
    # span-fed per-step timers with them.
    message = sink.get_nowait()
    for name, got in message["episode_metrics"].items():
        want = np.stack([np.asarray(s.extras["episode_metrics"][name]) for s in steps])
        assert got.dtype == want.dtype and np.array_equal(got.reshape(-1), want.reshape(-1)), name
    assert {"actor0_inference_time", "actor0_env_step_time", "actor0_prepare_data_time"} <= set(
        message["timings"]
    )
