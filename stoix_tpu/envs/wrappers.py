"""Core wrapper stack.

Reimplements the behavior of the reference's wrapper composition
(reference stoix/utils/make_env.py:29-61 `apply_core_wrappers`):

    env -> EpisodeStepLimit? -> RecordEpisodeMetrics
        -> { OptimisticResetVmapWrapper | AutoReset/CachedAutoReset -> Vmap }

with `next_obs_in_extras=True` semantics: `timestep.extras["next_obs"]` is always
the *true* successor observation (pre-auto-reset) so learners can bootstrap
correctly at truncations (reference ff_ppo.py:110-116).

All wrappers are pure-functional and shape-static: auto-reset uses `jnp.where`
selection over a freshly computed (or cached) reset state rather than host
branching, which keeps the whole rollout a single fused XLA program.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from stoix_tpu.envs.core import Action, Environment, State, Wrapper
from stoix_tpu.envs.types import StepType, TimeStep, _bcast


def _ensure_truncation(ts: TimeStep) -> None:
    """Guarantee the well-known extras["truncation"] key so the extras pytree
    contract is identical for reset/step across every env.

    The default is DERIVED from the timestep (LAST + discount > 0 is the
    truncation convention, types.py) rather than constant zeros: a constant
    is unvarying under shard_map's varying-manual-axes typing and would
    poison every scan carry it enters (check_vma would reject the learner)."""
    ts.extras["truncation"] = ts.extras.get(
        "truncation", jnp.logical_and(ts.last(), ts.discount > 0)
    )


class StepLimitState(NamedTuple):
    inner: Any
    step_count: jax.Array


class EpisodeStepLimit(Wrapper):
    """Truncates episodes at `max_steps`: step_type LAST, discount kept at 1."""

    def __init__(self, env: Environment, max_steps: int):
        super().__init__(env)
        self._max_steps = int(max_steps)

    def reset(self, key: jax.Array) -> Tuple[State, TimeStep]:
        return self._wrap_reset(*self._env.reset(key))

    def reset_to_level(self, level: Any, key: jax.Array) -> Tuple[State, TimeStep]:
        return self._wrap_reset(*self._env.reset_to_level(level, key))

    def _wrap_reset(self, state: State, ts: TimeStep) -> Tuple[State, TimeStep]:
        _ensure_truncation(ts)
        return StepLimitState(state, jnp.zeros((), jnp.int32)), ts

    def step(self, state: StepLimitState, action: Action) -> Tuple[State, TimeStep]:
        inner, ts = self._env.step(state.inner, action)
        count = state.step_count + 1
        truncate = jnp.logical_and(count >= self._max_steps, ~ts.last())
        ts = ts._replace(
            step_type=jnp.where(truncate, StepType.LAST, ts.step_type),
            # discount stays 1 on truncation — this is the whole point.
        )
        inner_trunc = ts.extras.get("truncation", jnp.zeros((), bool))
        ts.extras["truncation"] = jnp.logical_or(truncate, inner_trunc)
        return StepLimitState(inner, count), ts


class EpisodeMetricsState(NamedTuple):
    inner: Any
    episode_return: jax.Array
    episode_length: jax.Array
    # Running totals frozen at episode end, so LAST steps report full episodes.


class RecordEpisodeMetrics(Wrapper):
    """Accumulates per-episode return/length into extras["episode_metrics"]."""

    def reset(self, key: jax.Array) -> Tuple[State, TimeStep]:
        return self._wrap_reset(*self._env.reset(key))

    def reset_to_level(self, level: Any, key: jax.Array) -> Tuple[State, TimeStep]:
        return self._wrap_reset(*self._env.reset_to_level(level, key))

    def _wrap_reset(self, state: State, ts: TimeStep) -> Tuple[State, TimeStep]:
        zero = jnp.zeros((), jnp.float32)
        ts.extras["episode_metrics"] = {
            "episode_return": zero,
            "episode_length": jnp.zeros((), jnp.int32),
            "is_terminal_step": jnp.zeros((), bool),
        }
        _ensure_truncation(ts)
        return EpisodeMetricsState(state, zero, jnp.zeros((), jnp.int32)), ts

    def step(self, state: EpisodeMetricsState, action: Action) -> Tuple[State, TimeStep]:
        inner, ts = self._env.step(state.inner, action)
        ep_return = state.episode_return + ts.reward
        ep_length = state.episode_length + 1
        done = ts.last()
        ts.extras["episode_metrics"] = {
            "episode_return": ep_return,
            "episode_length": ep_length,
            "is_terminal_step": done,
        }
        _ensure_truncation(ts)
        # Reset accumulators after a terminal step (auto-reset follows above us).
        next_state = EpisodeMetricsState(
            inner,
            jnp.where(done, 0.0, ep_return),
            jnp.where(done, 0, ep_length),
        )
        return next_state, ts


class AutoResetState(NamedTuple):
    inner: Any
    key: jax.Array


class AutoResetWrapper(Wrapper):
    """Resets the env within `step` when an episode ends.

    The returned timestep keeps the terminal step_type/reward/discount but its
    `observation` becomes the first observation of the new episode, while
    `extras["next_obs"]` carries the true terminal observation for bootstrapping.
    """

    def __init__(self, env: Environment, next_obs_in_extras: bool = True):
        super().__init__(env)
        self._next_obs_in_extras = next_obs_in_extras

    def reset(self, key: jax.Array) -> Tuple[State, TimeStep]:
        key, inner_key = jax.random.split(key)
        inner, ts = self._env.reset(inner_key)
        if self._next_obs_in_extras:
            ts.extras["next_obs"] = ts.observation
        return AutoResetState(inner, key), ts

    def step(self, state: AutoResetState, action: Action) -> Tuple[State, TimeStep]:
        inner, ts = self._env.step(state.inner, action)
        key, reset_key = jax.random.split(state.key)
        reset_state, reset_ts = self._env.reset(reset_key)
        done = ts.last()
        next_inner = jax.tree.map(lambda a, b: jnp.where(_bcast(done, a), a, b), reset_state, inner)
        new_obs = jax.tree.map(lambda a, b: jnp.where(_bcast(done, a), a, b), reset_ts.observation, ts.observation)
        extras = dict(ts.extras)
        if self._next_obs_in_extras:
            extras["next_obs"] = ts.observation
        ts = ts._replace(observation=new_obs, extras=extras)
        return AutoResetState(next_inner, key), ts


def unwrapped_state(state: Any) -> Any:
    """The wrapped env's own state inside any stack of these wrappers' (each
    keeps the state it wraps in an `inner` field; a wrapper that adds no
    state passes it through), one episode's or a batch of them."""
    while hasattr(state, "inner"):
        state = state.inner
    return state


def _reseed(state: Any, key: jax.Array) -> Any:
    """Replace `key` fields in a (nested) NamedTuple env state with fresh keys.

    Env states follow the convention of carrying their PRNG key in a `key` field
    and their wrapped state in an `inner` field; re-seeding on cached-reset
    replay keeps episode randomness fresh even though the initial physics state
    is frozen.
    """
    if hasattr(state, "_fields"):
        updates = {}
        if "key" in state._fields:
            key, sub = jax.random.split(key)
            updates["key"] = sub
        if "inner" in state._fields:
            updates["inner"] = _reseed(state.inner, key)
        if updates:
            return state._replace(**updates)
    return state


class CachedAutoResetState(NamedTuple):
    inner: Any
    cached_state: Any
    cached_obs: Any
    key: jax.Array


class CachedAutoResetWrapper(Wrapper):
    """Auto-reset that replays the episode-initial state instead of re-running
    `reset` every step (reference make_env.py:48-52's CachedAutoResetWrapper).
    Valid for envs whose reset distribution the caller is happy to freeze per
    environment instance; saves the full reset computation in the hot loop.
    PRNG `key` fields in the cached state are re-seeded on replay so episode
    randomness stays fresh.
    """

    def __init__(self, env: Environment, next_obs_in_extras: bool = True):
        super().__init__(env)
        self._next_obs_in_extras = next_obs_in_extras

    def reset(self, key: jax.Array) -> Tuple[State, TimeStep]:
        key, inner_key = jax.random.split(key)
        inner, ts = self._env.reset(inner_key)
        if self._next_obs_in_extras:
            ts.extras["next_obs"] = ts.observation
        return CachedAutoResetState(inner, inner, ts.observation, key), ts

    def step(self, state: CachedAutoResetState, action: Action) -> Tuple[State, TimeStep]:
        inner, ts = self._env.step(state.inner, action)
        done = ts.last()
        key, reseed_key = jax.random.split(state.key)
        replay_state = _reseed(state.cached_state, reseed_key)
        next_inner = jax.tree.map(
            lambda cached, cur: jnp.where(_bcast(done, cached), cached, cur), replay_state, inner
        )
        new_obs = jax.tree.map(
            lambda cached, cur: jnp.where(_bcast(done, cached), cached, cur), state.cached_obs, ts.observation
        )
        extras = dict(ts.extras)
        if self._next_obs_in_extras:
            extras["next_obs"] = ts.observation
        ts = ts._replace(observation=new_obs, extras=extras)
        return CachedAutoResetState(next_inner, state.cached_state, state.cached_obs, key), ts


class FlattenObservationWrapper(Wrapper):
    """Flatten a structured (grid/pixel) agent_view to 1-D so MLP torsos can
    consume it — the reference pairs its MLP networks with grid envs via
    `stoa.FlattenObservationWrapper` (reference configs/env/jumanji/snake.yaml
    `wrapper: _target_: stoa.FlattenObservationWrapper`). Applied to the raw
    env, below the core stack, so `extras["next_obs"]` is flattened too."""

    def __init__(self, env: Environment):
        super().__init__(env)
        spec = env.observation_space().agent_view
        self._feature_rank = len(spec.shape)
        self._flat_dim = 1
        for d in spec.shape:
            self._flat_dim *= int(d)

    def _flatten(self, ts: TimeStep) -> TimeStep:
        view = ts.observation.agent_view
        shape = view.shape[: view.ndim - self._feature_rank] + (self._flat_dim,)
        return ts._replace(
            observation=ts.observation._replace(agent_view=view.reshape(shape))
        )

    def reset(self, key: jax.Array) -> Tuple[State, TimeStep]:
        state, ts = self._env.reset(key)
        return state, self._flatten(ts)

    def step(self, state: State, action: Action) -> Tuple[State, TimeStep]:
        state, ts = self._env.step(state, action)
        return state, self._flatten(ts)

    def observation_space(self) -> Any:
        import dataclasses

        obs = self._env.observation_space()
        return obs._replace(
            agent_view=dataclasses.replace(obs.agent_view, shape=(self._flat_dim,))
        )


class StartFlagPrevActionState(NamedTuple):
    inner: Any
    prev_action: jax.Array


class StartFlagPrevActionWrapper(Wrapper):
    """Append an episode-start flag and the previous action to a flat
    agent_view — the reference applies stoa's AddStartFlagAndPrevAction to
    POPJym POMDP envs (reference make_env.py:369-370) so memory models can
    condition on action history.

    Discrete actions append one-hot(prev_action); Box actions append the raw
    action vector. At reset (and on the first step after it) the start flag is
    1 and the previous action is zeros. Requires a 1-D agent_view — flatten
    structured observations first.
    """

    def __init__(self, env: Environment):
        super().__init__(env)
        space = env.action_space()
        from stoix_tpu.envs import spaces as _spaces

        self._discrete = isinstance(space, _spaces.Discrete)
        self._act_dim = (
            int(space.num_values) if self._discrete else int(space.shape[-1])
        )
        view = env.observation_space().agent_view
        if len(view.shape) != 1:
            raise ValueError(
                "StartFlagPrevActionWrapper needs a flat agent_view; apply "
                f"FlattenObservationWrapper first (got shape {view.shape})"
            )
        self._base_dim = int(view.shape[0])

    def _zero_action(self) -> jax.Array:
        if self._discrete:
            # -1 one-hot-encodes to all-zeros: "no previous action" is
            # distinguishable from "previous action was 0".
            return jnp.full((), -1, jnp.int32)
        return jnp.zeros((self._act_dim,), jnp.float32)

    def _augment(self, ts: TimeStep, start: jax.Array, prev_action: jax.Array) -> TimeStep:
        if self._discrete:
            act_feat = jax.nn.one_hot(prev_action, self._act_dim, dtype=jnp.float32)
        else:
            act_feat = jnp.asarray(prev_action, jnp.float32)
        view = jnp.concatenate(
            [ts.observation.agent_view, start[None].astype(jnp.float32), act_feat]
        )
        return ts._replace(observation=ts.observation._replace(agent_view=view))

    def reset(self, key: jax.Array) -> Tuple[State, TimeStep]:
        state, ts = self._env.reset(key)
        prev = self._zero_action()
        return (
            StartFlagPrevActionState(state, prev),
            self._augment(ts, jnp.ones((), jnp.float32), prev),
        )

    def step(self, state: StartFlagPrevActionState, action: Action) -> Tuple[State, TimeStep]:
        inner, ts = self._env.step(state.inner, action)
        return (
            StartFlagPrevActionState(inner, action),
            self._augment(ts, jnp.zeros((), jnp.float32), action),
        )

    def observation_space(self) -> Any:
        import dataclasses

        obs = self._env.observation_space()
        return obs._replace(
            agent_view=dataclasses.replace(
                obs.agent_view, shape=(self._base_dim + 1 + self._act_dim,)
            )
        )


class VmapWrapper(Wrapper):
    """Vectorizes reset/step over a leading batch of keys/states/actions."""

    def reset(self, keys: jax.Array) -> Tuple[State, TimeStep]:
        return jax.vmap(self._env.reset)(keys)

    def step(self, state: State, action: Action) -> Tuple[State, TimeStep]:
        return jax.vmap(self._env.step)(state, action)


class OptimisticResetState(NamedTuple):
    inner: Any
    key: jax.Array


class OptimisticResetVmapWrapper(Wrapper):
    """Vmapped auto-reset that amortizes reset cost (reference make_env.py:48-61,
    pattern from JaxUED/Craftax): per step only `num_envs / reset_ratio` reset
    states are computed; each done env optimistically grabs one (collisions share
    a reset state, which is statistically fine and much cheaper for expensive
    resets). Behaves like Vmap(AutoReset(env)) with reset_ratio == 1.
    """

    def __init__(self, env: Environment, num_envs: int, reset_ratio: int = 16, next_obs_in_extras: bool = True):
        super().__init__(env)
        if num_envs % reset_ratio != 0:
            raise ValueError(
                f"num_envs ({num_envs}) must be divisible by reset_ratio ({reset_ratio}); "
                "a silent fallback would defeat the amortization this wrapper exists for."
            )
        self._num_envs = int(num_envs)
        self._num_resets = max(1, int(num_envs) // int(reset_ratio))
        self._next_obs_in_extras = next_obs_in_extras

    def reset(self, keys: jax.Array) -> Tuple[State, TimeStep]:
        # keys: [num_envs, 2]; split so wrapper-carried keys never alias the
        # keys handed to the inner env.
        carry_and_env = jax.vmap(jax.random.split)(keys)
        inner, ts = jax.vmap(self._env.reset)(carry_and_env[:, 1])
        if self._next_obs_in_extras:
            ts.extras["next_obs"] = ts.observation
        return OptimisticResetState(inner, carry_and_env[:, 0]), ts

    def step(self, state: OptimisticResetState, action: Action) -> Tuple[State, TimeStep]:
        inner, ts = jax.vmap(self._env.step)(state.inner, action)
        split = jax.vmap(jax.random.split)(state.key)  # [num_envs, 2, key]
        keys, reset_keys = split[:, 0], split[: self._num_resets, 1]
        reset_state, reset_ts = jax.vmap(self._env.reset)(reset_keys)

        # Each env i is assigned reset slot i % num_resets.
        idx = jnp.arange(self._num_envs) % self._num_resets
        gathered_state = jax.tree.map(lambda x: x[idx], reset_state)
        gathered_obs = jax.tree.map(lambda x: x[idx], reset_ts.observation)

        done = ts.last()
        next_inner = jax.tree.map(lambda a, b: jnp.where(_bcast(done, a), a, b), gathered_state, inner)
        new_obs = jax.tree.map(lambda a, b: jnp.where(_bcast(done, a), a, b), gathered_obs, ts.observation)
        extras = dict(ts.extras)
        if self._next_obs_in_extras:
            extras["next_obs"] = ts.observation
        ts = ts._replace(observation=new_obs, extras=extras)
        return OptimisticResetState(next_inner, keys), ts


def apply_core_wrappers(
    env: Environment,
    num_envs: int,
    *,
    max_episode_steps: Optional[int] = None,
    use_optimistic_reset: bool = False,
    reset_ratio: int = 16,
    use_cached_auto_reset: bool = False,
) -> Environment:
    """The canonical wrapper composition (reference make_env.py:29-61)."""
    if max_episode_steps is not None and max_episode_steps > 0:
        env = EpisodeStepLimit(env, max_episode_steps)
    env = RecordEpisodeMetrics(env)
    if use_optimistic_reset:
        env = OptimisticResetVmapWrapper(env, num_envs=num_envs, reset_ratio=reset_ratio)
    else:
        env = CachedAutoResetWrapper(env) if use_cached_auto_reset else AutoResetWrapper(env)
        env = VmapWrapper(env)
    return env


def chained_wrappers(env: Environment, wrappers: list) -> Environment:
    """Compose a list of wrapper constructors (reference stoix/wrappers/base.py:
    6-15): each entry is a callable taking the env (use functools.partial or
    config _partial_ instantiation for extra kwargs)."""
    for ctor in wrappers:
        env = ctor(env)
    return env
