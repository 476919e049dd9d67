"""Stateful environment factories — the Sebulba env seam.

Mirrors the reference's factory boundary (reference stoix/utils/env_factory.py
:23-86 and stoix/wrappers/jax_to_factory.py): Sebulba actors consume STATEFUL
envs (`envs.reset() -> TimeStep`, `envs.step(action) -> TimeStep`, numpy-ish
batched outputs), so non-JAX simulators (EnvPool Atari, Gymnasium) and pure
JAX envs sit behind one interface. Thread-safe seed allocation lets every
actor thread draw unique env instances.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp

from stoix_tpu.envs.core import Environment
from stoix_tpu.envs.types import TimeStep
from stoix_tpu.envs.wrappers import AutoResetWrapper, RecordEpisodeMetrics, VmapWrapper


class EnvFactory:
    """Abstract factory with thread-safe unique seeding."""

    def __init__(self, task_id: str, init_seed: int = 42, **kwargs: Any):
        self._task_id = task_id
        self._seed = init_seed
        self._kwargs = kwargs
        self._lock = threading.Lock()

    def __call__(self, num_envs: int) -> Any:
        raise NotImplementedError

    def _next_seed(self, num_envs: int) -> int:
        with self._lock:
            seed = self._seed
            self._seed += num_envs
        return seed


def _host_cpu_device() -> jax.Device:
    """The host CPU device Sebulba's pure-JAX env twins step on, BESIDE the
    accelerator backend. That needs the CPU backend in the process, which a
    machine-wide `JAX_PLATFORMS=tpu` removes: say so instead of letting
    `jax.devices("cpu")` fail with an unknown-backend error."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as exc:
        raise RuntimeError(
            "Sebulba steps its pure-JAX envs on the host CPU beside the "
            "accelerator, so the CPU backend must be available, but "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} excludes it. "
            "Unset JAX_PLATFORMS or list cpu too (JAX_PLATFORMS=tpu,cpu), or "
            "use a host env pool (env.backend=cvec)."
        ) from exc


class JaxToStateful:
    """Wraps a batched pure-JAX env as a stateful Sebulba env pinned to a
    device (reference stoix/wrappers/jax_to_factory.py:11-107): reset/step are
    vmapped+jitted once; state lives inside this object. Placement follows the
    data: keys, state and actions are committed to `device` with
    `jax.device_put`, and the jitted programs run where their inputs live."""

    def __init__(self, env: Environment, num_envs: int, seed: int, device: Optional[jax.Device] = None):
        self._env = VmapWrapper(AutoResetWrapper(RecordEpisodeMetrics(env)))
        self._num_envs = num_envs
        self._device = device or _host_cpu_device()
        self._state = None
        self._keys = jax.device_put(
            jax.random.split(jax.random.PRNGKey(seed), num_envs), self._device
        )
        self._reset_fn = jax.jit(self._env.reset)
        self._step_fn = jax.jit(self._env.step)

    @property
    def num_envs(self) -> int:
        return self._num_envs

    def observation_space(self):
        return self._env.observation_space()

    def action_space(self):
        return self._env.action_space()

    @property
    def num_actions(self) -> int:
        return self._env.num_actions

    def reset(self, *, seed: Optional[int] = None) -> TimeStep:
        if seed is not None:
            self._keys = jax.device_put(
                jax.random.split(jax.random.PRNGKey(seed), self._num_envs), self._device
            )
        self._state, timestep = self._reset_fn(self._keys)
        return timestep

    def step(self, action: Any) -> TimeStep:
        action = jax.device_put(jnp.asarray(action), self._device)
        self._state, timestep = self._step_fn(self._state, action)
        return timestep


class JaxEnvFactory(EnvFactory):
    """Creates JaxToStateful instances of a registered env (CPU-pinned by
    default, reference jax_to_factory.py:110-130)."""

    def __init__(self, task_id: str, init_seed: int = 42, device: Optional[jax.Device] = None, **kwargs: Any):
        super().__init__(task_id, init_seed, **kwargs)
        self._device = device or _host_cpu_device()

    def __call__(self, num_envs: int) -> JaxToStateful:
        from stoix_tpu.envs.registry import make_single

        seed = self._next_seed(num_envs)
        env = make_single(self._task_id, **self._kwargs)
        return JaxToStateful(env, num_envs, seed, self._device)


class EnvPoolFactory(EnvFactory):
    """EnvPool (C++ vectorized envs) factory — requires the optional `envpool`
    dependency (reference env_factory.py:48-68). Raises a clear error when the
    package is absent from the environment."""

    def __call__(self, num_envs: int) -> Any:
        try:
            import envpool  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "EnvPoolFactory requires the optional 'envpool' package, which "
                "is not installed in this environment. Use JaxEnvFactory, or "
                "the native CVecEnvFactory (stoix_tpu/envs/cvec.py) for the "
                "first-party C++ vectorized envs."
            ) from e
        from stoix_tpu.envs.envpool_adapter import EnvPoolAdapter

        seed = self._next_seed(num_envs)
        # gym_reset_return_info: reset() -> (obs, info), the API the adapter
        # consumes (reference env_factory.py:57-66).
        return EnvPoolAdapter(
            envpool.make(
                self._task_id,
                env_type="gymnasium",
                num_envs=num_envs,
                seed=seed,
                gym_reset_return_info=True,
                **self._kwargs,
            )
        )


def make_factory(config: Any) -> EnvFactory:
    """Build the Sebulba env factory from config (reference make_env.py:469-513)."""
    scenario = (
        config.env.scenario.name
        if hasattr(config.env.scenario, "name")
        else config.env.scenario
    )
    kwargs = dict(config.env.get("kwargs", {}) or {})
    backend = str(config.env.get("backend", "jax"))
    seed = int(config.arch.seed)
    if backend == "envpool":
        return EnvPoolFactory(scenario, seed, **kwargs)
    if backend == "cvec":
        from stoix_tpu.envs.cvec import CVecEnvFactory

        return CVecEnvFactory(scenario, seed, **kwargs)
    if backend == "gymnasium":
        from stoix_tpu.envs.gymnasium_adapter import GymnasiumFactory

        return GymnasiumFactory(scenario, seed, **kwargs)
    return JaxEnvFactory(scenario, seed, **kwargs)
