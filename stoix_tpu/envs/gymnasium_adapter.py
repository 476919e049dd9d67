"""Gymnasium adapter (reference stoix/wrappers/gymnasium.py VecGymToStoa +
stoix/utils/env_factory.py GymnasiumFactory): wraps vectorized Gymnasium envs
as stateful Sebulba envs emitting the canonical TimeStep/Observation structs,
with episode-metric accounting done host-side in numpy.

Gymnasium's SyncVectorEnv auto-resets internally and reports the true final
observation via `final_observation`/`final_obs` infos, which this adapter
surfaces as extras["next_obs"] for correct bootstrapping.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from stoix_tpu.envs import spaces
from stoix_tpu.envs.factory import EnvFactory
from stoix_tpu.envs.types import Observation, TimeStep


class VecGymToStoix:
    takes_host_actions = True  # step() reads the action on the host (see CVecPool)

    def __init__(self, envs: Any):
        self._envs = envs
        self._n = envs.num_envs
        self._ep_return = np.zeros((self._n,), np.float32)
        self._ep_length = np.zeros((self._n,), np.int32)

    @property
    def num_envs(self) -> int:
        return self._n

    @property
    def num_actions(self) -> int:
        space = self._envs.single_action_space
        import gymnasium as gym

        if isinstance(space, gym.spaces.Discrete):
            return int(space.n)
        return int(np.prod(space.shape))

    def observation_space(self) -> Observation:
        obs_shape = self._envs.single_observation_space.shape
        return Observation(
            agent_view=spaces.Array(tuple(obs_shape), np.float32),
            action_mask=spaces.Array((self.num_actions,), np.float32),
            step_count=spaces.Array((), np.int32),
        )

    def action_space(self) -> spaces.Space:
        import gymnasium as gym

        space = self._envs.single_action_space
        if isinstance(space, gym.spaces.Discrete):
            return spaces.Discrete(int(space.n))
        return spaces.Box(low=space.low, high=space.high, shape=tuple(space.shape))

    def _observation(self, view: np.ndarray) -> Observation:
        return Observation(
            agent_view=np.asarray(view, np.float32),
            action_mask=np.ones((self._n, self.num_actions), np.float32),
            step_count=self._ep_length.copy(),
        )

    def reset(self, *, seed: Optional[int] = None) -> TimeStep:
        obs, _info = self._envs.reset(seed=seed)
        self._ep_return[:] = 0
        self._ep_length[:] = 0
        return TimeStep(
            step_type=np.zeros((self._n,), np.int8),
            reward=np.zeros((self._n,), np.float32),
            discount=np.ones((self._n,), np.float32),
            observation=self._observation(obs),
            extras={
                "next_obs": self._observation(obs),
                "truncation": np.zeros((self._n,), bool),
                "episode_metrics": {
                    "episode_return": self._ep_return.copy(),
                    "episode_length": self._ep_length.copy(),
                    "is_terminal_step": np.zeros((self._n,), bool),
                },
            },
        )

    def step(self, action: Any) -> TimeStep:
        obs, reward, terminated, truncated, infos = self._envs.step(np.asarray(action))
        reward = np.asarray(reward, np.float32)
        terminated = np.asarray(terminated, bool)
        truncated = np.asarray(truncated, bool)
        last = terminated | truncated

        self._ep_return += reward
        self._ep_length += 1
        ep_return = self._ep_return.copy()
        ep_length = self._ep_length.copy()
        self._ep_return[last] = 0
        self._ep_length[last] = 0

        # True successor observations (pre-auto-reset) for bootstrapping.
        next_obs = np.asarray(obs, np.float32).copy()
        final = infos.get("final_observation", infos.get("final_obs"))
        if final is not None:
            for i, fo in enumerate(final):
                if fo is not None:
                    next_obs[i] = np.asarray(fo, np.float32)

        return TimeStep(
            step_type=np.where(last, np.int8(2), np.int8(1)),
            reward=reward,
            discount=np.where(terminated, 0.0, 1.0).astype(np.float32),
            observation=self._observation(obs),
            extras={
                "next_obs": self._observation(next_obs),
                "truncation": truncated,
                "episode_metrics": {
                    "episode_return": ep_return,
                    "episode_length": ep_length,
                    "is_terminal_step": last,
                },
            },
        )


class GymnasiumFactory(EnvFactory):
    """Creates SyncVectorEnv batches of a Gymnasium task behind the Sebulba
    factory seam (thread-safe seeding via EnvFactory)."""

    def __call__(self, num_envs: int) -> VecGymToStoix:
        import gymnasium as gym

        self._next_seed(num_envs)  # keep thread-unique seed accounting
        fns = [lambda: gym.make(self._task_id, **self._kwargs) for _ in range(num_envs)]
        # SAME_STEP autoreset reports the true final observation in infos (the
        # 1.x default NEXT_STEP mode inserts a fabricated reset transition and
        # never exposes final observations).
        try:
            envs = gym.vector.SyncVectorEnv(
                fns, autoreset_mode=gym.vector.AutoresetMode.SAME_STEP
            )
        except TypeError:  # older gymnasium: SAME_STEP was the only behavior
            envs = gym.vector.SyncVectorEnv(fns)
        return VecGymToStoix(envs)
