"""EnvPool adapter: full-Atari reset/lives semantics for the Sebulba path.

The reference's Sebulba runs EnvPool Atari through `EnvPoolToStoa`
(reference stoix/wrappers/envpool.py:75-115), whose load-bearing behaviors are:

  1. **done-ids autoreset** (:75-86): envpool's own autoreset returns the
     terminal observation on the done step and the reset observation one step
     LATER; the stoix API wants the reset observation immediately. The adapter
     therefore issues a second `env.step(zeros, done_ids)` restricted to the
     finished envs and splices those reset observations in. The TRUE terminal
     successor is preserved in `extras["next_obs"]` for bootstrapping.
  2. **lives handling** (:99-117): on Atari, losing a life ends an envpool
     episode; episode metrics must only conclude when ALL lives are exhausted
     (`info["lives"] == 0`), otherwise per-life returns pollute the learning
     curves.
  3. **elapsed_step truncation** (:72, :144-148): envpool reports
     `info["elapsed_step"]`; hitting `max_episode_steps` is a truncation
     (discount stays 1) rather than a termination.

Produces the same stateful TimeStep contract as the native CVecPool
(stoix_tpu/envs/cvec.py): Observation(agent_view, action_mask, step_count) and
extras {next_obs, truncation, episode_metrics} — so the whole Sebulba rollout
machinery is backend-agnostic between the first-party C++ pool and EnvPool.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from stoix_tpu.envs import spaces
from stoix_tpu.envs.types import Observation, TimeStep


class EnvPoolAdapter:
    """Wrap a constructed envpool env (gymnasium API, gym_reset_return_info)."""

    takes_host_actions = True  # step() reads the action on the host (see CVecPool)

    def __init__(self, env: Any, has_lives: Optional[bool] = None):
        self._env = env
        obs, _ = env.reset()
        self._n = int(obs.shape[0])
        self._obs_shape = tuple(obs.shape[1:])
        self._num_actions = int(env.action_space.n)
        self._max_episode_steps = int(env.spec.config.max_episode_steps)

        if has_lives is None:
            # Probe: Atari tasks report a positive lives counter (reference
            # envpool.py:24-33 probes with one zero-action step).
            info = env.step(np.zeros(self._n, dtype=np.int32))[-1]
            has_lives = bool("lives" in info and np.sum(info["lives"]) > 0)
            obs, _ = env.reset()
        self._has_lives = bool(has_lives)

        self._obs = obs
        self._elapsed = np.zeros(self._n, dtype=np.int64)
        # Running episode accumulators + the last CONCLUDED episode's metrics
        # (concluded = all lives exhausted when has_lives, else any done).
        self._run_return = np.zeros(self._n, dtype=np.float64)
        self._run_length = np.zeros(self._n, dtype=np.int64)
        self._ep_return = np.zeros(self._n, dtype=np.float64)
        self._ep_length = np.zeros(self._n, dtype=np.int64)

    @property
    def num_envs(self) -> int:
        return self._n

    @property
    def num_actions(self) -> int:
        return self._num_actions

    def observation_space(self):
        return Observation(
            agent_view=spaces.Array(self._obs_shape, np.float32),
            action_mask=spaces.Array((self._num_actions,), np.float32),
            step_count=spaces.Array((), np.int32),
        )

    def action_space(self):
        return spaces.Discrete(self._num_actions)

    def _observation(self, view: np.ndarray, counts: np.ndarray) -> Observation:
        return Observation(
            agent_view=np.asarray(view, np.float32),
            action_mask=np.ones((self._n, self._num_actions), np.float32),
            step_count=counts.astype(np.int32),
        )

    def reset(self, *, seed: Optional[int] = None) -> TimeStep:
        del seed  # envpool seeds at construction
        obs, _ = self._env.reset()
        self._obs = obs
        self._elapsed[:] = 0
        self._run_return[:] = 0
        self._run_length[:] = 0
        self._ep_return[:] = 0
        self._ep_length[:] = 0
        zeros = np.zeros(self._n, np.float32)
        return TimeStep(
            step_type=np.zeros(self._n, np.int8),
            reward=zeros.copy(),
            discount=np.ones(self._n, np.float32),
            observation=self._observation(obs, np.zeros(self._n, np.int64)),
            extras={
                "next_obs": self._observation(obs, np.zeros(self._n, np.int64)),
                "truncation": np.zeros(self._n, bool),
                "episode_metrics": {
                    "episode_return": zeros.astype(np.float64),
                    "episode_length": np.zeros(self._n, np.int64),
                    "is_terminal_step": np.zeros(self._n, bool),
                },
            },
        )

    def step(self, action: Any) -> TimeStep:
        action = np.asarray(action, np.int32).reshape(self._n)
        obs, rewards, terminated, env_truncated, info = self._env.step(action)
        terminated = np.asarray(terminated, bool)
        elapsed = np.asarray(info.get("elapsed_step", self._elapsed + 1))
        # OR the pool's own truncated flag with the elapsed-step check: if the
        # pool truncates on a condition the step counter misses, dropping its
        # flag would desync the done-ids reset splice one step later.
        truncated = np.logical_and(
            np.logical_or(
                np.asarray(env_truncated, bool),
                elapsed >= self._max_episode_steps,
            ),
            ~terminated,
        )
        ep_done = np.logical_or(terminated, truncated)

        # True terminal successors, before any reset splice (bootstrapping).
        next_obs = np.array(obs, copy=True)

        # done-ids autoreset (reference envpool.py:75-86): step ONLY the
        # finished envs with a zero action to obtain their reset observations.
        done_ids = np.where(ep_done)[0]
        if len(done_ids) > 0:
            reset_obs = self._env.step(
                np.zeros(len(done_ids), dtype=np.int32), done_ids
            )[0]
            obs = np.array(obs, copy=True)
            obs[done_ids] = reset_obs

        metric_reward = np.asarray(info.get("reward", rewards), np.float64)
        new_return = self._run_return + metric_reward
        new_length = self._run_length + 1

        if self._has_lives:
            # A game concludes when all lives are gone — OR when the episode
            # is cut by the step limit with lives remaining (the run would
            # otherwise silently merge into the next game's metrics).
            concluded = np.logical_or(
                np.logical_and(ep_done, np.asarray(info["lives"]) == 0),
                truncated,
            )
        else:
            concluded = ep_done
        keep = ~concluded
        self._ep_return = np.where(concluded, new_return, self._ep_return)
        self._ep_length = np.where(concluded, new_length, self._ep_length)
        self._run_return = np.where(concluded, 0.0, new_return)
        self._run_length = np.where(concluded, 0, new_length)

        self._elapsed = np.where(ep_done, 0, elapsed)
        self._obs = obs

        counts = np.where(ep_done, 0, elapsed)
        discount = np.where(terminated, 0.0, 1.0).astype(np.float32)
        return TimeStep(
            step_type=np.where(ep_done, np.int8(2), np.int8(1)),
            reward=np.asarray(rewards, np.float32),
            discount=discount,
            observation=self._observation(obs, counts),
            extras={
                "next_obs": self._observation(next_obs, elapsed),
                "truncation": truncated,
                "episode_metrics": {
                    "episode_return": self._ep_return.copy(),
                    "episode_length": self._ep_length.copy(),
                    "is_terminal_step": concluded.copy(),
                },
            },
        )

    def close(self) -> None:
        self._env.close()
