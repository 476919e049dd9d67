"""ctypes adapter for the native C++ vectorized env pool (envs/native/cvec.cpp)
— the first-party EnvPool equivalent behind the Sebulba EnvFactory seam
(reference stoix/wrappers/envpool.py adapts EnvPool's API the same way: manual
auto-reset bookkeeping, numpy episode metrics, stoa-style TimeSteps).

Games: "CartPole-v1" (4-float obs), "Pendulum-v1" (continuous torque — the
Sebulba continuous-control workload, float actions through cvec_step_cont),
the 10x10x4-pixel MinAtar-class set "Breakout-minatar",
"Asterix-minatar", "Freeway-minatar", "SpaceInvaders-minatar" — each with a
(bit-)identical pure-JAX twin in envs/minatar.py / envs/classic.py — and
"Breakout-atari", the FULL-RESOLUTION pixel workload: 84x84x4 frame-stacked
grayscale observations, the exact tensor shape the reference's EnvPool Atari
path trains on (reference configs/env/envpool/*.yaml). The shared library is
compiled on first use with g++ into a git-ignored file next to the source,
named by the source's content hash; no Python-level per-env loops exist
anywhere on the hot path.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Any, Optional, Tuple

import numpy as np

from stoix_tpu.envs import spaces
from stoix_tpu.envs.factory import EnvFactory
from stoix_tpu.envs.types import Observation, TimeStep

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "native")
_SOURCE = os.path.join(_NATIVE_DIR, "cvec.cpp")
_BUILD_LOCK = threading.Lock()


def _ensure_built() -> str:
    """Build `cvec.cpp` on first use into `native/libcvec-<hash>.so` and return
    that path. The name is keyed by the SOURCE'S CONTENT (not an mtime, which
    a copy to another machine makes arbitrary), so the pool that runs is always
    the source that is read; the binaries are git-ignored, never committed.
    A missing compiler is an error here, at the first pool, not a stale or
    absent library later."""
    with open(_SOURCE, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    lib_path = os.path.join(_NATIVE_DIR, f"libcvec-{digest}.so")
    with _BUILD_LOCK:
        if os.path.exists(lib_path):
            return lib_path
        compiler = shutil.which("g++")
        if compiler is None:
            raise RuntimeError(
                "the native env pool (env.backend=cvec) is built from "
                f"{_SOURCE} on first use and needs g++ on PATH; none found"
            )
        # Compile to a private name, then rename: a concurrent process either
        # sees the finished library or builds its own identical copy.
        tmp_path = f"{lib_path}.{os.getpid()}.tmp"
        proc = subprocess.run(
            [compiler, "-O3", "-shared", "-fPIC", _SOURCE, "-o", tmp_path],
            capture_output=True,
            text=True,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"g++ failed to build {_SOURCE} (rc {proc.returncode}):\n{proc.stderr}"
            )
        os.replace(tmp_path, lib_path)
    return lib_path


def _load_lib() -> ctypes.CDLL:
    lib = ctypes.CDLL(_ensure_built())
    lib.cvec_create.restype = ctypes.c_void_p
    lib.cvec_create.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_uint64]
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.cvec_reset.argtypes = [ctypes.c_void_p, f32p]
    lib.cvec_step.argtypes = [ctypes.c_void_p, i32p, f32p, f32p, f32p, u8p, u8p, f32p, i32p]
    lib.cvec_obs_dim.argtypes = [ctypes.c_void_p]
    lib.cvec_obs_dim.restype = ctypes.c_int
    lib.cvec_obs_shape.argtypes = [ctypes.c_void_p, i32p]
    lib.cvec_num_actions.argtypes = [ctypes.c_void_p]
    lib.cvec_num_actions.restype = ctypes.c_int
    lib.cvec_action_dim.argtypes = [ctypes.c_void_p]
    lib.cvec_action_dim.restype = ctypes.c_int
    lib.cvec_action_bounds.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float),
    ]
    lib.cvec_step_cont.argtypes = [ctypes.c_void_p, f32p, f32p, f32p, f32p, u8p, u8p, f32p, i32p]
    lib.cvec_destroy.argtypes = [ctypes.c_void_p]
    return lib


class CVecPool:
    """Stateful Sebulba env backed by the native pool: numpy in, TimeStep out."""

    # step() reads the action on the host: a Sebulba actor copies it there
    # before it times the pool (sebulba/runner.py::_rollout_body).
    takes_host_actions = True

    def __init__(self, task: str, num_envs: int, seed: int, max_steps: int = 500):
        self._lib = _load_lib()
        self._handle = self._lib.cvec_create(task.encode(), num_envs, max_steps, seed)
        if not self._handle:
            raise ValueError(f"Unknown native pool game '{task}'")
        self._task = task
        self._n = num_envs
        shape3 = np.zeros((3,), np.int32)
        self._lib.cvec_obs_shape(self._handle, shape3)
        # (d, 1, 1) encodes a flat d-vector; anything else is an image.
        self._obs_shape: Tuple[int, ...] = (
            (int(shape3[0]),) if shape3[1] == 1 and shape3[2] == 1 else tuple(int(s) for s in shape3)
        )
        self._num_actions = int(self._lib.cvec_num_actions(self._handle))
        # action_dim > 0 marks a continuous game (float [n, action_dim]
        # actions through cvec_step_cont; Box action space with the game's
        # native bounds).
        self._action_dim = int(self._lib.cvec_action_dim(self._handle))
        lo, hi = ctypes.c_float(), ctypes.c_float()
        self._lib.cvec_action_bounds(self._handle, ctypes.byref(lo), ctypes.byref(hi))
        self._action_bounds = (float(lo.value), float(hi.value))
        dim = int(self._lib.cvec_obs_dim(self._handle))
        self._obs = np.zeros((num_envs, dim), np.float32)
        self._next_obs = np.zeros((num_envs, dim), np.float32)
        self._reward = np.zeros((num_envs,), np.float32)
        self._done = np.zeros((num_envs,), np.uint8)
        self._trunc = np.zeros((num_envs,), np.uint8)
        self._ep_return = np.zeros((num_envs,), np.float32)
        self._ep_length = np.zeros((num_envs,), np.int32)

    @property
    def num_envs(self) -> int:
        return self._n

    @property
    def num_actions(self) -> int:
        return self._num_actions

    def observation_space(self) -> Observation:
        return Observation(
            agent_view=spaces.Array(self._obs_shape, np.float32),
            action_mask=spaces.Array((self._num_actions,), np.float32),
            step_count=spaces.Array((), np.int32),
        )

    def action_space(self):
        if self._action_dim > 0:
            lo, hi = self._action_bounds
            return spaces.Box(low=lo, high=hi, shape=(self._action_dim,))
        return spaces.Discrete(self._num_actions)

    def _observation(self, view: np.ndarray, counts: np.ndarray) -> Observation:
        return Observation(
            agent_view=view.reshape((self._n,) + self._obs_shape).copy(),
            action_mask=np.ones((self._n, self._num_actions), np.float32),
            step_count=counts.astype(np.int32),
        )

    def _timestep(self, first: bool) -> TimeStep:
        done = self._done.astype(bool)
        trunc = self._trunc.astype(bool)
        last = done | trunc
        counts = np.where(last, 0, self._ep_length)
        return TimeStep(
            step_type=np.where(
                np.zeros((self._n,), bool) if not first else np.ones((self._n,), bool),
                np.int8(0),
                np.where(last, np.int8(2), np.int8(1)),
            ),
            reward=self._reward.copy(),
            discount=np.where(done, 0.0, 1.0).astype(np.float32),
            observation=self._observation(self._obs, counts),
            extras={
                "next_obs": self._observation(self._next_obs, self._ep_length),
                "truncation": trunc.copy(),
                "episode_metrics": {
                    "episode_return": self._ep_return.copy(),
                    "episode_length": self._ep_length.copy(),
                    "is_terminal_step": last.copy(),
                },
            },
        )

    def reset(self, *, seed: Optional[int] = None) -> TimeStep:
        del seed  # seeding fixed at construction (thread-unique via factory)
        self._lib.cvec_reset(self._handle, self._obs)
        self._reward[:] = 0
        self._done[:] = 0
        self._trunc[:] = 0
        self._ep_return[:] = 0
        self._ep_length[:] = 0
        self._next_obs[:] = self._obs
        return self._timestep(first=True)

    def step(self, action: Any) -> TimeStep:
        if self._action_dim > 0:
            actions = np.ascontiguousarray(
                np.asarray(action, np.float32).reshape(self._n, self._action_dim)
            )
            self._lib.cvec_step_cont(
                self._handle, actions, self._obs, self._next_obs, self._reward,
                self._done, self._trunc, self._ep_return, self._ep_length,
            )
        else:
            actions = np.ascontiguousarray(np.asarray(action, np.int32))
            self._lib.cvec_step(
                self._handle, actions, self._obs, self._next_obs, self._reward,
                self._done, self._trunc, self._ep_return, self._ep_length,
            )
        return self._timestep(first=False)

    def __del__(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.cvec_destroy(self._handle)
            self._handle = None


class CVecEnvFactory(EnvFactory):
    """Factory for the native pool; the scenario name selects the game."""

    def __call__(self, num_envs: int) -> CVecPool:
        seed = self._next_seed(num_envs)
        return CVecPool(self._task_id, num_envs, seed, **self._kwargs)


# Backwards-compatible alias (round-1 name, CartPole-only era).
class CVecCartPole(CVecPool):
    def __init__(self, num_envs: int, seed: int, max_steps: int = 500):
        super().__init__("CartPole-v1", num_envs, seed, max_steps)
