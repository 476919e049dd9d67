"""Core environment types: StepType, TimeStep, Observation.

Mirrors the behavior of the `stoa` types used by the reference (cited throughout
reference stoix/base_types.py:32-60) with a TPU-first representation: everything
is a flat pytree of fixed-shape arrays so that the whole rollout fits inside one
`lax.scan` under `jit`/`shard_map` with static shapes.

Truncation semantics (the subtle part, see reference stoix/utils/multistep.py:119-130):
  - termination: step_type == LAST and discount == 0.0
  - truncation:  step_type == LAST and discount == 1.0  (bootstrapping continues)
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp


class StepType:
    """Integer step-type codes, stored as int8 arrays inside TimeStep.

    Plain Python ints (not jnp arrays) so importing this module does no
    device work; comparisons and jnp.where treat them identically.
    """

    FIRST = 0
    MID = 1
    LAST = 2


class TimeStep(NamedTuple):
    """One transition's worth of env output.

    extras is a flat dict; well-known keys:
      "next_obs"          — true next observation before any auto-reset (bootstrap).
      "episode_metrics"   — dict(episode_return, episode_length, is_terminal_step).
      "truncation"        — bool, LAST due to step limit (discount stays 1).
    """

    step_type: jax.Array  # int8 []
    reward: jax.Array  # float32 []
    discount: jax.Array  # float32 []
    observation: Any  # pytree
    extras: Dict[str, Any]

    def first(self) -> jax.Array:
        return self.step_type == StepType.FIRST

    def mid(self) -> jax.Array:
        return self.step_type == StepType.MID

    def last(self) -> jax.Array:
        return self.step_type == StepType.LAST


def restart(observation: Any, extras: Optional[Dict[str, Any]] = None, shape: tuple = ()) -> TimeStep:
    return TimeStep(
        step_type=jnp.full(shape, 0, dtype=jnp.int8),
        reward=jnp.zeros(shape, dtype=jnp.float32),
        discount=jnp.ones(shape, dtype=jnp.float32),
        observation=observation,
        extras=extras if extras is not None else {},
    )


def transition(
    reward: jax.Array,
    observation: Any,
    discount: Optional[jax.Array] = None,
    extras: Optional[Dict[str, Any]] = None,
    shape: tuple = (),
) -> TimeStep:
    return TimeStep(
        step_type=jnp.full(shape, 1, dtype=jnp.int8),
        reward=jnp.asarray(reward, dtype=jnp.float32),
        discount=jnp.ones(shape, dtype=jnp.float32) if discount is None else jnp.asarray(discount, jnp.float32),
        observation=observation,
        extras=extras if extras is not None else {},
    )


def termination(
    reward: jax.Array, observation: Any, extras: Optional[Dict[str, Any]] = None, shape: tuple = ()
) -> TimeStep:
    return TimeStep(
        step_type=jnp.full(shape, 2, dtype=jnp.int8),
        reward=jnp.asarray(reward, dtype=jnp.float32),
        discount=jnp.zeros(shape, dtype=jnp.float32),
        observation=observation,
        extras=extras if extras is not None else {},
    )


def truncation(
    reward: jax.Array, observation: Any, extras: Optional[Dict[str, Any]] = None, shape: tuple = ()
) -> TimeStep:
    return TimeStep(
        step_type=jnp.full(shape, 2, dtype=jnp.int8),
        reward=jnp.asarray(reward, dtype=jnp.float32),
        discount=jnp.ones(shape, dtype=jnp.float32),
        observation=observation,
        extras=extras if extras is not None else {},
    )


def select_step(done: jax.Array, terminal_ts: TimeStep, mid_ts: TimeStep) -> TimeStep:
    """Elementwise select between terminal and mid timesteps on a traced `done`."""
    return jax.tree.map(lambda a, b: jnp.where(_bcast(done, a), a, b), terminal_ts, mid_ts)


def _bcast(flag: jax.Array, like: jax.Array) -> jax.Array:
    flag = jnp.asarray(flag)
    like = jnp.asarray(like)
    extra = like.ndim - flag.ndim
    return flag.reshape(flag.shape + (1,) * extra) if extra > 0 else flag


class Observation(NamedTuple):
    """Canonical structured observation (reference stoix/base_types.py:32-43).

    agent_view:  the raw observable features (e.g. [obs_dim] or [H, W, C]).
    action_mask: legal-action mask [num_actions] (all-ones when env has no masking).
    step_count:  steps elapsed in the current episode [].
    """

    agent_view: jax.Array
    action_mask: jax.Array
    step_count: jax.Array


# The prefix of an episode metric that is a record made once a SEQUENCE (its
# leading axes are not `is_terminal_step`'s), for whoever reads a window from
# outside; `get_final_step_metrics` leaves it out by this name.
ONCE_A_SEQUENCE = "sequence_"


def get_final_step_metrics(metrics: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Filter episode metrics to completed episodes only.

    Given a dict with "episode_return", "episode_length", "is_terminal_step"
    (each shaped [...]), returns values gathered where is_terminal_step is True,
    as 1-D host-side arrays. Used by the host logging loop (reference
    ff_ppo.py:624-629 via stoa's helper). A leaf with further axes behind
    those of is_terminal_step (a record of a block of tokens a step) keeps
    them: [episodes, ...]. A key under `ONCE_A_SEQUENCE` is a record made once a
    sequence, not once a step (a rollout's prompt): no step's, and left out.
    """
    import numpy as np

    terminal = np.asarray(metrics["is_terminal_step"])
    is_final = terminal.reshape(-1)
    out: Dict[str, jax.Array] = {}
    for k, v in metrics.items():
        if k == "is_terminal_step":
            continue
        if k.startswith(ONCE_A_SEQUENCE):
            continue
        v = np.asarray(v)
        out[k] = v.reshape((is_final.size,) + v.shape[terminal.ndim:])[is_final]
    return out
