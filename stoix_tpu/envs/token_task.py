"""A seeded token task of fixed length: the environment of the token-policy
systems (systems/ppo/anakin/ff_lm_ppo.py), standing where a reward model or a
verifier stands in LM post-training.

An episode is one response of `length` tokens. The observation is the token
the policy conditions on next — at t = 0 a task token drawn from the reset
key, afterwards the previous action — and its position. The action is the
next token, `Discrete(vocab_size)`. The reward is terminal and verifiable
from the tokens alone: the share of the `length` actions whose residue
modulo `modulus` equals that of the token before them (the task token for
the first). A uniform policy scores about 1 / `modulus` (0.5 at the default
2); a policy that keeps to one residue class scores near 1. A few integer
ops a step: the policy, not the env, is the work.

`action_mask` has ONE entry, not `vocab_size`: every token is legal, and a
mask of 50,304 ones an env a step would be 200 KB of traffic through every
wrapper's select for nothing.

With `prompt_length` P > 0 every episode also has a PROMPT: P prefix tokens
(ids from the vocabulary) that come before the task token — positions 0 ..
P - 1 of the sequence the policy conditions on, the task token at P. The
episode is still `length` actions and the reward still their rule; the
prefix is context, not scored. It is drawn from the key the reset leaves in
the state and read with `prompt(state)`, not carried: the state is no wider
for it, and no P ids a step pass through the observation or through any
wrapper's select (3,072 ids a step are the traffic the one-entry mask was
written to avoid). P = 0 is the env as it was, bit for
bit.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from stoix_tpu.envs import spaces
from stoix_tpu.envs.core import Environment
from stoix_tpu.envs.types import (
    Observation,
    TimeStep,
    restart,
    select_step,
    termination,
    transition,
)


class TokenTaskState(NamedTuple):
    key: jax.Array
    previous: jax.Array  # the token the next action is scored against
    step_count: jax.Array
    matches: jax.Array  # actions so far that met the rule


class TokenTask(Environment):
    def __init__(
        self, vocab_size: int = 50304, length: int = 512, modulus: int = 2, prompt_length: int = 0
    ):
        self.vocab_size = int(vocab_size)
        self.length = int(length)
        self.prompt_length = int(prompt_length)
        self._modulus = int(modulus)

    def observation_space(self) -> Observation:
        return Observation(
            agent_view=spaces.Array((2,), jnp.int32),  # (token, position)
            action_mask=spaces.Array((1,), jnp.float32),
            step_count=spaces.Array((), jnp.int32),
        )

    def action_space(self) -> spaces.Discrete:
        return spaces.Discrete(self.vocab_size)

    def _obs(self, state: TokenTaskState) -> Observation:
        return Observation(
            agent_view=jnp.stack([state.previous, state.step_count]).astype(jnp.int32),
            action_mask=jnp.ones((1,), jnp.float32),
            step_count=state.step_count,
        )

    def reset(self, key: jax.Array) -> Tuple[TokenTaskState, TimeStep]:
        key, sub = jax.random.split(key)
        task = jax.random.randint(sub, (), 0, self.vocab_size, jnp.int32)
        state = TokenTaskState(key, task, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        return state, restart(self._obs(state))

    def prompt(self, state: TokenTaskState) -> jax.Array:
        """The episode's prefix tokens, int32 [..., `prompt_length`], from this
        env's own state (`wrappers.unwrapped_state` of a wrapped one), one
        episode's or a batch of them: a function of the episode's reset key,
        the same at every step of the episode and new after an auto-reset."""
        if not isinstance(state, TokenTaskState):  # (a wrapper's state has a `key` of its own)
            raise TypeError(f"prompt() reads this env's own state, not {type(state).__name__}")
        keys = state.key.reshape(-1, state.key.shape[-1])
        draw = lambda key: jax.random.randint(
            jax.random.fold_in(key, 1), (self.prompt_length,), 0, self.vocab_size, jnp.int32
        )
        return jax.vmap(draw)(keys).reshape(*state.key.shape[:-1], self.prompt_length)

    def step(self, state: TokenTaskState, action: jax.Array) -> Tuple[TokenTaskState, TimeStep]:
        action = jnp.asarray(action, jnp.int32)
        match = (action % self._modulus) == (state.previous % self._modulus)
        count = state.step_count + 1
        matches = state.matches + match.astype(jnp.int32)
        next_state = TokenTaskState(state.key, action, count, matches)
        obs = self._obs(next_state)
        done = count >= self.length
        reward = matches.astype(jnp.float32) / self.length
        return next_state, select_step(
            done, termination(reward, obs), transition(jnp.zeros((), jnp.float32), obs)
        )
