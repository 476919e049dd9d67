"""The token task in block form: the environment of the block-diffusion
token policy (systems/ppo/anakin/ff_sdar_ppo.py). Same verifiable reward as
envs/token_task.py, but a step is one DENOISE PASS over a block of
`block_length` positions, not one token.

An episode is one response of `length` tokens, generated left to right in
`length / block_length` blocks of `passes` denoise passes each, after a
prompt block of `block_length` task tokens drawn from the reset key. The
observation is (the current block's contents — the mask id where a position
is still masked —, the prompt block, the response block's index, the pass's
index); the action is the block's contents AFTER the pass, `[block_length]`
ids. After a block's last pass its contents are final: the next observation
shows the next block, all masks. `length / block_length * passes` steps an
episode; how many tokens a pass commits is the policy's schedule
(`block_length / passes` under the static one).

The reward is terminal and verifiable from the finished tokens alone: the
share of the `length` response tokens whose residue modulo `modulus` equals
that of the token before them (the last prompt token for the first). A
position left masked counts as a miss. The mask id is the LAST id of the
vocabulary and is never a task token: prompts draw from `[0, vocab_size - 1)`.
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


class BlockTokenTaskState(NamedTuple):
    key: jax.Array
    prompt: jax.Array  # [block_length] task tokens
    block: jax.Array  # [block_length] the current block's contents
    previous: jax.Array  # the token the current block's first is scored against
    block_index: jax.Array  # response blocks finished
    pass_index: jax.Array  # denoise passes made over the current block
    step_count: jax.Array
    matches: jax.Array  # tokens of finished blocks that met the rule


class BlockTokenTask(Environment):
    def __init__(
        self, vocab_size: int = 18992, length: int = 512, block_length: int = 4, passes: int = 2,
        modulus: int = 2,
    ):
        self.vocab_size = int(vocab_size)
        self.length = int(length)
        self.block_length = int(block_length)
        self.passes = int(passes)
        self._modulus = int(modulus)
        if self.length % self.block_length or self.block_length % self.passes:
            raise ValueError(
                f"a response of {length} tokens does not divide into blocks of {block_length}, "
                f"or a block into {passes} passes"
            )
        self.num_blocks = self.length // self.block_length
        # Steps an episode: what `system.rollout_length` must equal.
        self.episode_steps = self.num_blocks * self.passes
        self.mask_id = self.vocab_size - 1

    def observation_space(self) -> Observation:
        return Observation(
            # (block [B], prompt [B], block index, pass index)
            agent_view=spaces.Array((2 * self.block_length + 2,), jnp.int32),
            action_mask=spaces.Array((1,), jnp.float32),
            step_count=spaces.Array((), jnp.int32),
        )

    def action_space(self) -> spaces.MultiDiscrete:
        return spaces.MultiDiscrete((self.vocab_size,) * self.block_length)

    def _obs(self, state: BlockTokenTaskState) -> Observation:
        return Observation(
            agent_view=jnp.concatenate([
                state.block, state.prompt, jnp.stack([state.block_index, state.pass_index])
            ]).astype(jnp.int32),
            action_mask=jnp.ones((1,), jnp.float32),
            step_count=state.step_count,
        )

    def reset(self, key: jax.Array) -> Tuple[BlockTokenTaskState, TimeStep]:
        key, sub = jax.random.split(key)
        prompt = jax.random.randint(sub, (self.block_length,), 0, self.mask_id, jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        masks = jnp.full((self.block_length,), self.mask_id, jnp.int32)
        state = BlockTokenTaskState(key, prompt, masks, prompt[-1], zero, zero, zero, zero)
        return state, restart(self._obs(state))

    def step(
        self, state: BlockTokenTaskState, action: jax.Array
    ) -> Tuple[BlockTokenTaskState, TimeStep]:
        block = jnp.asarray(action, jnp.int32)
        finished = state.pass_index + 1 >= self.passes
        before = jnp.concatenate([state.previous[None], block[:-1]])
        match = (block != self.mask_id) & (
            (block % self._modulus) == (before % self._modulus)
        )
        matches = state.matches + jnp.where(finished, jnp.sum(match.astype(jnp.int32)), 0)
        block_index = state.block_index + finished.astype(jnp.int32)
        next_state = BlockTokenTaskState(
            key=state.key,
            prompt=state.prompt,
            block=jnp.where(finished, self.mask_id, block),
            previous=jnp.where(finished, block[-1], state.previous),
            block_index=block_index,
            pass_index=jnp.where(finished, 0, state.pass_index + 1),
            step_count=state.step_count + 1,
            matches=matches,
        )
        obs = self._obs(next_state)
        done = block_index >= self.num_blocks
        reward = matches.astype(jnp.float32) / self.length
        return next_state, select_step(
            done, termination(reward, obs), transition(jnp.zeros((), jnp.float32), obs)
        )
