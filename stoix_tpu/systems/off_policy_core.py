"""Generic Anakin off-policy scaffolding for actor-critic systems
(DDPG/TD3/D4PG/SAC). Mirrors q_family.py's skeleton with an arbitrary params
pytree and a system-supplied per-shard learner.

Flow per update (reference ff_ddpg.py / ff_sac.py structure):
  scan(_env_step) rollout -> buffer.add -> scan(_update_epoch){ sample ->
  critic grad/update -> actor grad/update -> polyak targets } in one
  shard_mapped program; warmup pre-fills with uniform random actions.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu import envs
from stoix_tpu.base_types import ExperimentOutput, OffPolicyLearnerState, Transition
from stoix_tpu.buffers import make_item_buffer
from stoix_tpu.systems import anakin
from stoix_tpu.utils.jax_utils import tree_merge_leading_dims


def make_transition(last_timestep: Any, action: jax.Array, timestep: Any) -> Transition:
    return Transition(
        obs=last_timestep.observation,
        action=action,
        reward=timestep.reward,
        done=timestep.discount == 0.0,
        next_obs=timestep.extras["next_obs"],
        info=timestep.extras["episode_metrics"],
    )


def dummy_transition(env: envs.Environment, discrete_actions: bool = False) -> Transition:
    return Transition(
        obs=env.observation_value(),
        action=jnp.asarray(env.action_value(), jnp.int32 if discrete_actions else jnp.float32),
        reward=jnp.zeros((), jnp.float32),
        done=jnp.zeros((), bool),
        next_obs=env.observation_value(),
        info={
            "episode_return": jnp.zeros((), jnp.float32),
            "episode_length": jnp.zeros((), jnp.int32),
            "is_terminal_step": jnp.zeros((), bool),
        },
    )


def build_buffer(env: envs.Environment, config: Any, mesh: Mesh, discrete_actions: bool = False):
    """Per-(shard, update-batch) replay, dispatched on `system.replay.impl`
    (docs/DESIGN.md §2.10):

      local (default)  today's replicated uniform item buffer — every shard
                       samples only its own slice; bit-identical to the
                       pre-dispatch behavior (tests/test_replay.py pins it).
      sharded          the device-resident cross-shard sampler
                       (stoix_tpu/replay): the same ItemBuffer interface,
                       but `sample` draws the GLOBAL batch where the data
                       lives — one all_gather of shard masses + one psum of
                       the sampled minibatch — so per-shard HBM bounds only
                       a SHARD of the experience, not all of it.
    """
    n_shards = int(mesh.shape["data"])
    update_batch = int(config.arch.get("update_batch_size", 1))
    local_envs = int(config.arch.total_num_envs) // (n_shards * update_batch)
    buffer_size = max(1, int(config.system.total_buffer_size) // (n_shards * update_batch))
    batch_size = max(1, int(config.system.total_batch_size) // (n_shards * update_batch))
    replay_cfg = dict(config.system.get("replay") or {})
    impl = str(replay_cfg.get("impl", "local"))
    if impl == "local":
        buffer = make_item_buffer(
            max_length=buffer_size,
            min_length=batch_size,
            sample_batch_size=batch_size,
            add_batch_size=int(config.system.rollout_length) * local_envs,
        )
    elif impl == "sharded":
        from stoix_tpu.replay.compat import make_sharded_item_buffer

        if bool(replay_cfg.get("prioritized", False)):
            # The 4-function ItemBuffer interface this family consumes has
            # no set_priorities seam, so priorities would freeze at the
            # insert value and sampling would stay exactly uniform —
            # refuse rather than silently no-op the knob. The prioritized
            # path is Sebulba ff_dqn, whose learn program scatters TD
            # priorities in-program.
            raise ValueError(
                "system.replay.prioritized=true is not supported on the "
                "Anakin item-buffer path (no set_priorities seam in the "
                "ItemBuffer interface); use the Sebulba off-policy path "
                "(systems/q_learning/sebulba/ff_dqn.py) for distributed "
                "prioritized replay"
            )
        buffer = make_sharded_item_buffer(
            capacity_per_shard=buffer_size,
            sample_batch_size=batch_size * n_shards,
            num_shards=n_shards,
            min_fill=max(
                batch_size * n_shards,
                int(replay_cfg.get("min_fill", batch_size * n_shards)),
            ),
            axis="data",
        )
    else:
        raise ValueError(
            f"system.replay.impl must be 'local' or 'sharded', got {impl!r}"
        )
    return buffer, buffer.init(dummy_transition(env, discrete_actions))


def get_random_warmup_fn(env: envs.Environment, config: Any, buffer_add: Callable) -> Callable:
    """Uniform-random-action buffer pre-fill; continuous action spaces."""
    action_space = env.action_space()

    def warmup(state: OffPolicyLearnerState) -> OffPolicyLearnerState:
        def _step(carry, _):
            env_state, timestep, key = carry
            key, act_key = jax.random.split(key)
            n_envs = timestep.reward.shape[0]
            keys = jax.random.split(act_key, n_envs)
            action = jax.vmap(action_space.sample)(keys)
            next_env_state, next_timestep = env.step(env_state, action)
            return (next_env_state, next_timestep, key), make_transition(
                timestep, action, next_timestep
            )

        key, warmup_key = jax.random.split(state.key)
        (env_state, timestep, _), traj = jax.lax.scan(
            _step, (state.env_state, state.timestep, warmup_key), None,
            int(config.system.warmup_steps),
        )
        buffer_state = buffer_add(state.buffer_state, tree_merge_leading_dims(traj, 2))
        return state._replace(
            buffer_state=buffer_state, key=key, env_state=env_state, timestep=timestep
        )

    return warmup


def assemble_off_policy_state(
    config: Any,
    mesh: Mesh,
    env: envs.Environment,
    params: Any,
    opt_states: Any,
    buffer_state: Any,
    key: jax.Array,
    env_key: jax.Array,
) -> Tuple[OffPolicyLearnerState, OffPolicyLearnerState]:
    """Returns (placed learner_state, state_specs)."""
    n_shards = int(mesh.shape["data"])
    update_batch = int(config.arch.get("update_batch_size", 1))

    state_specs = OffPolicyLearnerState(
        params=P(),
        opt_states=P(),
        buffer_state=P("data"),
        key=P("data"),
        env_state=P(None, "data"),
        timestep=P(None, "data"),
    )
    env_state, timestep = anakin.reset_envs_for_anakin(env, config, env_key)
    learner_state = OffPolicyLearnerState(
        params=anakin.broadcast_to_update_batch(params, update_batch),
        opt_states=anakin.broadcast_to_update_batch(opt_states, update_batch),
        buffer_state=jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_shards, update_batch) + x.shape), buffer_state
        ),
        key=anakin.make_step_keys(key, mesh, config),
        env_state=env_state,
        timestep=timestep,
    )
    return anakin.place_learner_state(learner_state, mesh, state_specs), state_specs


def trajectory_buffer_sizing(
    config: Any, mesh: Mesh, min_length_time_axis: int
) -> Tuple[int, int, int]:
    """Per-shard trajectory-buffer sizes from the GLOBAL config totals.

    Returns (local_envs, sample_batch_size, max_length_time_axis): the
    global env/batch/buffer totals divided over data shards × update batch
    (reference ff_dqn.py:325-338 divides per device the same way). Shared by
    every sequence-replay system (AWR/MPO/Rainbow/R2D2/MuZero).
    """
    n_shards = int(mesh.shape["data"])
    update_batch = int(config.arch.get("update_batch_size", 1))
    denom = n_shards * update_batch
    local_envs = int(config.arch.total_num_envs) // denom
    if local_envs == 0:
        raise ValueError(
            f"arch.total_num_envs ({config.arch.total_num_envs}) must be >= "
            f"num_data_shards * update_batch_size ({denom})"
        )
    sample_batch = max(1, int(config.system.total_batch_size) // denom)
    max_length = max(
        int(config.system.total_buffer_size) // (denom * local_envs),
        int(min_length_time_axis),
    )
    return local_envs, sample_batch, max_length


def require_first_add_samplable(config: Any) -> None:
    """Guard for warmup-less sequence-replay learners (AZ/sampled-AZ/MZ
    variants): the trajectory buffer silently returns ZERO-initialized
    sequences when no full sequence has been written yet (buffers.py clamps
    n_periods to >= 1), so the first rollout add must already contain at
    least one sampleable start — otherwise every epoch of the first update
    trains on all-zero garbage with no error."""
    seq = int(config.system.get("sample_sequence_length", 8))
    rollout = int(config.system.rollout_length)
    if rollout - seq + 1 <= 0:
        raise ValueError(
            f"system.sample_sequence_length ({seq}) must be <= "
            f"system.rollout_length ({rollout}) for warmup-less replay "
            "learners: the first buffer add must already contain a full "
            "sequence, or early updates silently train on zero-filled samples"
        )


def wrap_learn(
    learn_per_shard: Callable,
    mesh: Mesh,
    state_specs: Any,
) -> Callable:
    """shard_map a learner fn, squeezing the buffer's [S] shard axis per
    shard (every buffer-holding system shares this wrapper)."""

    def per_shard_learn(state):
        squeezed = state._replace(
            buffer_state=jax.tree.map(lambda x: x[0], state.buffer_state)
        )
        out = learn_per_shard(squeezed)
        new_state = out.learner_state._replace(
            buffer_state=jax.tree.map(lambda x: x[None], out.learner_state.buffer_state)
        )
        return out._replace(learner_state=new_state)

    return anakin.shardmap_learner(per_shard_learn, mesh, state_specs)


def wrap_learn_and_warmup(
    learn_per_shard: Callable,
    warmup_core: Callable,
    mesh: Mesh,
    state_specs: Any,
) -> Tuple[Callable, Callable]:
    """shard_map both fns, squeezing the buffer's [S] shard axis per shard."""
    learn = wrap_learn(learn_per_shard, mesh, state_specs)

    def per_shard_warmup(state):
        squeezed = state._replace(
            buffer_state=jax.tree.map(lambda x: x[0], state.buffer_state),
            key=state.key[0],
        )
        out = jax.vmap(warmup_core, axis_name="batch")(squeezed)
        return out._replace(
            buffer_state=jax.tree.map(lambda x: x[None], out.buffer_state),
            key=out.key[None],
        )

    warmup = jax.jit(
        jax.shard_map(
            per_shard_warmup, mesh=mesh, in_specs=(state_specs,),
            # Same Anakin opt-out as systems/anakin.py: the in-shard
            # update-batch vmap axis' pmean fails check_vma's internal
            # assert (JAX limitation, not a spec bug).
            out_specs=state_specs, check_vma=False,
        )
    )
    return learn, warmup


def standard_off_policy_learner(
    env: envs.Environment,
    buffer: Any,
    config: Any,
    update_from_batch: Callable[[Any, Any, Any, jax.Array], Tuple[Tuple[Any, Any], dict]],
    act_in_env: Callable[[Any, Any, jax.Array], jax.Array],
) -> Callable:
    """Standard off-policy learner loop.

    update_from_batch(params, opt_states, batch, key) -> ((params, opt_states), metrics)
    act_in_env(params, observation, key, buffer_state) -> action — buffer_state
    enables training-progress schedules (e.g. epsilon decay keyed on
    buffer_state.num_added); implementations that don't need it take it as an
    unused parameter.
    """

    def _env_step(learner_state: OffPolicyLearnerState, _):
        params, opt_states, buffer_state, key, env_state, last_timestep = learner_state
        key, act_key = jax.random.split(key)
        action = act_in_env(
            params, last_timestep.observation, act_key, buffer_state=buffer_state
        )
        env_state, timestep = env.step(env_state, action)
        transition = make_transition(last_timestep, action, timestep)
        return (
            OffPolicyLearnerState(params, opt_states, buffer_state, key, env_state, timestep),
            transition,
        )

    def _update_epoch(carry, _):
        params, opt_states, buffer_state, key = carry
        key, sample_key, update_key = jax.random.split(key, 3)
        batch = buffer.sample(buffer_state, sample_key).experience
        (params, opt_states), metrics = update_from_batch(params, opt_states, batch, update_key)
        return (params, opt_states, buffer_state, key), metrics

    def _update_step(learner_state: OffPolicyLearnerState, _):
        learner_state, traj = jax.lax.scan(
            _env_step, learner_state, None, int(config.system.rollout_length)
        )
        params, opt_states, buffer_state, key, env_state, timestep = learner_state
        buffer_state = buffer.add(buffer_state, tree_merge_leading_dims(traj, 2))
        (params, opt_states, buffer_state, key), metrics = jax.lax.scan(
            _update_epoch, (params, opt_states, buffer_state, key), None,
            int(config.system.epochs),
        )
        learner_state = OffPolicyLearnerState(
            params, opt_states, buffer_state, key, env_state, timestep
        )
        return learner_state, (traj.info, metrics)

    def learner_fn(learner_state: OffPolicyLearnerState) -> ExperimentOutput:
        key = learner_state.key[0]
        state = learner_state._replace(key=key)
        state, (episode_info, loss_info) = jax.lax.scan(
            jax.vmap(_update_step, axis_name="batch"),
            state, None, int(config.arch.num_updates_per_eval),
        )
        state = state._replace(key=state.key[None])
        loss_info = jax.lax.pmean(loss_info, axis_name="data")
        return ExperimentOutput(state, episode_info, loss_info)

    return learner_fn


def pmean_grads(grads: Any) -> Any:
    grads = jax.lax.pmean(grads, axis_name="batch")
    return jax.lax.pmean(grads, axis_name="data")
