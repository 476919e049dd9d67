"""Sebulba DQN — the off-policy ingestion path (docs/DESIGN.md §2.10).

Actor devices run epsilon-greedy inference against stateful envs and PUSH
transition shards whenever a rollout chunk is ready; learner devices own a
device-resident sharded replay service (stoix_tpu/replay) and SAMPLE it
independently — no lockstep collect, so a slow or supervisor-restarting actor
never stalls the learner (Podracer's actor/learner core split, arxiv
2104.06272, applied to the DQN family). The host loop is the Sebulba
runner's (stoix_tpu/sebulba/runner.py) and the ingestion its `ReplaySource`
(stoix_tpu/sebulba/sources.py); this file is the DQN update, the actors'
act function and the replay service's set-up.

The learn step is one jitted shard_map program embedding the replay core's
cross-shard sampler: sample (a psum of the drawn minibatch is the only
experience bytes on the interconnect) -> Q-learning update -> polyak target
sync, with optional prioritized replay (per-TD-error priorities scattered
back through global indices, importance weights from the GLOBAL sampling
probabilities).
"""

from __future__ import annotations

import functools
import sys
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stoix_tpu.base_types import OnlineAndTarget, Transition
from stoix_tpu.ops import pick_along_last
from stoix_tpu.replay import ShardedReplayService, service_from_config
from stoix_tpu.resilience import guards
from stoix_tpu.sebulba import runner
from stoix_tpu.sebulba.runner import LAST_RUN_STATS  # noqa: F401 — read through this module
from stoix_tpu.sebulba.sources import ReplaySource
from stoix_tpu.systems.q_learning.q_family import act_dist, build_q_network
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.training import make_learning_rate


class DQNLearnerState(NamedTuple):
    params: OnlineAndTarget
    opt_state: Any
    key: jax.Array


def get_dqn_learn_step(
    q_apply, q_update, config: Any, mesh: Mesh, service: ShardedReplayService
):
    """One jitted shard_map program per update: sample the sharded replay
    where the data lives, Q-learning step, polyak target sync. The replay
    state threads through (donated — the ring is the device's largest
    allocation) so prioritized runs scatter fresh priorities in-program."""
    core = service.core
    gamma = float(config.system.gamma)
    tau = float(config.system.tau)
    epochs = int(config.system.epochs)
    replay_cfg = dict(config.system.get("replay") or {})
    prioritized = bool(replay_cfg.get("prioritized", False))
    beta = float(replay_cfg.get("importance_beta", 0.4))
    guard_mode = guards.resolve_mode(config)

    def per_shard(state: DQNLearnerState, replay_state):
        rstate = jax.tree.map(lambda x: x[0], replay_state)

        def _epoch(carry, _):
            state, rstate = carry
            key, sample_key = jax.random.split(state.key)
            # state.key is replicated (in_specs P()), so every shard draws
            # the same uniforms — the core's ownership-partition contract.
            drawn = core.sample(rstate, sample_key)
            batch: Transition = drawn.experience

            if prioritized:
                # PER importance weights from the GLOBAL sampling
                # probabilities (the psum'd normalization), so the
                # correction is exact however mass is spread over shards.
                # A zero-probability row (zeroed priority resampled before
                # its slot was overwritten) contributes NOTHING — the
                # (N*p)^-beta form would instead hand it the batch's
                # LARGEST weight and flatten every real row to ~0 through
                # the max-normalization.
                n_global = jax.lax.psum(core.occupancy(rstate), "data")
                w = jnp.where(
                    drawn.probabilities > 0,
                    jnp.power(
                        jnp.maximum(n_global.astype(jnp.float32), 1.0)
                        * jnp.maximum(drawn.probabilities, 1e-9),
                        -beta,
                    ),
                    0.0,
                )
                w = w / jnp.maximum(jax.lax.pmax(jnp.max(w), "data"), 1e-9)
            else:
                w = jnp.ones_like(batch.reward)

            def loss_fn(online):
                q_tm1 = q_apply(online, batch.obs, 0.0).preferences
                q_t = q_apply(state.params.target, batch.next_obs, 0.0).preferences
                d_t = gamma * (1.0 - batch.done.astype(jnp.float32))
                target = batch.reward + d_t * jnp.max(q_t, axis=-1)
                qa = pick_along_last(q_tm1, batch.action)
                td = jax.lax.stop_gradient(target) - qa
                loss = 0.5 * jnp.mean(w * jnp.square(td))
                return loss, (td, jnp.mean(q_tm1))

            (loss, (td, mean_q)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params.online
            )
            grads = jax.lax.pmean(grads, axis_name="data")
            updates, opt_state = q_update(grads, state.opt_state)
            online = optax.apply_updates(state.params.online, updates)
            target = optax.incremental_update(online, state.params.target, tau)
            (params, opt_state), guard_metrics = guards.guard_update(
                guard_mode,
                new=(OnlineAndTarget(online, target), opt_state),
                old=(state.params, state.opt_state),
                loss=loss,
                grads=grads,
                opt_state=state.opt_state,
                axis_names=("data",),
            )
            if prioritized:
                rstate = core.set_priorities(rstate, drawn.indices, jnp.abs(td))
            metrics = {"q_loss": loss, "mean_q": mean_q, **guard_metrics}
            return (DQNLearnerState(params, opt_state, key), rstate), metrics

        (state, rstate), metrics = jax.lax.scan(
            _epoch, (state, rstate), None, epochs
        )
        metrics = jax.lax.pmean(metrics, axis_name="data")
        return state, jax.tree.map(lambda x: x[None], rstate), metrics

    return jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(), P("data")),
            out_specs=(P(), P("data"), P()),
            check_vma=False,
        ),
        donate_argnums=(1,),
    )


def get_act_fn(q_apply, epsilon: float):
    @jax.jit
    def act_fn(params, observation, key):
        return (act_dist(q_apply(params, observation, epsilon)).sample(seed=key),)

    return act_fn


def transition(obs: Any, act_out: Any, next_timestep: Any) -> Transition:
    return Transition(
        obs=obs,
        action=act_out[0],
        reward=next_timestep.reward,
        done=next_timestep.discount == 0.0,
        next_obs=next_timestep.extras["next_obs"],
        # For the actor's metrics sink; the source drops it from what it pushes.
        info=next_timestep.extras["episode_metrics"],
    )


def _init_networks(config: Any, probe_envs: Any, key: jax.Array):
    q_network = build_q_network(config, probe_envs.num_actions)
    key, net_key, learn_key = jax.random.split(key, 3)
    obs0 = jax.tree.map(lambda x: jnp.asarray(x), probe_envs.reset(seed=0).observation)
    return (q_network, q_network.init(net_key, obs0), obs0, learn_key), key


def _setup_learner(config: Any, networks: Any, key: jax.Array, learner_mesh: Mesh):
    q_network, online_params, obs0, learn_key = networks
    n_learners = learner_mesh.devices.size
    chunk = int(config.arch.actor.envs_per_actor) * int(config.system.rollout_length)
    if chunk % n_learners != 0:
        raise ValueError(
            f"envs_per_actor * rollout_length ({chunk}) must divide over "
            f"{n_learners} learner device(s) for shard-wise ingestion"
        )
    q_optim = optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(
            make_learning_rate(float(config.system.q_lr), config, int(config.system.epochs)),
            eps=1e-5,
        ),
    )
    state = jax.device_put(
        DQNLearnerState(
            OnlineAndTarget(online_params, online_params), q_optim.init(online_params), learn_key
        ),
        NamedSharding(learner_mesh, P()),
    )

    # Replay service: buffer state sharded across learner HBM. The item
    # prototype is one UNBATCHED transition from the probe env.
    obs_single = jax.tree.map(lambda x: x[0], obs0)
    item = Transition(
        obs=obs_single,
        action=jnp.zeros((), jnp.int32),
        reward=jnp.zeros((), jnp.float32),
        done=jnp.zeros((), bool),
        next_obs=obs_single,
        info={},
    )
    service = service_from_config(learner_mesh, item, config)
    if service is None:
        raise ValueError(
            "Sebulba ff_dqn ingests through the sharded replay service: set "
            "system.replay.impl=sharded (the local item buffer lives inside "
            "Anakin's jitted learner and has no ingestion seam)"
        )
    eval_eps = float(config.system.evaluation_epsilon)
    param_sync = int(dict(config.system.get("replay") or {}).get("param_sync_interval", 1))
    learner = runner.Learner(
        state=state,
        step=get_dqn_learn_step(q_network.apply, q_optim.update, config, learner_mesh, service),
        make_source=functools.partial(
            ReplaySource, service=service, epochs=int(config.system.epochs),
            param_sync_interval=max(1, param_sync),
        ),
        make_act_fn=functools.partial(
            get_act_fn, q_network.apply, float(config.system.training_epsilon)
        ),
        transition=transition,
        actor_params=lambda state: state.params.online,
        eval_params=lambda state: state.params.online,
        eval_apply=lambda p, observation: act_dist(q_network.apply(p, observation, eval_eps)),
    )
    return learner, key


def run_experiment(config: Any) -> float:
    return runner.run_experiment(config, runner.SebulbaSystem(_init_networks, _setup_learner))


def main() -> float:
    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_dqn.yaml",
        sys.argv[1:],
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
