"""Population runner: P agents as ONE jitted program on a ("pop", "data")
mesh, dispatched by the unchanged pipelined Anakin host loop
(docs/DESIGN.md §2.11).

Layout (P = population, S = data shards per member, U = update batch):

  members.params/opt_states:    [P, U, ...]       P("pop")
  members.key:                  [P, S, U, 2]      P("pop", "data")
  members.env_state/timestep:   [P, U, S*E, ...]  P("pop", None, "data")
  hparams[name] / fitness:      [P]               P("pop")
  updates_done/pbt_key/exploit: scalars           P()   (replicated)

The per-member learner is ff_ppo's OWN `get_learner_fn`, called inside the
vmapped member function with that member's traced hparam scalars — so one
compiled program trains P members with different lr/ent_coef/gamma/... Each
member keeps its own optax state and PRNG stream. When the local pop slice
is a single member (pop axis fully sharded, or P=1), the vmap is elided
entirely — squeeze -> plain per-shard learner -> unsqueeze — which is what
makes the population-of-1 trajectory BIT-identical to the plain Anakin
ff_ppo run (pinned, tests/test_population.py).

Fitness (the psum-consistent mean completed-episode return of the window)
updates inside the program; PBT exploit/explore (population/pbt.py) composes
into the SAME jitted program behind `arch.population.pbt.enabled`, so
selection costs zero host round-trips. Per-member episode metrics and
fitness ride the runner's existing coalesced metric fetch; eval snapshots
serve the currently-fittest member through the standard evaluator.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams, ExperimentOutput
from stoix_tpu.evaluator import get_distribution_act_fn
from stoix_tpu.observability import RunStats, get_logger
from stoix_tpu.ops import running_statistics
from stoix_tpu.parallel import is_coordinator, materialize
from stoix_tpu.population import hparams as hparams_lib
from stoix_tpu.population import pbt as pbt_lib
from stoix_tpu.systems import anakin
from stoix_tpu.systems.ppo.anakin import ff_ppo
from stoix_tpu.systems.runner import AnakinSetup, run_anakin_experiment, _tree_copy
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.training import make_learning_rate

import optax


class PopulationState(NamedTuple):
    """The whole population as one pytree: stacked member learner states plus
    the lifted hparams, fitness, and PBT bookkeeping."""

    members: Any  # ff_ppo.PPOLearnerState with a leading [P] axis
    hparams: Dict[str, jax.Array]  # name -> [P]
    fitness: jax.Array  # [P] f32; -inf until a member completes an episode
    updates_done: jax.Array  # scalar int32 window counter (replicated)
    pbt_key: jax.Array  # [2] uint32 (replicated)
    exploit_total: jax.Array  # scalar int32 cumulative exploited members


# Stats of the most recent run_population_experiment in this process:
# population_size, member_fitness [P], hparams {name: [P]}, pbt_exploits,
# pbt_enabled. bench.py --population and sweep.py --backend population read
# this after the run; values are host numpy (materialized once, at run end).
LAST_POPULATION_STATS = RunStats()


def _validate_population_config(config: Any, mesh: Any) -> None:
    if "pop" not in mesh.axis_names:
        raise hparams_lib.PopulationConfigError(
            f"population training needs a 'pop' mesh axis; arch.mesh declares "
            f"{dict(mesh.shape)} — compose with arch=population (or add pop "
            "to arch.mesh)"
        )
    if bool(((config.get("arch") or {}).get("integrity") or {}).get("enabled", False)):
        raise hparams_lib.PopulationConfigError(
            "arch.integrity.enabled=true is not supported under population "
            "training yet: the sentinel's replica fingerprints assume "
            "replicated state, but population members are SHARDED over the "
            "pop axis — use arch.population.member_fingerprints plus "
            "population.pbt.quarantine_members (docs/DESIGN.md §2.11)"
        )
    if bool(config.arch.get("fused_eval", False)):
        raise hparams_lib.PopulationConfigError(
            "arch.fused_eval is not supported under population training (the "
            "evaluator serves the argmax-fitness member, selected per window)"
        )


def population_setup(
    env: envs.Environment, config: Any, mesh: Any, keys: jax.Array
) -> AnakinSetup:
    """Build the population learner state + ONE jitted learn program.

    Matches the AnakinSetup contract, so systems/runner.py dispatches it
    exactly like any single-agent learner."""
    import os

    _validate_population_config(config, mesh)
    pop_size, hp_arrays = hparams_lib.lift_hparams(config)
    pop_shards = int(mesh.shape["pop"])
    if pop_size % pop_shards != 0:
        raise hparams_lib.PopulationConfigError(
            f"arch.population.size ({pop_size}) must divide over the pop mesh "
            f"axis ({pop_shards} shard(s))"
        )
    p_local = pop_size // pop_shards
    learner_hp = hparams_lib.learner_hparams(hp_arrays)
    lr_threaded = "actor_lr" in learner_hp or "critic_lr" in learner_hp
    if lr_threaded and bool(config.system.get("decay_learning_rates", False)):
        raise hparams_lib.PopulationConfigError(
            "system.decay_learning_rates cannot combine with a lifted "
            "actor_lr/critic_lr: per-member learning rates are flat scalars"
        )

    config.system.action_dim = env.num_actions
    actor_network, critic_network = ff_ppo.build_networks(env, config)

    # Optimizers: when lr is lifted onto the pop axis the chain ends at
    # scale_by_adam and get_learner_fn applies `u * (-lr)` per member —
    # bitwise the multiply optax's scale(-lr) performs. Otherwise the chain
    # is exactly learner_setup's (config lr, schedules included).
    max_grad_norm = float(config.system.max_grad_norm)
    if "actor_lr" in learner_hp:
        actor_optim = optax.chain(
            optax.clip_by_global_norm(max_grad_norm),
            optax.scale_by_adam(eps=1e-5),
        )
    else:
        actor_lr = make_learning_rate(
            float(config.system.actor_lr), config, int(config.system.epochs),
            int(config.system.num_minibatches),
        )
        actor_optim = optax.chain(
            optax.clip_by_global_norm(max_grad_norm), optax.adam(actor_lr, eps=1e-5)
        )
    if "critic_lr" in learner_hp:
        critic_optim = optax.chain(
            optax.clip_by_global_norm(max_grad_norm),
            optax.scale_by_adam(eps=1e-5),
        )
    else:
        critic_lr = make_learning_rate(
            float(config.system.critic_lr), config, int(config.system.epochs),
            int(config.system.num_minibatches),
        )
        critic_optim = optax.chain(
            optax.clip_by_global_norm(max_grad_norm), optax.adam(critic_lr, eps=1e-5)
        )
    apply_fns = (actor_network.apply, critic_network.apply)
    update_fns = (actor_optim.update, critic_optim.update)

    # --- per-member state construction (host loop over P; P is small) -------
    # Member 0's key path is EXACTLY learner_setup's (the population-of-1
    # bit-identity pin); members p>0 fold_in(p) — or, when arch.seed is
    # lifted, each member restarts the full key path from PRNGKey(seed_p).
    seeds = hp_arrays.get("seed")
    update_batch = int(config.arch.get("update_batch_size", 1))
    dummy_obs = jax.tree.map(lambda x: x[None], env.observation_value())
    obs_stats0 = running_statistics.init_state(env.observation_value().agent_view)
    kl_beta0 = jnp.asarray(float(config.system.get("kl_beta", 3.0)))
    member_states = []
    for p in range(pop_size):
        if seeds is not None:
            _, member_key = jax.random.split(jax.random.PRNGKey(int(seeds[p])))
        elif p == 0:
            member_key = keys
        else:
            member_key = jax.random.fold_in(keys, p)
        key_p, actor_key, critic_key, env_key = jax.random.split(member_key, 4)
        actor_params = actor_network.init(actor_key, dummy_obs)
        critic_params = critic_network.init(critic_key, dummy_obs)
        env_state, timestep = anakin.reset_envs_for_anakin(env, config, env_key)
        member_states.append(
            ff_ppo.PPOLearnerState(
                params=anakin.broadcast_to_update_batch(
                    ActorCriticParams(actor_params, critic_params), update_batch
                ),
                opt_states=anakin.broadcast_to_update_batch(
                    ActorCriticOptStates(
                        actor_optim.init(actor_params), critic_optim.init(critic_params)
                    ),
                    update_batch,
                ),
                key=anakin.make_step_keys(key_p, mesh, config),
                env_state=env_state,
                timestep=timestep,
                obs_stats=anakin.broadcast_to_update_batch(obs_stats0, update_batch),
                kl_beta=anakin.broadcast_to_update_batch(kl_beta0, update_batch),
            )
        )
    members = jax.tree.map(lambda *xs: jnp.stack(xs), *member_states)

    pop_state = PopulationState(
        members=members,
        hparams={k: jnp.asarray(v) for k, v in learner_hp.items()},
        fitness=jnp.full((pop_size,), -jnp.inf, dtype=jnp.float32),
        updates_done=jnp.asarray(0, dtype=jnp.int32),
        pbt_key=jax.random.fold_in(keys, 0x5B7),
        exploit_total=jnp.asarray(0, dtype=jnp.int32),
    )

    member_specs = ff_ppo.PPOLearnerState(
        params=P("pop"),
        opt_states=P("pop"),
        key=P("pop", "data"),
        env_state=P("pop", None, "data"),
        timestep=P("pop", None, "data"),
        obs_stats=P("pop"),
        kl_beta=P("pop"),
    )
    pop_specs = PopulationState(
        members=member_specs,
        hparams=P("pop"),
        fitness=P("pop"),
        updates_done=P(),
        pbt_key=P(),
        exploit_total=P(),
    )
    pop_state = anakin.place_learner_state(pop_state, mesh, pop_specs)

    fingerprint_members = bool(
        ((config.get("arch") or {}).get("population") or {}).get(
            "member_fingerprints", False
        )
    )
    settings = pbt_lib.settings_from_config(config)
    pbt_step = pbt_lib.make_pbt_step(settings, pop_size) if settings.enabled else None

    def per_shard_learn(state: PopulationState) -> ExperimentOutput:
        def member_learn(member_state: Any, member_hp: Dict[str, Any]):
            fn = ff_ppo.get_learner_fn(
                env, apply_fns, update_fns, config, hparams=member_hp
            )
            return fn(member_state)

        if p_local == 1:
            # Squeeze -> plain per-shard learner -> unsqueeze: reshapes only,
            # so a population of one trains BIT-identically to plain ff_ppo
            # (and a fully-sharded pop axis pays zero vmap overhead).
            m1 = jax.tree.map(lambda x: x[0], state.members)
            h1 = {k: v[0] for k, v in state.hparams.items()}
            out = member_learn(m1, h1)
            out = jax.tree.map(lambda x: x[None], out)
        else:
            out = jax.vmap(member_learn)(state.members, state.hparams)

        # Fitness: mean completed-episode return of this window, psummed over
        # the data axis so every data shard agrees; members with no completed
        # episode keep their previous fitness.
        info = out.episode_metrics
        ret = info["episode_return"]
        mask = info["is_terminal_step"].astype(jnp.float32)
        reduce_axes = tuple(range(1, ret.ndim))
        total = jax.lax.psum(jnp.sum(ret * mask, axis=reduce_axes), axis_name="data")
        count = jax.lax.psum(jnp.sum(mask, axis=reduce_axes), axis_name="data")
        fitness = jnp.where(
            count > 0, total / jnp.maximum(count, 1.0), state.fitness
        )
        new_state = state._replace(
            members=out.learner_state,
            fitness=fitness,
            updates_done=state.updates_done + 1,
        )
        train_metrics = dict(out.train_metrics)
        train_metrics["member_fitness"] = fitness
        if fingerprint_members:
            train_metrics["member_fingerprint"] = pbt_lib.member_fingerprints(
                out.learner_state.params
            )
        return ExperimentOutput(
            learner_state=new_state,
            episode_metrics=out.episode_metrics,
            train_metrics=train_metrics,
        )

    learn_sm = jax.shard_map(
        per_shard_learn,
        mesh=mesh,
        in_specs=(pop_specs,),
        out_specs=ExperimentOutput(
            learner_state=pop_specs,
            episode_metrics=P("pop", None, None, None, "data"),
            train_metrics=P("pop"),
        ),
        # Same Anakin opt-out as systems/anakin.py shardmap_learner: the
        # in-member update-batch vmap's pmean trips check_vma's
        # varying-manual-axes assert.
        check_vma=False,
    )

    def _full_step(state: PopulationState) -> ExperimentOutput:
        out = learn_sm(state)
        if pbt_step is not None:
            # Exploit/explore composes INTO the same program: gather/where
            # over the (possibly sharded) pop axis, partitioned by GSPMD —
            # zero host round-trips per selection round.
            out = out._replace(learner_state=pbt_step(out.learner_state))
        return out

    donate = {} if os.environ.get("STOIX_TPU_NO_DONATE") else {"donate_argnums": (0,)}
    learn = jax.jit(_full_step, **donate)

    # --- evaluation: serve the currently-fittest member ---------------------
    normalize_obs = bool(config.system.get("normalize_observations", False))

    def _best_member(state: PopulationState) -> jax.Array:
        fit = jnp.where(jnp.isfinite(state.fitness), state.fitness, -jnp.inf)
        return jnp.argmax(fit)

    if normalize_obs:

        def eval_apply(bundle, observation):
            params, stats = bundle
            observation = running_statistics.normalize_observation(observation, stats)
            return actor_network.apply(params, observation)

        eval_act_fn = get_distribution_act_fn(config, eval_apply)

        def eval_params_fn(state: PopulationState):
            best = _best_member(state)
            return (
                jax.tree.map(lambda x: x[best, 0], state.members.params.actor_params),
                jax.tree.map(lambda x: x[best, 0], state.members.obs_stats),
            )

    else:
        eval_act_fn = get_distribution_act_fn(config, actor_network.apply)

        def eval_params_fn(state: PopulationState):
            best = _best_member(state)
            return jax.tree.map(
                lambda x: x[best, 0], state.members.params.actor_params
            )

    if is_coordinator():
        get_logger("stoix_tpu.population").info(
            "[population] %d member(s) | mesh %s | lifted hparams: %s | pbt %s",
            pop_size, dict(mesh.shape), sorted(learner_hp) or "none",
            "on" if settings.enabled else "off",
        )

    from stoix_tpu.population import elastic as elastic_lib

    return AnakinSetup(
        learn=learn,
        learner_state=pop_state,
        eval_act_fn=eval_act_fn,
        eval_params_fn=eval_params_fn,
        # Elastic restore (docs/DESIGN.md §2.14): an emergency store saved by
        # a DIFFERENT population size is re-placed onto this one before tree
        # placement — identity when the sizes already agree.
        restore_transform=elastic_lib.raw_resize_transform(config),
    )


def run_population_experiment(config: Any) -> float:
    """Train a population through the pipelined Anakin dispatcher; returns
    the final eval episode-return mean (of the fittest member) and fills
    LAST_POPULATION_STATS with per-member results."""
    holder: Dict[str, Any] = {}
    pop_size = hparams_lib.population_size(config)

    def recording_setup(env, cfg, mesh, key):
        setup = population_setup(env, cfg, mesh, key)
        inner = setup.learn

        def _capture(out):
            # Donation-safe per-window capture: a jitted on-device COPY of
            # the tiny per-member summary, enqueued BEFORE the next learn
            # dispatch can donate the state (the snapshot-vs-donation
            # invariant, systems/anakin.py). Materialized ONCE, at run end.
            holder["summary"] = _tree_copy(
                {
                    "fitness": out.learner_state.fitness,
                    "hparams": out.learner_state.hparams,
                    "exploit_total": out.learner_state.exploit_total,
                    "updates_done": out.learner_state.updates_done,
                }
            )

        def learn(state):
            out = inner(state)
            _capture(out)
            return out

        def lower(state):
            # Forward AOT lowering to the real jit (the runner's aot_warmup
            # would otherwise silently degrade on this wrapper and push the
            # whole compile into window 0), wrapping the compiled executable
            # so per-window capture survives warmup.
            lowered = inner.lower(state)

            class _RecordingLowered:
                @staticmethod
                def compile():
                    compiled = lowered.compile()

                    def run(s):
                        out = compiled(s)
                        _capture(out)
                        return out

                    return run

            return _RecordingLowered()

        learn.lower = lower
        return setup._replace(learn=learn)

    final_return = run_anakin_experiment(config, recording_setup)

    LAST_POPULATION_STATS.clear()
    LAST_POPULATION_STATS["population_size"] = pop_size
    LAST_POPULATION_STATS["pbt_enabled"] = pbt_lib.settings_from_config(config).enabled
    if holder:
        summary = materialize(holder["summary"])
        LAST_POPULATION_STATS.update(
            {
                "member_fitness": [float(v) for v in np.asarray(summary["fitness"])],
                "hparams": {
                    k: [float(v) for v in np.asarray(a)]
                    for k, a in summary["hparams"].items()
                },
                "pbt_exploits": int(summary["exploit_total"]),
                "windows": int(summary["updates_done"]),
            }
        )
    return final_return


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/population/default_ff_ppo.yaml",
        sys.argv[1:],
    )
    return run_population_experiment(config)


if __name__ == "__main__":
    main()
