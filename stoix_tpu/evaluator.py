"""Evaluator (reference stoix/evaluator.py:87-416).

Runs `num_eval_episodes` episodes to completion (lax.while_loop keyed on
timestep.last(), reference evaluator.py:152) with episodes vmapped within each
shard and sharded over the mesh's data axis via shard_map — the TPU-native
replacement for the reference's pmapped evaluator. The absolute-metric
evaluator is the same function with eval_multiplier=10.

Caveat preserved from the reference (README.md:197): non-terminating envs make
the while_loop spin forever — give eval envs a step limit.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu.envs.core import Environment
from stoix_tpu.envs.types import _bcast

# act_fn(params, observation, key) -> action  (single unbatched observation)
ActFn = Callable[[Any, Any, jax.Array], jax.Array]


class _EvalCarry(NamedTuple):
    env_state: Any
    timestep: Any
    key: jax.Array


def get_distribution_act_fn(
    config: Any,
    actor_apply: Callable[..., Any],
    rngs: Optional[Dict[str, jax.Array]] = None,
) -> ActFn:
    """Greedy (mode) or sampled acting from a distribution-returning network
    (reference evaluator.py:48-67)."""

    greedy = bool(config.arch.get("evaluation_greedy", False))

    def act(params: Any, observation: Any, key: jax.Array) -> jax.Array:
        if rngs is None:
            dist = actor_apply(params, observation)
        else:
            dist = actor_apply(params, observation, rngs=rngs)
        return dist.mode() if greedy else dist.sample(seed=key)

    return act


def _make_eval_reset_fn(eval_env: Environment, config: Any):
    """Episode-reset function for evaluation: (key, episode_index) -> (state, ts).

    By default the env's own reset. An env-specific override (e.g. fixed
    evaluation levels, the reference's kinetix hook at evaluator.py:365-372)
    is instantiated from config.env.eval_reset_fn as either
      callable(env, key) -> (state, timestep), or
      callable(env, key, episode_index) -> (state, timestep)
    — the 3-arg form receives the global episode index so hooks can tile a
    fixed level list deterministically across episodes (see
    make_tiled_eval_reset_fn; reference wrappers/kinetix.py:15-51)."""
    hook_cfg = config.env.get("eval_reset_fn")
    if not hook_cfg:
        return lambda key, idx: eval_env.reset(key)
    import inspect

    from stoix_tpu.utils.config import instantiate

    hook = instantiate(hook_cfg)
    try:
        n_params = len(inspect.signature(hook).parameters)
    except (TypeError, ValueError):
        n_params = 2
    if n_params >= 3:
        return lambda key, idx: hook(eval_env, key, idx)
    return lambda key, idx: hook(eval_env, key)


def make_tiled_eval_reset_fn(levels: Any):
    """Eval-reset hook that cycles a fixed list of levels across episodes
    (the reference's kinetix list-mode eval reset, wrappers/kinetix.py:15-51,
    generalized to any env exposing reset_to_level(level, key)).

    `levels` is a sequence of per-level values — scalars, arrays, or pytrees
    (kinetix-style level states) — or an already-stacked pytree whose leaves
    have a leading level axis. Episode i resets to level i % n_levels, so with
    num_eval_episodes a multiple of n_levels every level is evaluated equally
    often.
    """
    import numpy as np

    if isinstance(levels, (list, tuple)):
        stacked = jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *levels)
        n_levels = len(levels)
    else:
        stacked = levels
        n_levels = int(np.asarray(jax.tree.leaves(levels)[0]).shape[0])

    def hook(env: Environment, key: jax.Array, episode_index: jax.Array):
        level = jax.tree.map(lambda x: x[episode_index % n_levels], stacked)
        return env.reset_to_level(level, key)

    return hook


def get_ff_evaluator_fn(
    eval_env: Environment,
    act_fn: ActFn,
    config: Any,
    mesh: Mesh,
    eval_multiplier: int = 1,
):
    """Build the sharded evaluator: (params, key) -> episode metrics dict with
    leaves shaped [global_eval_episodes]."""

    n_shards = int(mesh.shape["data"])
    episodes_global = int(config.arch.num_eval_episodes) * eval_multiplier
    if episodes_global % n_shards != 0:
        episodes_global = ((episodes_global // n_shards) + 1) * n_shards
    per_shard = episodes_global // n_shards
    reset_fn = _make_eval_reset_fn(eval_env, config)
    # Fixed-trip-count episode loop (SURVEY §7.3.6): under vmap, a while_loop
    # runs every episode until the LONGEST one ends (divergence cost); with a
    # known step limit a lax.scan with result masking is fully static and
    # TPU-friendly. Enabled via arch.eval_max_steps.
    eval_max_steps = config.arch.get("eval_max_steps")

    def eval_one_episode(params: Any, key: jax.Array, idx: jax.Array) -> Dict[str, jax.Array]:
        reset_key, act_key = jax.random.split(key)
        env_state, timestep = reset_fn(reset_key, idx)

        def body(carry: _EvalCarry) -> _EvalCarry:
            key, act_key = jax.random.split(carry.key)
            action = act_fn(params, carry.timestep.observation, act_key)
            env_state, timestep = eval_env.step(carry.env_state, action)
            return _EvalCarry(env_state, timestep, key)

        if eval_max_steps:

            def scan_body(carry: _EvalCarry, _):
                stepped = body(carry)
                # Freeze the carry once the episode has ended; the env is
                # still stepped but its results are discarded, keeping the
                # trip count static for XLA.
                done = carry.timestep.last()  # scalar — broadcasts over leaves
                frozen = jax.tree.map(lambda a, b: jnp.where(done, a, b), carry, stepped)
                return frozen, None

            final, _ = jax.lax.scan(
                scan_body, _EvalCarry(env_state, timestep, act_key), None,
                int(eval_max_steps),
            )
            # Episodes still running at the step cap are truncated AT the cap:
            # their running return/length are reported as-is, and the
            # episode_finished metric surfaces how many were cut short (a
            # mean < 1.0 in the logs means eval_max_steps is too small for
            # this env — not a silent condition).
            finished = final.timestep.last()
        else:

            def cond(carry: _EvalCarry) -> jax.Array:
                return ~carry.timestep.last()

            final = jax.lax.while_loop(cond, body, _EvalCarry(env_state, timestep, act_key))
            finished = jnp.ones((), bool)
        metrics = final.timestep.extras["episode_metrics"]
        return {
            "episode_return": metrics["episode_return"],
            "episode_length": metrics["episode_length"],
            "episode_finished": finished.astype(jnp.float32),
        }

    def _shard_eval(params: Any, keys: jax.Array, idxs: jax.Array) -> Dict[str, jax.Array]:
        return jax.vmap(eval_one_episode, in_axes=(None, 0, 0))(params, keys, idxs)

    sharded = jax.jit(
        jax.shard_map(
            _shard_eval,
            mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=P("data"),
            check_vma=False,  # while_loop carries mix replicated and varying leaves
        )
    )

    def evaluator(params: Any, key: jax.Array) -> Dict[str, jax.Array]:
        keys = jax.random.split(key, episodes_global)
        return sharded(params, keys, jnp.arange(episodes_global))

    # Pure-JAX and stateless: the runner may inline this into the jitted learn
    # program under arch.fused_eval (RNN/stateful evaluators never set this —
    # they fall back to the snapshot-overlap path, systems/runner.py).
    evaluator.supports_fusion = True
    return evaluator


def get_stateful_evaluator_fn(env_factory: Any, act_fn: ActFn, config: Any):
    """Evaluator for stateful env backends with no JAX twin (EnvPool /
    Gymnasium pools): drives one vectorized pool host-side until
    `arch.num_eval_episodes` episodes conclude, acting through the same
    act_fn as the sharded evaluator. Returns the same metrics contract
    ({"episode_return": [episodes]}), so AsyncEvaluator and the run loop are
    agnostic to which evaluator backs them (the reference's Sebulba evaluates
    EnvPool Atari on factory envs the same way, stoix/evaluator.py)."""
    import numpy as np

    episodes_needed = int(config.arch.num_eval_episodes)
    envs = env_factory(episodes_needed)
    jit_act = jax.jit(act_fn)
    # Host-loop safety cap: generous multiple of any sane episode length so a
    # never-terminating pool cannot hang the evaluator thread.
    max_host_steps = int(config.arch.get("eval_max_steps") or 0) or 100_000

    def evaluator(params: Any, key: jax.Array) -> Dict[str, jax.Array]:
        ts = envs.reset()
        returns: list = []
        for _ in range(max_host_steps):
            if len(returns) >= episodes_needed:
                break
            key, act_key = jax.random.split(key)
            action = jit_act(params, ts.observation, act_key)
            ts = envs.step(np.asarray(action))
            em = ts.extras["episode_metrics"]
            concluded = np.asarray(em["is_terminal_step"]).astype(bool)
            returns.extend(np.asarray(em["episode_return"])[concluded].tolist())
        if not returns:
            returns = [float("nan")]  # visible in logs, never silently zero
        return {"episode_return": jnp.asarray(returns[:episodes_needed])}

    return evaluator


def get_rnn_evaluator_fn(
    eval_env: Environment,
    act: Callable[..., Tuple[Any, jax.Array]],
    config: Any,
    mesh: Mesh,
    init_carry: Callable[[int], Any],
    eval_multiplier: int = 1,
    start_carry: Optional[Callable[[Any, Any], Any]] = None,
):
    """Evaluator for a policy that carries state through the episode
    (reference evaluator.py:209-344): an RNN's hidden vector, a context
    window, a KV cache — any pytree the system's core declares.

    The core acts for a shard's E episodes together: `act(params, carry,
    obs [E, ...], done [E], keys [E, 2]) -> (carry, action [E])` and
    `init_carry(E)` — which is what a policy whose layers work on the whole
    batch (a sort of all tokens by expert) needs. A core written for ONE
    episode is vmapped by its caller (`per_episode_evaluator_setup`).

    A core whose first carry depends on the policy and on the episodes (a
    cache prefilled with each episode's prompt) gives `start_carry(params,
    the shard's reset env states [E, ...]) -> carry`, which then stands in
    for `init_carry(E)`.

    Episodes run until the longest ends. A finished episode's env state,
    timestep and key are frozen; its carry is not (nothing reads it again),
    so a large carry costs no select a step."""

    n_shards = int(mesh.shape["data"])
    episodes_global = int(config.arch.num_eval_episodes) * eval_multiplier
    if episodes_global % n_shards != 0:
        episodes_global = ((episodes_global // n_shards) + 1) * n_shards
    per_shard = episodes_global // n_shards

    reset_fn = _make_eval_reset_fn(eval_env, config)

    def _shard_eval(params: Any, keys: jax.Array, idxs: jax.Array) -> Dict[str, jax.Array]:
        split = jax.vmap(jax.random.split)(keys)  # [E, 2, 2]
        env_state, timestep = jax.vmap(reset_fn)(split[:, 0], idxs)

        def cond(carry) -> jax.Array:
            return jnp.any(~carry[0][1].last())

        def body(carry):
            (env_state, timestep, key), hstate = carry
            split = jax.vmap(jax.random.split)(key)
            hstate, action = act(
                params, hstate, timestep.observation, timestep.last(), split[:, 1]
            )
            stepped = jax.vmap(eval_env.step)(env_state, action)
            running = ~timestep.last()
            frozen = jax.tree.map(
                lambda new, old: jnp.where(_bcast(running, new), new, old),
                (*stepped, split[:, 0]), (env_state, timestep, key),
            )
            return frozen, hstate

        hstate = init_carry(per_shard) if start_carry is None else start_carry(params, env_state)
        final, _ = jax.lax.while_loop(cond, body, ((env_state, timestep, split[:, 1]), hstate))
        metrics = final[1].extras["episode_metrics"]
        return {
            "episode_return": metrics["episode_return"],
            "episode_length": metrics["episode_length"],
        }

    sharded = jax.jit(
        jax.shard_map(
            _shard_eval, mesh=mesh, in_specs=(P(), P("data"), P("data")), out_specs=P("data"),
            check_vma=False,
        )
    )

    def evaluator(params: Any, key: jax.Array) -> Dict[str, jax.Array]:
        keys = jax.random.split(key, episodes_global)
        return sharded(params, keys, jnp.arange(episodes_global))

    return evaluator


def carry_evaluator_setup(init_carry: Optional[Callable[[int], Any]] = None):
    """The `evaluator_setup_fn` of a system whose policy carries state:
    (evaluator, absolute-metric evaluator) over `get_rnn_evaluator_fn`.
    Without `init_carry` the carry is the one the system's `act_fn` declares
    (`act_fn.init_carry`: what its network built, known only once the
    learner is set up); an `act_fn.start_carry`, where the system declares
    one, is `get_rnn_evaluator_fn`'s."""

    def setup(eval_env: Environment, act_fn: Any, config: Any, mesh: Mesh) -> Tuple[Any, Any]:
        init = init_carry or act_fn.init_carry
        make = lambda multiplier: get_rnn_evaluator_fn(
            eval_env, act_fn, config, mesh, init, eval_multiplier=multiplier,
            start_carry=getattr(act_fn, "start_carry", None),
        )
        return make(1), make(int(config.arch.get("absolute_metric_multiplier", 10)))

    return setup


def per_episode_evaluator_setup(init_one: Callable[[], Any]):
    """`carry_evaluator_setup` for a core written for ONE episode —
    `act_fn(params, carry, obs, done, key) -> (carry, action)`, `init_one()`
    one episode's carry (an RNN cell, a context window): both are vmapped over
    the shard's episodes here, at the call site."""
    batched = carry_evaluator_setup(lambda n: jax.vmap(lambda _: init_one())(jnp.arange(n)))

    def setup(eval_env: Environment, act_fn: Any, config: Any, mesh: Mesh) -> Tuple[Any, Any]:
        return batched(eval_env, jax.vmap(act_fn, in_axes=(None, 0, 0, 0, 0)), config, mesh)

    return setup


def evaluator_setup(
    eval_env: Environment,
    act_fn: ActFn,
    config: Any,
    mesh: Mesh,
) -> Tuple[Any, Any]:
    """Returns (evaluator, absolute_metric_evaluator) — the latter runs
    eval_multiplier x episodes (reference evaluator.py:347-416)."""
    evaluator = get_ff_evaluator_fn(eval_env, act_fn, config, mesh)
    absolute_evaluator = get_ff_evaluator_fn(
        eval_env,
        act_fn,
        config,
        mesh,
        eval_multiplier=int(config.arch.get("absolute_metric_multiplier", 10)),
    )
    return evaluator, absolute_evaluator
