"""Anakin learner-construction helpers shared by system files.

Encapsulates the mesh/layout conventions every Anakin system uses
(see ff_ppo.py module docstring for the layout):

  params/opt/buffer:   [U, ...]       P()          (replicated)
  key:                 [S, U, 2]      P("data")
  env_state/timestep:  [U, S*E, ...]  P(None, "data")
"""

from __future__ import annotations

from typing import Any, Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stoix_tpu import envs
from stoix_tpu.base_types import ExperimentOutput


def head_kwargs_for_env(head_cfg: Any, env: envs.Environment) -> dict:
    """Infer action-head constructor kwargs from the env's action space, so one
    network config mechanism serves discrete/continuous/multi-discrete heads.
    """
    from stoix_tpu.envs import spaces as env_spaces
    from stoix_tpu.utils.config import _import_target

    import numpy as np

    target = _import_target(head_cfg["_target_"])
    fields = getattr(target, "__dataclass_fields__", {})
    kwargs: dict = {}
    space = env.action_space()

    def bound(v: Any) -> Any:
        # Preserve per-dimension bounds (heads broadcast arrays/lists fine).
        arr = np.asarray(v)
        return float(arr) if arr.ndim == 0 or np.all(arr == arr.flat[0]) else arr.tolist()

    if "num_actions" in fields:
        kwargs["num_actions"] = env.num_actions
    if "action_dim" in fields:
        kwargs["action_dim"] = env.num_actions
    if "num_values" in fields and isinstance(space, env_spaces.MultiDiscrete):
        kwargs["num_values"] = space.num_values
    if "minimum" in fields and hasattr(space, "low"):
        kwargs["minimum"] = bound(space.low)
    if "maximum" in fields and hasattr(space, "high"):
        kwargs["maximum"] = bound(space.high)
    # Explicit values in the network YAML win over inferred ones.
    return {k: v for k, v in kwargs.items() if k not in head_cfg}


def broadcast_to_update_batch(tree: Any, update_batch: int) -> Any:
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (update_batch,) + x.shape), tree)


def reset_envs_for_anakin(
    env: envs.Environment, config: Any, env_key: jax.Array
) -> Tuple[Any, Any]:
    """Reset all global envs and shape leaves to [U, S*E, ...]."""
    update_batch = int(config.arch.get("update_batch_size", 1))
    envs_axis = int(config.arch.total_num_envs) // update_batch
    env_keys = jax.random.split(env_key, update_batch * envs_axis)
    env_state, timestep = env.reset(env_keys)
    reshape = lambda x: x.reshape((update_batch, envs_axis) + x.shape[1:])
    return jax.tree.map(reshape, env_state), jax.tree.map(reshape, timestep)


def make_step_keys(key: jax.Array, mesh: Mesh, config: Any) -> jax.Array:
    n_shards = int(mesh.shape["data"])
    update_batch = int(config.arch.get("update_batch_size", 1))
    return jax.random.split(key, n_shards * update_batch).reshape(n_shards, update_batch, -1)


def _state_shardings(mesh: Mesh, state_specs: Any) -> Any:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), state_specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def place_learner_state(learner_state: Any, mesh: Mesh, state_specs: Any) -> Any:
    """Device-put the state pytree with per-subtree PartitionSpecs."""
    return jax.device_put(learner_state, _state_shardings(mesh, state_specs))


def build_learner_state(
    init_fn: Callable[[jax.Array], Any], key: jax.Array, mesh: Mesh, state_specs: Any
) -> Any:
    """`init_fn(key)` as ONE jitted program whose outputs are made on their
    per-subtree PartitionSpecs: one compilation where an eager construction
    makes one an op (some 180 for an MLP PPO state), each device computes its
    own shards, and no host-built copy is held beside the placed one.

    The program runs once, so nothing folded at compile time pays for itself,
    and XLA's constant folding evaluates on the host whatever an env's reset
    derives from constants alone, for the whole batch of envs: 22 of the 24
    seconds of the program's first compilation at 262,144 Ant envs on a v5e's
    host. It is compiled without that pass."""
    return jax.jit(
        init_fn,
        out_shardings=_state_shardings(mesh, state_specs),
        compiler_options={"xla_disable_hlo_passes": "constant_folding"},
    )(key)


def shardmap_learner(
    learn_per_shard: Callable[[Any], ExperimentOutput],
    mesh: Mesh,
    state_specs: Any,
    episode_metrics_spec: P = P(None, None, None, "data"),
    compiler_options: Optional[Mapping[str, Any]] = None,
) -> Callable[[Any], ExperimentOutput]:
    """Wrap a per-shard learner in shard_map + jit with the standard specs.

    `compiler_options` are XLA options this one program is compiled with (a
    network's yaml names them where its learner needs any, and says why);
    none by default, which is `jax.jit` as it was.

    The learner state is donated (donate_argnums): the host loop's
    `state = learn(state).learner_state` never reads the old state again, and
    donation lets XLA reuse its HBM for the output instead of holding both
    copies live across the update. chip_smoke.py runs two donating windows
    back to back on the v5e; STOIX_TPU_NO_DONATE=1 is the kill-switch for a
    runtime that mishandles donation.

    Snapshot-vs-donation invariant (the pipelined runner depends on it):
    anything read AFTER the next `learn(state)` dispatch — eval params, best
    params, the checkpoint state — must be an on-device COPY taken from the
    device stream BEFORE that dispatch (systems/runner.py _tree_copy). The
    copy is enqueued ahead of the donating program, so the runtime orders the
    read before the buffers are reused; reading the donated tree itself after
    the dispatch is a use-after-free. tests/test_runner_pipeline.py guards
    this with donation on and off.
    """
    import os

    jit_options = {} if os.environ.get("STOIX_TPU_NO_DONATE") else {"donate_argnums": (0,)}
    if compiler_options:
        jit_options["compiler_options"] = dict(compiler_options)
    return jax.jit(
        jax.shard_map(
            learn_per_shard,
            mesh=mesh,
            in_specs=(state_specs,),
            out_specs=ExperimentOutput(
                learner_state=state_specs,
                episode_metrics=episode_metrics_spec,
                train_metrics=P(),
            ),
            # Anakin-specific opt-out (VERDICT r3 #9, investigated r4): with
            # check_vma=True the learner compiles until the first
            # `jax.lax.pmean(..., axis_name="batch")` — the in-shard
            # update-batch VMAP axis — which fails an internal assert in
            # JAX's varying-manual-axes machinery (collectives over vmap axes
            # nested in shard_map are outside what the validator models).
            # The Sebulba learners have no in-shard vmap axis and run with
            # check_vma=True (sebulba/actor_critic.py::shard_learn_step); carry-leaf
            # varying-ness was fixed where real (wrappers._ensure_truncation).
            check_vma=False,
        ),
        **jit_options,
    )


def unbatch_params(params: Any) -> Any:
    """Strip the [U] update-batch axis (all replicas identical post-pmean)."""
    return jax.tree.map(lambda x: x[0], params)
