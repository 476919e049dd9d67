"""Checkpoint -> servable policy (docs/DESIGN.md §2.8).

A trained policy's life after training starts here: given EITHER an orbax
store (what `logger.checkpointing.save_model` writes) or a fleet local-shard
emergency store (resilience/fleet.py), rebuild the actor network from the
TRAINING config and restore just the actor-params subtree through the
topology-elastic machinery (utils/checkpointing.read_host_leaves +
place_host_leaves): leaves materialize to host, match by normalized
tree-path, and re-place onto whatever devices the SERVER runs — any
checkpoint serves on any mesh, params bit-identical (PR 4's guarantee,
pinned for the serving path in tests/test_serve.py).

Where the training config comes from, in priority order:
  1. `arch.serve.checkpoint.train_config` (+ train_overrides) — an explicit
     root yaml, required for emergency stores (they carry no metadata);
  2. the orbax store's own root metadata — the Checkpointer saves the FULL
     composed training config there, so a plain `serve` launch needs nothing
     but the store path.

The restored subtree keeps the training-side [update_batch] leading axis
while matching (the store's shapes are authoritative); replica 0 is served —
gradient pmean over the ("batch", "data") axes keeps all replicas
bit-identical during training, so replica choice cannot matter.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import numpy as np

from stoix_tpu.observability import get_logger
from stoix_tpu.resilience import fleet
from stoix_tpu.resilience.errors import CheckpointIntegrityError
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.checkpointing import _orbax, place_host_leaves, read_host_leaves

DEFAULT_PARAMS_PATH = "params/actor_params"
OBS_STATS_PATH = "obs_stats"


def build_actor(config: Any, env: Any):
    """Instantiate the actor network exactly as learner_setup does (the
    PPO-family template, systems/ppo/anakin/ff_ppo.py): config.network's
    actor_network block with env-inferred head kwargs."""
    from stoix_tpu.networks.base import FeedForwardActor
    from stoix_tpu.systems import anakin

    net_cfg = config.network
    return FeedForwardActor(
        action_head=config_lib.instantiate(
            net_cfg.actor_network.action_head,
            **anakin.head_kwargs_for_env(net_cfg.actor_network.action_head, env),
        ),
        torso=config_lib.instantiate(net_cfg.actor_network.pre_torso),
        input_layer=config_lib.instantiate(net_cfg.actor_network.input_layer),
    )


def store_metadata(path: str) -> Dict[str, Any]:
    """The custom metadata dict an orbax store root carries ({} when absent
    or unreadable). The training Checkpointer writes the full composed config
    there, which is what makes `serve` self-describing."""
    ocp = _orbax()
    try:
        manager = ocp.CheckpointManager(os.path.abspath(path))
    except Exception as exc:  # noqa: BLE001 — any unreadable store => no metadata
        get_logger("stoix_tpu.serve").warning(
            "[serve] could not open store metadata at %s (%s: %s)",
            path, type(exc).__name__, exc,
        )
        return {}
    try:
        meta = manager.metadata()
        custom = getattr(meta, "custom_metadata", meta)
        return dict(custom or {})
    finally:
        manager.close()


class PolicySource:
    """Where serving params come from — an orbax store directory (the
    model dir holding numeric step subdirectories) or a fleet emergency
    store. Re-loadable: the hot-swap watcher polls latest_step() and calls
    load() again when the store advances."""

    def __init__(
        self,
        path: str,
        templates: Dict[Tuple[str, ...], Any],
        bundle: Callable[[Dict[Tuple[str, ...], Any]], Any],
    ):
        self.path = str(path)
        self._templates = templates
        self._bundle = bundle
        self.is_emergency = fleet.is_emergency_store(self.path)

    def latest_step(self) -> Optional[int]:
        """Newest step available in the store (None when empty/missing)."""
        if self.is_emergency:
            return fleet.emergency_step(self.path)
        try:
            steps = [
                int(entry)
                for entry in os.listdir(self.path)
                if entry.isdigit() and os.path.isdir(os.path.join(self.path, entry))
            ]
        except OSError:
            return None
        return max(steps) if steps else None

    def _raw_leaves(self, step: Optional[int]) -> Tuple[Dict[Tuple[str, ...], Any], int]:
        if self.is_emergency:
            raw, casts, found = fleet.read_emergency_raw(self.path)
            if step is not None and found != int(step):
                # An emergency store holds exactly ONE step; an explicit
                # timestep it cannot honor must refuse, not silently serve a
                # different policy than the operator pinned.
                raise FileNotFoundError(
                    f"emergency store {self.path} holds step {found}, not "
                    f"the requested timestep {step}"
                )
            template_dtypes = {
                key: getattr(leaf, "dtype", np.asarray(leaf).dtype)
                for prefix, template in self._templates.items()
                for key, leaf in _flatten_with_prefix(template, prefix).items()
            }
            for key in casts:
                joined = tuple(key.split("/"))
                if key in raw and joined in template_dtypes:
                    raw[key] = raw[key].astype(template_dtypes[joined])
            return {tuple(k.split("/")): v for k, v in raw.items()}, found
        found = int(step) if step is not None else self.latest_step()
        if found is None:
            raise FileNotFoundError(f"no checkpoint steps under {self.path}")
        raw = read_host_leaves(self.path, found)
        # Digest verification when the store carries a manifest
        # (docs/DESIGN.md §2.9): a bit-rotted or half-synced checkpoint is
        # REJECTED here — the hot-swap watcher counts the error and keeps
        # serving the params it has — instead of being swapped into live
        # traffic. (Emergency stores verify inside fleet.read_emergency_raw.)
        from stoix_tpu.resilience import integrity
        from stoix_tpu.utils.checkpointing import saved_digest_record

        record = saved_digest_record(self.path).get(found) or {}
        if record:
            mismatched = integrity.verify_digests(
                {"/".join(key): arr for key, arr in raw.items()}, record
            )
            if mismatched:
                raise CheckpointIntegrityError(
                    found,
                    f"store {self.path} failed sha256 verification for "
                    f"{len(mismatched)} leaf(s): {', '.join(mismatched[:5])}"
                    f"{'...' if len(mismatched) > 5 else ''}",
                    kind="digest",
                )
        return raw, found

    def load(self, step: Optional[int] = None) -> Tuple[Any, int]:
        """Restore the configured subtrees at `step` (None = newest) and
        return (engine params, step). Every template leaf must match — a
        serving params subtree with reinitialized leaves would silently serve
        garbage, so partial matches raise CheckpointIntegrityError."""
        raw_by_path, found = self._raw_leaves(step)
        loaded: Dict[Tuple[str, ...], Any] = {}
        for prefix, template in self._templates.items():
            sub = {
                key[len(prefix):]: value
                for key, value in raw_by_path.items()
                if key[: len(prefix)] == prefix
            }
            placed, _matched, reinitialized, _reinit_keys = place_host_leaves(
                sub, template, found
            )
            if reinitialized:
                raise CheckpointIntegrityError(
                    found,
                    f"serving subtree {'/'.join(prefix)} has "
                    f"{len(reinitialized)} unmatched leaf(s) — refusing to "
                    f"serve a partially restored policy: "
                    f"{'; '.join(reinitialized)}",
                )
            # Serve replica 0 of the [update_batch] axis (replicas are
            # bit-identical by the training-side pmean discipline).
            loaded[prefix] = jax.tree.map(lambda x: x[0], placed)
        return self._bundle(loaded), found


def _flatten_with_prefix(template: Any, prefix: Tuple[str, ...]) -> Dict[Tuple[str, ...], Any]:
    from stoix_tpu.utils.checkpointing import _path_key

    return {
        prefix + _path_key(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(template)[0]
    }


class PolicyBundle(NamedTuple):
    """Everything the server needs to run a restored policy."""

    apply_fn: Callable[[Any, Any], Any]  # (params, batched observation) -> dist
    params: Any
    obs_template: Any  # ONE unbatched observation pytree
    step: int
    source: PolicySource
    train_config: Any


def resolve_train_config(config: Any) -> Any:
    """The TRAINING config the checkpoint was produced under (see module
    docstring for the precedence)."""
    serve_cfg = config.arch.serve
    ckpt_cfg = serve_cfg.checkpoint
    explicit = ckpt_cfg.get("train_config")
    if explicit:
        overrides = [str(o) for o in (ckpt_cfg.get("train_overrides") or [])]
        return config_lib.compose(
            config_lib.default_config_dir(), str(explicit), overrides
        )
    path = str(ckpt_cfg.path)
    if fleet.is_emergency_store(path):
        raise ValueError(
            "emergency stores carry no config metadata: set "
            "arch.serve.checkpoint.train_config to the training root yaml "
            "(e.g. default/anakin/default_ff_ppo.yaml) plus train_overrides"
        )
    meta = store_metadata(path)
    if not meta.get("env"):
        raise ValueError(
            f"store {path} has no usable config metadata; set "
            "arch.serve.checkpoint.train_config explicitly"
        )
    return config_lib.Config.from_dict(meta)


def load_policy(config: Any) -> PolicyBundle:
    """Build the servable policy for a composed serve config (the
    `default/serve.yaml` root): rebuild the actor from the training config,
    restore the actor-params subtree (+ observation statistics when the
    policy trained with normalize_observations), and return the bundle."""
    from stoix_tpu import envs
    from stoix_tpu.ops import running_statistics
    from stoix_tpu.systems.anakin import broadcast_to_update_batch

    serve_cfg = config.arch.serve
    ckpt_cfg = serve_cfg.checkpoint
    path = str(ckpt_cfg.path or "")
    if not path or path == "None":
        raise ValueError("arch.serve.checkpoint.path must name a checkpoint store")

    train_config = resolve_train_config(config)
    env, _ = envs.make(train_config)
    actor_network = build_actor(train_config, env)
    obs_template = env.observation_value()
    dummy_obs = jax.tree.map(lambda x: x[None], obs_template)
    init_params = actor_network.init(jax.random.PRNGKey(0), dummy_obs)
    update_batch = int(train_config.arch.get("update_batch_size", 1))

    params_path = str(ckpt_cfg.get("params_path") or DEFAULT_PARAMS_PATH)
    params_prefix = tuple(p for p in params_path.split("/") if p)
    templates: Dict[Tuple[str, ...], Any] = {
        params_prefix: broadcast_to_update_batch(init_params, update_batch)
    }

    normalize = bool(train_config.system.get("normalize_observations", False))
    stats_prefix = (OBS_STATS_PATH,)
    if normalize:
        stats_template = running_statistics.init_state(
            env.observation_value().agent_view
        )
        templates[stats_prefix] = broadcast_to_update_batch(
            stats_template, update_batch
        )

        def bundle(loaded: Dict[Tuple[str, ...], Any]) -> Any:
            return (loaded[params_prefix], loaded[stats_prefix])

        def apply_fn(bundled: Any, observation: Any) -> Any:
            actor_params, stats = bundled
            observation = running_statistics.normalize_observation(
                observation, stats
            )
            return actor_network.apply(actor_params, observation)

    else:

        def bundle(loaded: Dict[Tuple[str, ...], Any]) -> Any:
            return loaded[params_prefix]

        apply_fn = actor_network.apply

    source = PolicySource(path, templates, bundle)
    timestep = ckpt_cfg.get("timestep")
    params, step = source.load(None if timestep is None else int(timestep))
    scenario = train_config.env.scenario
    task = scenario.get("task_name", "policy") if hasattr(scenario, "get") else str(scenario)
    get_logger("stoix_tpu.serve").info(
        "[serve] restored %s policy at step %d from %s (%s store%s)",
        task, step, path,
        "emergency" if source.is_emergency else "orbax",
        ", obs-normalized" if normalize else "",
    )
    return PolicyBundle(
        apply_fn=apply_fn,
        params=params,
        obs_template=obs_template,
        step=step,
        source=source,
        train_config=train_config,
    )
