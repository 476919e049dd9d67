"""Orbax-backed checkpointing (reference stoix/utils/checkpointing.py:20-187).

Saves learner state keyed by timestep with best-by-episode-return tracking and
config-as-metadata with a major-version compatibility check. TPU-native
difference from the reference: states are GLOBAL (sharded) arrays — orbax
handles sharded save/restore natively, so there is no unreplicate step
(SURVEY.md §7.1.1).

Resilience (docs/DESIGN.md §2.3): `restore` validates what it loads —
tree-structure against the template plus a finiteness spot-check (leaves
whose TEMPLATE is fully finite must restore fully finite; leaves where the
template itself carries inf/nan sentinels are exempt) — and, when the newest
checkpoint is corrupt or truncated (a preempted save, a chaos-injected
`ckpt_corrupt`), automatically falls back to the newest VALID step instead
of dying on a bare orbax error.

Topology-elastic restore (docs/DESIGN.md §2.4): every save records its device
footprint (the number of distinct devices the state's shardings span) in a
`_topology.json` sidecar next to the step directories, plus the saving
process's device/process counts in the manager metadata. When `restore` sees
a template whose footprint differs from the saved one — a run saved on an
8-device mesh resuming on 1 device, or vice versa — it takes the RESHARD
path: materialize the checkpoint to host WITHOUT a sharded template, match
leaves to the template by tree-path (orbax serializes NamedTuples as dicts,
so leaf ORDER differs), validate shape/dtype, and re-place each leaf via the
template's own NamedShardings (the fresh setup built them from
`parallel.mesh`). Values pass through the host unchanged: params restore
bit-identical. Leaves whose GLOBAL shape is topology-dependent (the
per-shard RNG key state, shaped [num_shards, ...]) cannot be ported; they
keep the template's freshly-initialized value and are logged loudly.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from stoix_tpu.resilience.errors import CheckpointIntegrityError

# 2.0: continuous MPO/V-MPO dual variables changed shape from (2,) to
# [2, action_dim] (per-dimension KL constraints) — old checkpoints cannot
# restore into the new template.
# 3.0: PPOLearnerState grew a `kl_beta` leaf (adaptive-KL PPO-penalty state)
# — pre-3.0 PPO/DPO/penalty checkpoints lack it and cannot restore into the
# new template.
CHECKPOINTER_VERSION = 3.0

# Sidecar recording each step's device footprint (docs/DESIGN.md §2.4):
# {"steps": {"<step>": {"devices": N}}}. Lives at the store root next to the
# step directories; orbax's step scan only considers directories, so the
# file is invisible to it.
TOPOLOGY_SIDECAR = "_topology.json"

# Sidecar recording each step's per-leaf sha256 digests (docs/DESIGN.md
# §2.9): {"steps": {"<step>": {"<slash-joined tree path>": "<hex>"}}}.
# Written by save() from the exact host bytes orbax serializes; restore()
# recomputes digests from what came back and REJECTS the step on mismatch
# (on-disk bit-rot walks to the next-newest checkpoint instead of resuming
# as garbage). Shares the digest helpers with the fleet emergency store and
# the serving canary (resilience/integrity.py).
DIGEST_SIDECAR = "_digests.json"


def _orbax() -> Any:
    """`orbax.checkpoint`, imported by the first caller that builds or reads a
    store (docs/DESIGN.md §2.2, "A feature's library is imported where the
    feature is built"): seconds on the chip's host, most of them
    `google.cloud.logging`, that a run with checkpointing off never pays and a
    run that saves or restores pays inside its `logger_build` or `restore`
    phase. That one import is timed into the gauge
    `stoix_tpu_checkpoint_library_import_seconds`, absent until then. The only
    way the package reaches orbax."""
    first = "orbax.checkpoint" not in sys.modules
    began = time.perf_counter()
    import orbax.checkpoint as ocp

    if first:
        from stoix_tpu.observability import get_registry

        get_registry().gauge(
            "stoix_tpu_checkpoint_library_import_seconds",
            "Wall seconds of this process's one import of orbax.checkpoint",
        ).set(time.perf_counter() - began)
    return ocp


def saved_digest_record(store_dir: str) -> Dict[int, Dict[str, str]]:
    """Per-step digest records from a store's `_digests.json` ({} when
    absent). Module-level so the serving loader (stoix_tpu/serve) can verify
    a store it reads without constructing a Checkpointer."""
    try:
        with open(os.path.join(str(store_dir), DIGEST_SIDECAR)) as f:
            data = json.load(f)
        return {
            int(step): {str(k): str(v) for k, v in (record or {}).items()}
            for step, record in (data.get("steps") or {}).items()
        }
    except (OSError, ValueError):
        return {}


def _device_footprint(tree: Any) -> Optional[int]:
    """Number of distinct devices the tree's jax.Array leaves span, or None
    when the tree carries no addressable device arrays (host/numpy state)."""
    ids = set()
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            try:
                ids.update(d.id for d in leaf.sharding.device_set)
            except Exception:  # noqa: BLE001 — deleted/donated arrays have no sharding
                continue
    return len(ids) or None


def _path_key(path: Any) -> Tuple[str, ...]:
    """Normalize a jax key-path so the same LOGICAL leaf matches across
    container types: orbax serializes NamedTuples as dicts (GetAttrKey on the
    template side, DictKey on the restored side) and tuples as lists."""
    parts = []
    for entry in path:
        if hasattr(entry, "name"):  # GetAttrKey (NamedTuple/dataclass field)
            parts.append(str(entry.name))
        elif hasattr(entry, "key"):  # DictKey / FlattenedIndexKey
            parts.append(str(entry.key))
        elif hasattr(entry, "idx"):  # SequenceKey
            parts.append(str(entry.idx))
        else:
            parts.append(str(entry))
    return tuple(parts)


def place_host_leaves(
    raw_by_path: Dict[Tuple[str, ...], Any],
    template: Any,
    step: int,
    allow_missing: bool = False,
) -> Tuple[Any, int, List[str], List[Tuple[str, ...]]]:
    """Place host-materialized leaves into `template`'s structure and
    shardings, matching by normalized tree-path — the placement half of the
    topology-elastic restore (docs/DESIGN.md §2.4), shared with the fleet
    local-shard emergency restore (resilience/fleet.py, §2.6).

    Returns (tree, matched_count, reinitialized_descriptions,
    reinitialized_keys) — the keys let digest verification (§2.9) skip
    leaves that deliberately kept the template's fresh value. Shape
    mismatches are topology-dependent state and keep the template's value;
    dtype mismatches raise CheckpointIntegrityError (corruption, not
    topology). A missing leaf raises unless `allow_missing` (the fleet store
    legitimately omits partially-addressable leaves); zero matched leaves is
    always an error — that is a different state, not a topology change."""
    template_leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    placed: List[Any] = []
    reinitialized: List[str] = []
    reinitialized_keys: List[Tuple[str, ...]] = []
    matched = 0
    for path, ref in template_leaves:
        key = _path_key(path)
        if key not in raw_by_path:
            if allow_missing:
                reinitialized.append(
                    f"{jax.tree_util.keystr(path)} (absent from the store)"
                )
                reinitialized_keys.append(key)
                placed.append(ref)
                continue
            raise CheckpointIntegrityError(
                step,
                f"leaf {jax.tree_util.keystr(path)} missing from the "
                f"checkpoint (resharded restore matches by tree-path)",
            )
        arr = np.asarray(raw_by_path[key])
        ref_dtype = getattr(ref, "dtype", None) or np.asarray(ref).dtype
        ref_shape = tuple(np.shape(ref))
        if arr.dtype != ref_dtype:
            raise CheckpointIntegrityError(
                step,
                f"dtype mismatch at {jax.tree_util.keystr(path)}: saved "
                f"{arr.dtype} vs template {ref_dtype}",
            )
        if arr.shape != ref_shape:
            # Topology-dependent global shape (e.g. the [num_shards, ...]
            # per-shard key state): not portable across meshes by
            # construction — keep the template's fresh value.
            reinitialized.append(
                f"{jax.tree_util.keystr(path)} (saved {arr.shape} vs "
                f"template {ref_shape})"
            )
            reinitialized_keys.append(key)
            placed.append(ref)
            continue
        matched += 1
        if isinstance(ref, jax.Array):
            placed.append(jax.device_put(arr, ref.sharding))
        else:
            placed.append(arr)
    if matched == 0:
        raise CheckpointIntegrityError(
            step,
            "resharded restore matched ZERO leaves by shape — this is a "
            "different state entirely, not a topology change",
        )
    return treedef.unflatten(placed), matched, reinitialized, reinitialized_keys


def read_host_leaves(store_dir: str, step: int) -> Dict[Tuple[str, ...], Any]:
    """Materialize one checkpoint step to HOST numpy leaves keyed by
    normalized tree-path — the read half of the topology-elastic restore
    (docs/DESIGN.md §2.4), shared with the serving path (stoix_tpu/serve/
    checkpoint.py), which restores a params SUBTREE onto whatever device
    topology the server runs.

    Reads through a standalone PyTree handler with restore_type=ndarray: the
    MANAGER's restore (with or without a template) reconstructs jax.Arrays on
    the devices recorded AT SAVE TIME, which need not exist on the restoring
    host — forcing numpy never touches device placement."""
    ocp = _orbax()
    step_path = os.path.join(store_dir, str(step), "default")
    reader = ocp.Checkpointer(ocp.PyTreeCheckpointHandler())
    try:
        # orbax returns a StepMetadata; the saved tree's per-leaf metadata is
        # its `.item_metadata.tree`.
        saved_tree = reader.metadata(step_path).item_metadata.tree
        restore_args = jax.tree.map(
            lambda _m: ocp.RestoreArgs(restore_type=np.ndarray), saved_tree
        )
        raw = reader.restore(
            step_path, args=ocp.args.PyTreeRestore(restore_args=restore_args)
        )
    finally:
        reader.close()
    return {
        _path_key(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(raw)[0]
    }


class Checkpointer:
    def __init__(
        self,
        model_name: str,
        metadata: Optional[dict] = None,
        rel_dir: str = "checkpoints",
        checkpoint_uid: Optional[str] = None,
        save_interval_steps: int = 1,
        max_to_keep: Optional[int] = 1,
        keep_period: Optional[int] = None,
    ):
        ocp = _orbax()
        uid = checkpoint_uid
        if uid is None:
            uid = time.strftime("%Y%m%d%H%M%S")
            if jax.process_count() > 1:
                # All processes must agree on the directory (collective save);
                # startup skew can cross a second boundary, so broadcast the
                # coordinator's stamp.
                import numpy as np
                from jax.experimental import multihost_utils

                stamp = multihost_utils.broadcast_one_to_all(
                    np.asarray([int(uid)], dtype=np.int64)
                )
                uid = str(int(stamp[0]))
        self.directory = os.path.abspath(os.path.join(rel_dir, uid, model_name))
        options = ocp.CheckpointManagerOptions(
            save_interval_steps=save_interval_steps,
            max_to_keep=max_to_keep,
            keep_period=keep_period,
            best_fn=lambda m: m["episode_return"],
            best_mode="max",
            create=True,
        )
        metadata = dict(metadata or {})
        metadata["checkpointer_version"] = CHECKPOINTER_VERSION
        # Saving process's topology, for operators reading the store; the
        # per-step footprint that drives elastic restore lives in the
        # _topology.json sidecar (written by save — only then is the actual
        # device span of the state known).
        metadata["topology"] = {
            "device_count": jax.device_count(),
            "process_count": jax.process_count(),
        }
        self._save_interval_steps = int(save_interval_steps)
        # Typed rejection log of the most recent restore()'s fallback walk
        # (docs/DESIGN.md §2.9): [{"step", "reason", "error"}, ...].
        self.last_restore_report: List[Dict[str, str]] = []
        self._manager = ocp.CheckpointManager(
            self.directory,
            options=options,
            metadata=json.loads(json.dumps(metadata, default=str)),
        )

    def should_save(self, timestep: int, last_issued: Optional[int] = None) -> bool:
        """Whether the manager's save policy (save_interval_steps etc.) will
        accept a save at `timestep`. The pipelined runner checks this BEFORE
        taking the on-device state snapshot, so skipped windows don't pay the
        full-state copy.

        `last_issued` is the step of a save the CALLER has already decided on
        but orbax may not have registered yet (the pipelined loop decides one
        window ahead of issuing): the interval policy is applied against it
        first, since the manager's latest_step is stale until that save
        lands."""
        if (
            last_issued is not None
            and timestep - last_issued < self._save_interval_steps
        ):
            return False
        try:
            return bool(self._manager.should_save(timestep))
        except Exception:  # noqa: BLE001 — older orbax: assume it saves
            return True

    def save(
        self,
        timestep: int,
        state: Any,
        episode_return: float = 0.0,
        force: bool = False,
    ) -> bool:
        """Hand `state` to orbax; serialization may complete asynchronously.

        Callers must pass buffers that no later XLA program donates: the
        Anakin runner saves an on-device SNAPSHOT copy of the learner state
        (systems/runner.py), which is what makes the save safely async — the
        hot path never calls wait(). `force=True` bypasses the save-interval
        policy (the preemption handler's emergency checkpoint must land
        regardless of cadence)."""
        footprint = _device_footprint(state)
        saved = self._manager.save(
            timestep,
            args=_orbax().args.StandardSave(jax.tree.map(jax.numpy.asarray, state)),
            metrics={"episode_return": float(episode_return)},
            force=force,
        )
        if saved and jax.process_index() == 0:
            self._record_topology(timestep, footprint)
            self._record_digests(timestep, state)
        # Chaos hook (`STOIX_TPU_FAULT=ckpt_corrupt`, one-shot): mangle this
        # step's files AFTER serialization completes, so the restore-fallback
        # path is exercised against a real on-disk layout.
        from stoix_tpu.resilience import faultinject

        if saved and faultinject.consume_ckpt_corrupt():
            self._manager.wait_until_finished()
            faultinject.corrupt_checkpoint_files(
                os.path.join(self.directory, str(timestep))
            )
        return saved

    def all_steps(self) -> List[int]:
        """Ascending steps with a checkpoint on disk."""
        return sorted(int(s) for s in self._manager.all_steps())

    # -- topology sidecar ----------------------------------------------------
    def _sidecar_path(self) -> str:
        return os.path.join(self.directory, TOPOLOGY_SIDECAR)

    def _record_topology(self, timestep: int, footprint: Optional[int]) -> None:
        """Read-modify-write the per-step footprint sidecar. Best-effort: a
        missing sidecar only disables the PROACTIVE reshard decision (restore
        still falls back to resharding when the template path fails)."""
        if footprint is None:
            return
        try:
            record = self.saved_topologies()
            record[int(timestep)] = {"devices": int(footprint)}
            with open(self._sidecar_path(), "w") as f:
                json.dump(
                    {"steps": {str(k): v for k, v in sorted(record.items())}}, f
                )
        except OSError as exc:
            from stoix_tpu.observability import get_logger

            get_logger("stoix_tpu.checkpoint").warning(
                "[checkpoint] could not record topology sidecar for step %d "
                "(%s) — elastic restore will rely on its fallback path",
                timestep, exc,
            )

    def saved_topologies(self) -> Dict[int, dict]:
        """Per-step device footprints from the sidecar ({} when absent)."""
        try:
            with open(self._sidecar_path()) as f:
                data = json.load(f)
            return {int(k): dict(v) for k, v in (data.get("steps") or {}).items()}
        except (OSError, ValueError):
            return {}

    # -- digest sidecar (docs/DESIGN.md §2.9) --------------------------------
    def _record_digests(self, timestep: int, state: Any) -> None:
        """Record per-leaf sha256 digests of the exact host bytes orbax is
        serializing for `timestep` (read-modify-write; entries for steps the
        retention policy deleted are pruned). Best-effort like the topology
        sidecar: a missing record only disables digest VERIFICATION for this
        step — restore still runs its structural + finiteness gates.

        Cost: one device->host materialization of the snapshot per save —
        paid on the overlapped host half of the pipelined runner, never on
        the device stream. Leaves not fully addressable from this process
        (multi-host shards) are skipped and simply not verified."""
        from stoix_tpu.resilience import integrity

        try:
            digests: Dict[str, str] = {}
            for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
                if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                    continue
                digests["/".join(_path_key(path))] = integrity.leaf_digest(
                    np.asarray(leaf)
                )
            record = self.saved_digests()
            record[int(timestep)] = digests
            try:
                on_disk = set(self._manager.all_steps())
            except Exception:  # noqa: BLE001 — pruning is housekeeping only
                on_disk = set(record)
            keep = {step for step in record if step in on_disk or step == int(timestep)}
            path = os.path.join(self.directory, DIGEST_SIDECAR)
            with open(path, "w") as f:
                json.dump(
                    {
                        "steps": {
                            str(step): record[step] for step in sorted(keep)
                        }
                    },
                    f,
                )
        except OSError as exc:
            from stoix_tpu.observability import get_logger

            get_logger("stoix_tpu.checkpoint").warning(
                "[checkpoint] could not record digest sidecar for step %d "
                "(%s) — this step will restore without digest verification",
                timestep, exc,
            )

    def saved_digests(self) -> Dict[int, Dict[str, str]]:
        """Per-step digest records from this store's sidecar ({} = none)."""
        return saved_digest_record(self.directory)

    def _verify_digests(
        self, restored: Any, step: int, skip_keys: Optional[set] = None
    ) -> None:
        """Recompute each restored leaf's digest and compare against the
        record made at save time; a mismatch is on-disk bit-rot and raises
        the typed 'digest' rejection (the fallback walk tries the next-
        newest step). `skip_keys` excludes leaves the elastic restore
        deliberately reinitialized from the template. No record for this
        step (pre-digest store, sidecar lost) = skip, logged at debug."""
        from stoix_tpu.resilience import integrity

        record = self.saved_digests().get(int(step)) or {}
        if not record:
            return
        skip = skip_keys or set()
        arrays: Dict[str, np.ndarray] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(restored)[0]:
            key = _path_key(path)
            if key in skip:
                continue
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                continue
            arrays["/".join(key)] = np.asarray(leaf)
        mismatched = integrity.verify_digests(arrays, record)
        if mismatched:
            raise CheckpointIntegrityError(
                step,
                f"sha256 digest mismatch on {len(mismatched)} leaf(s) — the "
                f"bytes on disk are not the bytes that were saved (bit-rot "
                f"or tampering): {', '.join(mismatched[:5])}"
                f"{'...' if len(mismatched) > 5 else ''}",
                kind="digest",
            )

    @staticmethod
    def _validate(restored: Any, template: Any, step: int) -> None:
        """Integrity gate: identical tree structure, and every float leaf
        whose TEMPLATE is fully finite must restore fully finite. Template
        leaves that legitimately carry inf/nan (masks, bound sentinels) are
        exempt — the template defines what 'finite' means for this state."""
        got = jax.tree.structure(restored)
        want = jax.tree.structure(template)
        if got != want:
            raise CheckpointIntegrityError(
                step,
                f"tree structure mismatch: restored {got} != template {want}",
                kind="structure",
            )
        def _as_float_array(leaf: Any):
            """Host float array for finiteness checks, or None for non-float
            leaves. jnp.issubdtype (not np.) so ml_dtypes floats — bfloat16,
            the common TPU param dtype — are validated, not skipped; they are
            widened to float32 because numpy ufuncs don't cover them."""
            arr = np.asarray(leaf)
            if not jnp.issubdtype(arr.dtype, jnp.floating):
                return None
            if arr.dtype not in (np.float16, np.float32, np.float64):
                arr = arr.astype(np.float32)
            return arr

        restored_leaves = jax.tree_util.tree_flatten_with_path(restored)[0]
        template_leaves = jax.tree.leaves(template)
        for (path, leaf), ref in zip(restored_leaves, template_leaves):
            if not getattr(leaf, "is_fully_addressable", True):
                continue  # multi-host shard not local to this process
            arr = _as_float_array(leaf)
            if arr is None or np.isfinite(arr).all():
                continue
            ref_arr = _as_float_array(ref)
            if ref_arr is not None and not np.isfinite(ref_arr).all():
                continue  # the template itself carries non-finite sentinels
            raise CheckpointIntegrityError(
                step,
                f"non-finite values in leaf {jax.tree_util.keystr(path)} "
                f"(template expects finite values here)",
                kind="non_finite",
            )

    def _restore_resharded(self, step: int, template: Any) -> Tuple[Any, set]:
        """Topology-elastic restore path (docs/DESIGN.md §2.4): materialize
        the checkpoint to host with NO sharded template, match leaves to the
        template by normalized tree-path, and re-place each onto the
        template's own sharding. Values round-trip through the host
        untouched — params restore bit-identical across meshes. Returns
        (tree, reinitialized_key_set) so digest verification skips the
        leaves that deliberately kept the template's fresh value.

        Shape-mismatched leaves are topology-dependent state (the per-shard
        RNG keys, [num_shards, ...]): they keep the TEMPLATE's value and are
        logged. dtype mismatches and missing leaves are corruption, not
        topology — they raise CheckpointIntegrityError."""
        from stoix_tpu.observability import get_logger

        raw_by_path = read_host_leaves(self.directory, step)
        restored, matched, reinitialized, reinit_keys = place_host_leaves(
            raw_by_path, template, step
        )
        if reinitialized:
            get_logger("stoix_tpu.checkpoint").warning(
                "[checkpoint] elastic restore of step %d re-placed %d leaf(s) "
                "onto the new mesh; %d topology-dependent leaf(s) kept their "
                "template initialization: %s",
                step, matched, len(reinitialized), "; ".join(reinitialized),
            )
        return restored, set(reinit_keys)

    def restore(
        self,
        template: Any,
        timestep: Optional[int] = None,
        validate: bool = True,
        fallback: bool = True,
        reshard: str = "auto",
    ) -> Tuple[Any, int]:
        """Restore into the shape/sharding of `template`; returns (state, step).

        Latest-step restores walk newest-to-oldest past corrupt/truncated/
        non-finite/digest-mismatched checkpoints until one validates — a
        preempted, chaos-corrupted, or bit-rotted save costs one checkpoint
        interval, not the run. Each rejection is logged with its DISTINCT
        typed reason ('structure' | 'non_finite' | 'digest' | the raising
        exception's type) and recorded in `self.last_restore_report`
        (docs/DESIGN.md §2.9; the runner surfaces the count as
        LAST_RUN_STATS.resilience.restore_skipped). An EXPLICIT `timestep`
        never falls back: a missing step raises FileNotFoundError listing
        what IS available, and a corrupt one raises its own error (the
        caller asked for that step by name).

        `reshard` controls topology elasticity (docs/DESIGN.md §2.4):
        'auto' (default) takes the resharding path when the sidecar-recorded
        footprint of a step differs from the template's — and additionally
        retries a failed template-path restore through it (old stores have no
        sidecar); 'never' restores strictly into the template's topology;
        'force' always reshards through the host."""
        from stoix_tpu.observability import get_logger

        if reshard not in ("auto", "never", "force"):
            raise ValueError(f"reshard must be auto|never|force, got {reshard!r}")
        self.last_restore_report: List[Dict[str, str]] = []
        steps = self.all_steps()
        if timestep is not None:
            if int(timestep) not in steps:
                raise FileNotFoundError(
                    f"No checkpoint at timestep {timestep} under "
                    f"{self.directory}; available steps: {steps or '[]'}"
                )
            candidates = [int(timestep)]
            fallback = False
        else:
            if not steps:
                raise FileNotFoundError(f"No checkpoints under {self.directory}")
            candidates = steps[::-1]

        saved_topologies = self.saved_topologies() if reshard == "auto" else {}
        template_footprint = _device_footprint(template)
        log = get_logger("stoix_tpu.checkpoint")
        last_error: Optional[Exception] = None
        for step in candidates:
            saved_fp = (saved_topologies.get(step) or {}).get("devices")
            proactive_reshard = reshard == "force" or (
                reshard == "auto"
                and saved_fp is not None
                and template_footprint is not None
                and int(saved_fp) != int(template_footprint)
            )
            try:
                digest_skip: set = set()
                if proactive_reshard:
                    log.info(
                        "[checkpoint] step %d saved on %s device(s), template "
                        "spans %s — taking the elastic (resharding) restore "
                        "path", step, saved_fp or "?", template_footprint,
                    )
                    restored, digest_skip = self._restore_resharded(step, template)
                else:
                    try:
                        restored = self._manager.restore(
                            step, args=_orbax().args.StandardRestore(template)
                        )
                    except (CheckpointIntegrityError, FileNotFoundError):
                        raise
                    except Exception as exc:  # noqa: BLE001 — template-path
                        # restore failures on an UNKNOWN-topology store (no
                        # sidecar entry) are often sharding mismatches: give
                        # the elastic path one shot before rejecting the step.
                        # A KNOWN-matching topology that failed is corruption
                        # — re-reading the whole state through the host path
                        # would double the I/O for nothing.
                        if reshard != "auto" or saved_fp is not None:
                            raise
                        log.warning(
                            "[checkpoint] template-path restore of step %d "
                            "failed (%s: %s) — retrying through the elastic "
                            "resharding path", step, type(exc).__name__, exc,
                        )
                        restored, digest_skip = self._restore_resharded(
                            step, template
                        )
                if validate:
                    self._validate(restored, template, step)
                    self._verify_digests(restored, step, skip_keys=digest_skip)
                return restored, int(step)
            except Exception as exc:  # noqa: BLE001 — each candidate's failure
                # mode differs (orbax I/O error, msgpack truncation, integrity
                # rejection, digest mismatch); all mean "try the next-newest",
                # each with its DISTINCT typed reason in the log + report.
                if not fallback:
                    raise
                last_error = exc
                reason = getattr(exc, "kind", None) or type(exc).__name__
                self.last_restore_report.append(
                    {"step": str(step), "reason": str(reason), "error": str(exc)}
                )
                log.warning(
                    "[checkpoint] step %d unusable [reason: %s] (%s: %s) — "
                    "falling back to the next-newest checkpoint",
                    step, reason, type(exc).__name__, exc,
                )
        raise CheckpointIntegrityError(
            candidates[-1],
            f"no valid checkpoint among steps {candidates} under "
            f"{self.directory}; last error: {type(last_error).__name__}: {last_error}",
        )

    def get_metadata(self) -> dict:
        meta = self._manager.metadata()
        # Orbax returns a RootMetadata object; the user-provided dict lives in
        # `custom_metadata` (older versions returned the dict directly).
        custom = getattr(meta, "custom_metadata", meta)
        return dict(custom or {})

    def check_version(self) -> None:
        meta = self.get_metadata()
        saved = float(meta.get("checkpointer_version", CHECKPOINTER_VERSION))
        if int(saved) != int(CHECKPOINTER_VERSION):
            raise ValueError(
                f"Checkpoint major version {saved} incompatible with {CHECKPOINTER_VERSION}"
            )

    def wait(self) -> None:
        """Block until in-flight (async) saves complete. NOT on the Anakin hot
        path anymore: the runner saves from a donation-safe snapshot copy, so
        only tests and external callers that need save-visible-on-disk
        ordering (and close()) should call this."""
        self._manager.wait_until_finished()

    def close(self) -> None:
        self._manager.wait_until_finished()
        self._manager.close()


def checkpointer_from_config(config: Any, model_name: str) -> Optional[Checkpointer]:
    ckpt_cfg = config.logger.checkpointing
    if not ckpt_cfg.get("save_model", False):
        return None
    save_args = ckpt_cfg.get("save_args") or {}
    return Checkpointer(
        model_name=model_name,
        metadata=config.to_dict() if hasattr(config, "to_dict") else dict(config),
        checkpoint_uid=save_args.get("checkpoint_uid"),
        save_interval_steps=int(save_args.get("save_interval_steps", 1)),
        max_to_keep=save_args.get("max_to_keep", 1),
        keep_period=save_args.get("keep_period"),
    )
