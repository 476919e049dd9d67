"""Global device mesh + sharding helpers — the heart of the TPU-native design.

The reference distributes with single-host `jax.pmap(axis_name="device")` and a
nested `vmap(axis_name="batch")` (reference ff_ppo.py:361-365,487-489,
SURVEY.md §2.3). Here there is ONE global `jax.sharding.Mesh` spanning every
chip in the job (multi-host included) with named axes:

    "data"   — environment / batch sharding; gradients pmean over it, riding
               ICI within a slice and DCN across slices.
    (more axes — "model", "sequence" — can be added per system; helpers below
    are axis-generic.)

Learner steps are written per-shard and wrapped with `jax.shard_map`; inputs
and learner state live as global arrays with NamedShardings, so checkpointing
saves globals directly and there is no `unreplicate_*` dance.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def create_mesh(
    axes: Optional[Dict[str, int]] = None, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build a Mesh from {axis_name: size}; one size may be -1 (inferred).

    Defaults to a pure data-parallel mesh over all devices in the job
    (jax.devices() is global across hosts after jax.distributed.initialize).
    """
    devices = list(devices if devices is not None else jax.devices())
    axes = dict(axes or {"data": -1})
    sizes = list(axes.values())
    n = len(devices)
    if sizes.count(-1) > 1:
        raise ValueError("At most one mesh axis may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1])) if len(sizes) > 1 else 1
        if n % known != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes {axes}")
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(f"Mesh axes {dict(zip(axes, sizes))} do not cover {n} devices")
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, tuple(axes.keys()))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh, axis: str = "data", rank_axis: int = 0) -> NamedSharding:
    """Shard leading (or given) array axis over a mesh axis."""
    spec = [None] * (rank_axis + 1)
    spec[rank_axis] = axis
    return NamedSharding(mesh, P(*spec))


def shard_leading_axis(tree: Any, mesh: Mesh, axis: str = "data") -> Any:
    """Device-put a host pytree with its leading axis sharded over `axis`."""
    sharding = NamedSharding(mesh, P(axis))

    def put(x: Any) -> jax.Array:
        x = jax.numpy.asarray(x)
        spec = P(*([axis] + [None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree)


def replicate(tree: Any, mesh: Mesh) -> Any:
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(jax.numpy.asarray(x), sharding), tree)


def axis_size(mesh: Mesh, axis: str) -> int:
    return int(mesh.shape[axis])


def assemble_global_array(
    per_device_arrays: Sequence[jax.Array], mesh: Mesh, axis: str = "data",
    array_axis: int = 0,
) -> jax.Array:
    """Build one global array from per-device shards without host concat —
    the Sebulba trajectory hand-off primitive, called by the batch sources of
    `sebulba/sources.py` (replaces the reference's `jax.device_put_sharded`,
    reference sebulba/ff_ppo.py:263; see SURVEY.md §7.1.3).

    `array_axis` names the array dimension the shards tile (and the mesh
    axis shards): 0 for leading-axis items (the replay service's transition
    ingestion), 1 for `[T, E]` trajectories whose ENV axis is split across
    learner devices — assembling those on axis 0 would concatenate
    different devices' trajectories along TIME, which silently corrupts
    every cross-step computation downstream (GAE bootstrapping across the
    device seam).
    """
    shard = per_device_arrays[0]
    global_shape = list(shard.shape)
    global_shape[array_axis] = shard.shape[array_axis] * len(per_device_arrays)
    spec_slots: list = [None] * shard.ndim
    spec_slots[array_axis] = axis
    spec = P(*spec_slots)
    return jax.make_array_from_single_device_arrays(
        tuple(global_shape), NamedSharding(mesh, spec), list(per_device_arrays)
    )


# LRU of jitted replicate-identities: move-to-end on hit, evict ONE oldest
# entry at capacity (never clear wholesale — dropping the entire cache on the
# 65th signature would silently recompile every signature thereafter).
_FETCH_GLOBAL_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_FETCH_GLOBAL_CACHE_SIZE = 64


def fetch_global_async(tree: Any, mesh: Mesh) -> Any:
    """DISPATCH the device half of a global fetch without touching the host.

    Single-process: the tree is returned as-is — device arrays fetch directly
    at materialize() time. Multi-process: enqueue the replicate collective
    (sharded globals span non-addressable devices and cannot be fetched
    directly) and return the still-on-device replicated tree; every process
    must call this, it runs a collective. Splitting dispatch from the host
    copy lets the pipelined Anakin host loop enqueue the collective BEFORE the
    next `learn` dispatch, so materialize() never queues behind a full
    training window. The jitted identity is memoized per tree signature so
    repeated host-loop calls hit the compile cache.
    """
    if jax.process_count() == 1:
        return tree
    leaves, treedef = jax.tree.flatten(tree)
    cache_key = (treedef, tuple((l.shape, str(l.dtype)) for l in leaves), id(mesh))
    fn = _FETCH_GLOBAL_CACHE.get(cache_key)
    if fn is None:
        while len(_FETCH_GLOBAL_CACHE) >= _FETCH_GLOBAL_CACHE_SIZE:
            _FETCH_GLOBAL_CACHE.popitem(last=False)
        shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
        fn = jax.jit(lambda t: t, out_shardings=shardings)
        _FETCH_GLOBAL_CACHE[cache_key] = fn
    else:
        _FETCH_GLOBAL_CACHE.move_to_end(cache_key)
    return fn(tree)


def materialize(tree: Any) -> Any:
    """Host-materialize a (possibly in-flight) device tree as numpy — the
    blocking half of fetch_global_async. Blocks only until the arrays' own
    producers finish, not until the whole device queue drains."""
    return jax.tree.map(np.asarray, tree)


def fetch_global(tree: Any, mesh: Mesh) -> Any:
    """Bring (possibly sharded) global arrays to the host as numpy.

    Distinct from distributed.process_allgather, which gathers HOST-LOCAL
    values. Synchronous convenience wrapper; the pipelined host loop uses the
    fetch_global_async / materialize halves separately.
    """
    return materialize(fetch_global_async(tree, mesh))
