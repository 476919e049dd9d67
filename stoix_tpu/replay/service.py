"""Host-facing device-resident replay service (docs/DESIGN.md §2.10).

`ShardedReplayService` owns a buffer whose state is a sharded pytree living
in learner-device HBM: every leaf carries a leading [num_shards] axis with
spec P(axis), so shard k's ring and priority table live ONLY in device k's
memory. Each op is ONE jitted shard_map program, built once at construction
(STX012: never per call):

  add(batch)          batch is a GLOBAL array sharded P(axis) on its item
                      axis — assembled upstream via
                      parallel.assemble_global_array from per-device shards,
                      so raw experience lands on its owning shard with no
                      host concat and no cross-device copy.
  sample(key)         the global prioritized/uniform draw of replay/core.py;
                      returns a ShardedSample of GLOBAL arrays sharded
                      P(axis) — each learner shard already holds its slice.
  set_priorities(...) scatter new priorities through global flat indices
                      (cross-shard: each shard gathers the full index set
                      and keeps what it owns).
  can_sample()        psum'd global fill >= min_fill, as a host bool.

The service also meters itself into the PR 2 registry
(`stoix_tpu_replay_*`): add/sample op+item counters, ingested-bytes vs
sampled-bytes-crossed counters (byte sizes are static properties of the
avals — zero device syncs on the hot path), and occupancy / per-shard
priority-mass gauges refreshed by the off-hot-path `observe()`.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stoix_tpu.observability import get_registry
from stoix_tpu.replay.core import ShardedSample, make_sharded_replay


def tree_bytes(tree: Any) -> int:
    """Static byte size of a pytree of arrays (shape x itemsize; no fetch)."""
    return int(
        sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))
    )


def _squeeze(tree: Any) -> Any:
    return jax.tree.map(lambda x: x[0], tree)


def _unsqueeze(tree: Any) -> Any:
    return jax.tree.map(lambda x: x[None], tree)


class ShardedReplayService:
    """Device-resident sharded replay over a mesh axis.

    `item` is one example transition (no batch axis) defining leaf shapes
    and dtypes; `capacity_per_shard` rings per shard; `sample_batch_size`
    is the GLOBAL batch drawn per sample call.
    """

    def __init__(
        self,
        mesh: Mesh,
        item: Any,
        *,
        capacity_per_shard: int,
        sample_batch_size: int,
        axis: str = "data",
        prioritized: bool = False,
        priority_exponent: float = 0.6,
        min_fill: int = 1,
    ):
        self.mesh = mesh
        self.axis = axis
        self.num_shards = int(mesh.shape[axis])
        self.capacity_per_shard = int(capacity_per_shard)
        self.sample_batch_size = int(sample_batch_size)
        self.prioritized = bool(prioritized)
        self.core = make_sharded_replay(
            capacity=self.capacity_per_shard,
            sample_batch_size=self.sample_batch_size,
            num_shards=self.num_shards,
            axis=axis,
            prioritized=self.prioritized,
            priority_exponent=priority_exponent,
            min_fill=min_fill,
        )

        sharded = NamedSharding(mesh, P(axis))
        host_state = self.core.init(item)
        self._state = jax.device_put(
            jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.num_shards,) + x.shape), host_state
            ),
            sharded,
        )

        core = self.core

        def per_shard_add(state, batch):
            return _unsqueeze(core.add(_squeeze(state), batch))

        def per_shard_sample(state, key):
            return core.sample(_squeeze(state), key)

        def per_shard_set_priorities(state, indices, priorities):
            return _unsqueeze(
                core.set_priorities(_squeeze(state), indices, priorities)
            )

        def per_shard_can_sample(state):
            return core.can_sample(_squeeze(state))

        def per_shard_stats(state):
            s = _squeeze(state)
            return core.occupancy(s)[None], jnp.sum(s.priorities)[None]

        # ONE jitted program per op, built here and reused for the service's
        # lifetime. The add donates the old state buffers — the ring is the
        # largest live allocation on a learner device, and the service owns
        # it exclusively (the previous state is never read again).
        self._add = jax.jit(
            jax.shard_map(
                per_shard_add, mesh=mesh, in_specs=(P(axis), P(axis)),
                out_specs=P(axis),
            ),
            donate_argnums=(0,),
        )
        self._sample = jax.jit(
            jax.shard_map(
                per_shard_sample, mesh=mesh, in_specs=(P(axis), P()),
                out_specs=P(axis),
            )
        )
        self._set_priorities = jax.jit(
            jax.shard_map(
                per_shard_set_priorities, mesh=mesh,
                in_specs=(P(axis), P(axis), P(axis)), out_specs=P(axis),
            ),
            donate_argnums=(0,),
        )
        self._can_sample = jax.jit(
            jax.shard_map(
                per_shard_can_sample, mesh=mesh, in_specs=(P(axis),),
                out_specs=P(),
            )
        )
        self._stats = jax.jit(
            jax.shard_map(
                per_shard_stats, mesh=mesh, in_specs=(P(axis),),
                out_specs=(P(axis), P(axis)),
            )
        )

        registry = get_registry()
        self._add_ops = registry.counter(
            "stoix_tpu_replay_add_ops_total", "Replay add programs executed"
        )
        self._add_items = registry.counter(
            "stoix_tpu_replay_add_items_total", "Transitions ingested into replay"
        )
        self._ingested_bytes = registry.counter(
            "stoix_tpu_replay_ingested_bytes_total",
            "Raw experience bytes ingested (these bytes never cross shards)",
        )
        self._sample_ops = registry.counter(
            "stoix_tpu_replay_sample_ops_total", "Replay sample programs executed"
        )
        self._sample_items = registry.counter(
            "stoix_tpu_replay_sample_items_total", "Transitions drawn from replay"
        )
        self._sampled_bytes = registry.counter(
            "stoix_tpu_replay_sampled_bytes_crossed_total",
            "Logical bytes of sampled minibatches (+ indices/probabilities) "
            "reconstructed across shards by the sample psum",
        )
        self._occupancy_gauge = registry.gauge(
            "stoix_tpu_replay_occupancy", "Items currently held, per shard"
        )
        self._mass_gauge = registry.gauge(
            "stoix_tpu_replay_priority_mass", "Total sampling mass, per shard"
        )

    # -- state ownership -----------------------------------------------------
    @property
    def state(self) -> Any:
        """The live sharded buffer state. Systems embedding replay ops in
        their own learn program (Sebulba ff_dqn) read this, thread it through
        the program, and hand the result back via `commit`."""
        return self._state

    def commit(self, new_state: Any) -> None:
        self._state = new_state

    # -- ops -----------------------------------------------------------------
    def add(self, global_batch: Any) -> None:
        """Ingest a GLOBAL batch sharded P(axis) on its leading item axis."""
        n = jax.tree.leaves(global_batch)[0].shape[0]
        self._state = self._add(self._state, global_batch)
        self._add_ops.inc()
        self._add_items.inc(n)
        self._ingested_bytes.inc(tree_bytes(global_batch))

    def sample(self, key: jax.Array) -> ShardedSample:
        out = self._sample(self._state, key)
        self._sample_ops.inc()
        self._sample_items.inc(self.sample_batch_size)
        self._sampled_bytes.inc(self.sample_bytes_crossed)
        return out

    def note_embedded_samples(self, ops: int = 1) -> None:
        """Account sample draws made by an EMBEDDED `core.sample` inside a
        system's own learn program (Sebulba ff_dqn fuses sample+update into
        one shard_map, bypassing the service's jitted sample op — the
        transport accounting must still see those draws)."""
        self._sample_ops.inc(ops)
        self._sample_items.inc(ops * self.sample_batch_size)
        self._sampled_bytes.inc(ops * self.sample_bytes_crossed)

    def set_priorities(self, indices: jax.Array, priorities: jax.Array) -> None:
        self._state = self._set_priorities(self._state, indices, priorities)

    def can_sample(self) -> bool:
        return bool(np.asarray(self._can_sample(self._state)))

    # -- accounting ----------------------------------------------------------
    @property
    def sample_bytes_crossed(self) -> int:
        """Logical interconnect payload of ONE sample op: the global batch's
        rows plus indices (int32) and probabilities (f32). The psum's ring
        schedule moves ~2(K-1)/K x this; the counter tracks the logical
        payload so the number is topology-independent."""
        row_bytes = sum(
            int(np.prod(x.shape[2:])) * x.dtype.itemsize
            for x in jax.tree.leaves(self._state.experience)
        )
        return self.sample_batch_size * (int(row_bytes) + 8)

    def observe(self) -> dict:
        """Off-hot-path telemetry refresh: fetch the [K] occupancy and
        priority-mass vectors (tiny) and publish per-shard gauges."""
        occupancy, mass = jax.tree.map(np.asarray, self._stats(self._state))
        for shard in range(self.num_shards):
            labels = {"shard": str(shard)}
            self._occupancy_gauge.set(float(occupancy[shard]), labels)
            self._mass_gauge.set(float(mass[shard]), labels)
        return {
            "occupancy": occupancy.tolist(),
            "priority_mass": [float(m) for m in mass],
        }

    def stats(self) -> dict:
        """Cumulative transport accounting (bench.py --replay reads this)."""
        return {
            "add_ops": int(self._add_ops.value()),
            "added_items": int(self._add_items.value()),
            "ingested_bytes_total": int(self._ingested_bytes.value()),
            "sample_ops": int(self._sample_ops.value()),
            "sampled_items": int(self._sample_items.value()),
            "sampled_bytes_crossed": int(self._sampled_bytes.value()),
        }


def service_from_config(
    mesh: Mesh, item: Any, config: Any, axis: str = "data"
) -> Optional["ShardedReplayService"]:
    """Build a service from `system.replay` + the global buffer/batch totals
    (None when replay.impl != sharded). Capacity and batch divide over the
    axis exactly like off_policy_core's per-shard sizing."""
    replay_cfg = dict(config.system.get("replay") or {})
    if str(replay_cfg.get("impl", "local")) != "sharded":
        return None
    n_shards = int(mesh.shape[axis])
    capacity = max(1, int(config.system.total_buffer_size) // n_shards)
    batch = int(config.system.total_batch_size)
    return ShardedReplayService(
        mesh,
        item,
        capacity_per_shard=capacity,
        sample_batch_size=batch,
        axis=axis,
        prioritized=bool(replay_cfg.get("prioritized", False)),
        priority_exponent=float(replay_cfg.get("priority_exponent", 0.6)),
        min_fill=max(1, int(replay_cfg.get("min_fill", batch))),
    )
