"""One Sebulba actor's rollout, stored once, where it is read (docs/DESIGN.md
§3).

An actor collects `rollout_length` transitions a step at a time and hands the
learner, for every leaf, one `[T, E/n, ...]` array on each of its `n` devices.
A leaf goes where its steps arrive:

  * **Host leaves** (whatever the env returns as numpy: a C++/EnvPool pool's
    reward, done, next_obs, episode metrics) are copied at step `t` into row
    `t` of a `[T, E, ...]` numpy array made once, and cross to each learner
    device in one `jax.device_put` of its `[T, E/n]` slice. `device_put`
    returns before the bytes are read, so an actor owns TWO sets of these
    arrays, alternates them, and before it writes into a set again waits for
    the transfers made from it two rollouts earlier (by then long over).
  * **Device leaves** (`jax.Array`s: the staged observation, action, value,
    log-prob; with a pure-JAX env twin every leaf) stay where they are and are
    stacked and cut into the `n` slices by ONE jitted program at the end.

Which path a leaf takes is read from its type at every step; nothing is
configured. The values are those of `jnp.stack` over the per-step leaves
followed by `jnp.split(..., n, axis=1)` and a `device_put` a slice, bit for
bit, dtypes canonicalised as `jnp.asarray` would.

A reader that takes items and not trajectories (the replay service) gets them
from `FlatRolloutStorage`: every leaf as `[T*E, ...]`, cut along that axis into
`[T*E/n, ...]`, bit for bit the stack / reshape / `jnp.split(..., n, axis=0)`.
"""

from __future__ import annotations

import functools
import weakref
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _cut(xp: Any, stacked: Any, n: int, flat: bool) -> List[Any]:
    """A `[T, E, ...]` leaf as `n` equal slices: along the env axis, or, `flat`,
    as `[T*E, ...]` along that one (with one learner device the array IS the
    slice). `xp` is numpy for host rows and jax.numpy inside the program."""
    if flat:
        stacked = stacked.reshape((-1,) + stacked.shape[2:])
    return xp.split(stacked, n, axis=0 if flat else 1) if n > 1 else [stacked]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _stack_and_cut(n: int, flat: bool, rows: List[List[jax.Array]]) -> List[List[jax.Array]]:
    """`rows[leaf][t]` -> `[leaf][device]`: every leaf's steps stacked to
    `[T, E, ...]` and cut into the `n` learner devices' slices."""
    return [_cut(jnp, jnp.stack(steps), n, flat) for steps in rows]


def _send(rows: np.ndarray, device: jax.Device) -> jax.Array:
    # The CPU client may take a 64-byte-aligned numpy buffer as the array's
    # own memory instead of copying it, and these rows are written again two
    # rollouts on: a CPU device is given a copy to keep.
    if device.platform == "cpu":
        rows = rows.copy()
    return jax.device_put(rows, device)


class RolloutStorage:
    """`add` a transition a step, `finish` after `rollout_length` of them.
    Owned and called by one actor thread."""

    _flat = False  # slices are `[T, E/n, ...]`, cut along the env axis

    def __init__(self, rollout_length: int, learner_devices: Sequence[jax.Device]) -> None:
        self._length = int(rollout_length)
        self._devices = list(learner_devices)
        # Two sets of host rows, leaf index -> [T, E, ...], and weak
        # references to the device arrays last made from each (weak: a payload
        # the learner is done with frees its device memory as before).
        self._host: Tuple[Dict[int, np.ndarray], ...] = ({}, {})
        self._in_flight: Tuple[List[Any], ...] = ([], [])
        self._set = 0
        self._steps: Dict[int, List[jax.Array]] = {}  # leaf index -> its T device arrays
        self._treedef: Any = None
        self._t = 0

    def _await_transfers(self) -> None:
        pending = self._in_flight[self._set]
        for ref in pending:
            array = ref()
            if array is not None and not array.is_deleted():
                array.block_until_ready()
        pending.clear()

    def add(self, transition: Any) -> None:
        """Row `t` of the rollout: a pytree whose leaves are `[E, ...]`."""
        leaves, self._treedef = jax.tree.flatten(transition)
        if self._t == 0:
            self._await_transfers()
        host = self._host[self._set]
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, jax.Array):
                self._steps.setdefault(i, []).append(leaf)
                continue
            rows = host.get(i)
            if rows is None:
                leaf = np.asarray(leaf)
                rows = host[i] = np.empty(
                    (self._length,) + leaf.shape, jax.dtypes.canonicalize_dtype(leaf.dtype)
                )
            rows[self._t] = leaf
        self._t += 1

    def finish(self) -> Tuple[Any, Any]:
        """`(payload, stored)`, both shaped like a transition. A payload leaf
        is the list of the learner devices' `[T, E/n, ...]` arrays; a `stored`
        leaf is the host rows `[T, E, ...]` (a view of a set that is written
        again: see `host_copy`) or, for a device leaf, that same list."""
        flat = self._flat
        n = len(self._devices)
        host = self._host[self._set]
        uneven = [i for i, steps in self._steps.items() if len(steps) != self._length or i in host]
        if self._t != self._length or uneven:
            raise ValueError(
                f"rollout of {self._length} steps finished after {self._t}, or leaves {uneven} "
                "arrived on the host at some steps and on a device at others"
            )
        # One program for the leaves of each device the steps live on (with a
        # pure-JAX env twin on the host CPU beside the actor's chip: two).
        by_device: Dict[Any, List[int]] = {}
        for i, steps in self._steps.items():
            by_device.setdefault(steps[0].device, []).append(i)
        stacked: Dict[int, List[jax.Array]] = {}
        for indices in by_device.values():
            cut = _stack_and_cut(n, flat, [self._steps[i] for i in indices])
            for i, slices in zip(indices, cut):
                stacked[i] = [jax.device_put(s, d) for s, d in zip(slices, self._devices)]
        sent = {
            i: [_send(s, d) for s, d in zip(_cut(np, rows, n, flat), self._devices)]
            for i, rows in host.items()
        }
        self._in_flight[self._set].extend(
            weakref.ref(array) for arrays in sent.values() for array in arrays
        )
        self._steps = {}
        self._t = 0
        self._set ^= 1

        def as_transition(leaves: Dict[int, Any]) -> Any:
            return jax.tree.unflatten(
                self._treedef, [leaves[i] for i in range(self._treedef.num_leaves)]
            )

        return as_transition({**stacked, **sent}), as_transition({**stacked, **host})

    def host_copy(self, stored: Any) -> Any:
        """A copy on the host of (a subtree of) the second tree `finish`
        returns: of the host rows themselves — a copy, because their set is
        written again — or, for a device leaf, of the learner devices' slices."""

        def to_host(leaf: Any) -> np.ndarray:
            if isinstance(leaf, np.ndarray):
                return leaf.copy()
            return np.concatenate([np.asarray(s) for s in leaf], axis=0 if self._flat else 1)

        return jax.tree.map(to_host, stored, is_leaf=lambda x: isinstance(x, list))


class FlatRolloutStorage(RolloutStorage):
    """The same rollout for a reader of items: slices are `[T*E/n, ...]`."""

    _flat = True
