"""Shuffled minibatches for the PPO learners' epoch / minibatch SGD scans.

Contract: from the same `shuffle_key`, minibatch `m` of an epoch is
`flat[jax.random.permutation(shuffle_key, N)[m*B:(m+1)*B]]` for every leaf
of `data` flattened to `[N, ...]` — the arrays, bit for bit, that "permute,
`take` every leaf, reshape to `[M, B, ...]`, scan" hands the SGD step. What
changes is how many row gathers it takes (docs/DESIGN.md §2.7a, PERF.md §6
PR 24: on the v5e a row gather costs per ROW, not per byte):

  * the narrow 32-bit leaves the step reads are packed ONCE, before the epoch
    scan, into one row-major slab of `_LANES`-word rows (bitcast to uint32,
    so no value changes; a sample of at most 64 words shares a row with its
    neighbours), and each minibatch is ONE gather of slab rows inside the
    minibatch scan; the shuffled copy of the whole batch is never built;
  * a leaf that is wide by itself (`_LANES` words a sample or more) or not 32
    bits wide is gathered alone: packing it would only copy it. So is a leaf
    the step does not read, and XLA deletes that gather as dead code;
  * under a `vmap` whose axis has size 1 (the Anakin learner's
    `update_batch_size` = 1) the permutation's sorts and the gathers run on
    the unbatched operands; a larger axis maps over lanes, each lane with its
    own permutation.
"""

from __future__ import annotations

import math
from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from stoix_tpu.observability import SCOPES, annotate, get_registry
from stoix_tpu.utils.jax_utils import merge_leading_dims

# Words in a slab row; a leaf of this many 32-bit words a sample or more
# (pixel frames, stacked observations) fills rows by itself and is gathered
# alone. Measured on the v5e (PERF.md §6, PR 24), one epoch's gather of
# 4,194,304 rows of 39 useful words: rows of 39 or 40 words 186 ms, 48 217 ms
# (laid out samples-along-lanes: a row is W scattered words), 64 and 96 50 ms
# (XLA pads them to 128 in a temporary of its own), 128 45.5 ms, 256 57.9 ms;
# the six per-leaf gathers this replaces 404 ms.
_LANES = 128

# What the last traced learner's shuffle was made of (trace-time facts).
_GAUGE_NAME = "stoix_tpu_minibatch_shuffle"
_GAUGE_HELP = (
    "minibatch shuffle of the last traced PPO learner, by field: packed_leaves, "
    "packed_words (a sample), slab_words (the padded row), alone_leaves (read by "
    "the SGD step, gathered alone), unbatched (1 = a size-1 vmap axis was squeezed)"
)


def _set_gauge(field: str, value: float) -> None:
    get_registry().gauge(_GAUGE_NAME, _GAUGE_HELP).set(value, {"field": field})


def _unbatched_when_single(fn: Callable) -> Callable:
    """`fn` with a batching rule that squeezes a batch axis of size 1, runs
    `fn` on the unbatched operands and puts the axis back; a larger axis is
    mapped as `vmap` would map it."""
    wrapped = jax.custom_batching.custom_vmap(fn)

    @wrapped.def_vmap
    def _rule(axis_size, in_batched, *args):
        if axis_size == 1:
            _set_gauge("unbatched", 1)
            squeezed = [a[0] if b else a for a, b in zip(args, in_batched)]
            return wrapped(*squeezed)[None], True
        in_axes = [0 if b else None for b in in_batched]
        return jax.vmap(fn, in_axes=in_axes)(*args), True

    return wrapped


_take_rows = _unbatched_when_single(
    # "clip": a permutation's indices are in bounds, and the default "fill"
    # costs a select over every gathered row (1 GB a Sebulba minibatch).
    lambda operand, rows: jnp.take(operand, rows, axis=0, mode="clip")
)


def _permutation(key: jax.Array, n: int) -> jax.Array:
    return _unbatched_when_single(lambda k: jax.random.permutation(k, n))(key)


def _slab_shape(words: int, num_envs: int) -> Tuple[int, int]:
    """(samples a slab row, words a sample in it) for `words` packed words a
    sample: up to `_LANES` a sample takes a power-of-two share of one
    `_LANES`-word row and shares the row with its neighbours (as many as
    divide `num_envs`, so a row never spans two time steps); more takes
    whole rows."""
    if words > _LANES:
        return 1, -(-words // _LANES) * _LANES
    width = 1 << (words - 1).bit_length()
    group = math.gcd(_LANES // width, num_envs)
    return group, width


def _pack(group: int, width: int, leaves: List[jax.Array]) -> jax.Array:
    """32-bit leaves `[T, E, ...]` -> slab `uint32[T*E/group, group*width]`:
    sample `n`'s words, zero-padded to `width`, are words `(n % group) *
    width ...` of row `n // group`. One time step at a time: concatenated in
    one piece, every one-word leaf would first be laid out `[T*E, 1]`, padded
    to 128 lanes (14 GB of temporaries at the benchmark's 4,194,304 samples;
    `T` times less this way)."""

    def _step_rows(step_leaves):
        num_envs = step_leaves[0].shape[0]
        columns = [
            jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(num_envs, -1)
            for x in step_leaves
        ]
        pad = width - sum(c.shape[1] for c in columns)
        if pad:
            columns.append(jnp.zeros((num_envs, pad), jnp.uint32))
        return jnp.concatenate(columns, axis=1).reshape(num_envs // group, group * width)

    pack = _unbatched_when_single(
        lambda *xs: jax.lax.map(_step_rows, xs).reshape(-1, group * width)
    )
    return pack(*leaves)


def _leaves_read(step: Callable, carry: Any, minibatch: Any) -> List[bool]:
    """Which leaves of `minibatch` `step(carry, minibatch)` reads, from one
    abstract trace of it: an input that no equation takes and no output
    returns is dead. Errs on the side of "read" (an input handed whole to a
    `jit` or `remat` inside the step counts), which only costs slab width."""
    jaxpr = jax.make_jaxpr(step)(carry, minibatch).jaxpr
    used = {v for eqn in jaxpr.eqns for v in eqn.invars if isinstance(v, jex_core.Var)}
    used.update(v for v in jaxpr.outvars if isinstance(v, jex_core.Var))
    return [v in used for v in jaxpr.invars[len(jax.tree.leaves(carry)):]]


def shuffled_minibatch_epoch(
    step: Callable[[Any, Any], Tuple[Any, Any]],
    carry: Any,
    data: Any,
    num_minibatches: int,
) -> Callable[[Any, jax.Array], Tuple[Any, Any]]:
    """Pack `data` (leaves `[T, E, ...]`) once; return `epoch(carry,
    shuffle_key)`, which scans `step(carry, minibatch)` over the
    `num_minibatches` shuffled minibatches of one epoch and returns `(carry,
    stacked step outputs)`. Call this before the epoch scan and `epoch`
    inside it. `carry` only types the abstract trace that finds the leaves
    `step` reads. `T*E` must divide by `num_minibatches`."""
    num_minibatches = int(num_minibatches)
    leaves, treedef = jax.tree.flatten(data)
    num_samples = leaves[0].shape[0] * leaves[0].shape[1]
    with annotate(SCOPES["minibatch_shuffle"]):
        flat = [merge_leading_dims(x, 2) for x in leaves]
        head = treedef.unflatten([x[: num_samples // num_minibatches] for x in flat])
        read = _leaves_read(step, carry, head)
        words = [math.prod(x.shape[1:]) for x in flat]
        packed = [
            r and x.dtype.itemsize == 4 and w < _LANES
            for r, x, w in zip(read, flat, words)
        ]
        _set_gauge("unbatched", 0)
        packed_words = sum(w for w, p in zip(words, packed) if p)
        slab = None
        if packed_words:
            group, width = _slab_shape(packed_words, leaves[0].shape[1])
            slab = _pack(group, width, [x for x, p in zip(leaves, packed) if p])
    _set_gauge("packed_leaves", sum(packed))
    _set_gauge("packed_words", packed_words)
    _set_gauge("slab_words", 0 if slab is None else slab.shape[1])
    _set_gauge("alone_leaves", sum(r and not p for r, p in zip(read, packed)))

    def _slab_samples(samples: jax.Array) -> jax.Array:
        """uint32[B, width]: the packed words of `samples`, from ONE gather."""
        rows = _take_rows(slab, samples // group)
        if group == 1:
            return rows
        # The sample's share of its row, as a masked sum over the row's shares
        # (one term is not zero, so the sum is exact): on the v5e 114 ms an
        # epoch of 8,388,608 samples with the SGD step's reads behind it,
        # against 183 for a chain of selects over lane slices and 144 for
        # unshared 128-word rows (PERF.md §6, PR 24).
        shares = rows.reshape(-1, group, width)
        mine = (samples % group)[:, None] == jnp.arange(group)[None, :]
        return jnp.where(mine[:, :, None], shares, jnp.uint32(0)).sum(axis=1, dtype=jnp.uint32)

    def _minibatch(samples: jax.Array) -> Any:
        gathered = None if slab is None else _slab_samples(samples)
        out, offset = [], 0
        for x, w, p in zip(flat, words, packed):
            if p:
                column = gathered[:, offset : offset + w].reshape((-1,) + x.shape[1:])
                out.append(jax.lax.bitcast_convert_type(column, x.dtype))
                offset += w
            else:
                out.append(_take_rows(x, samples))
        return treedef.unflatten(out)

    def _body(carry: Any, samples: jax.Array) -> Tuple[Any, Any]:
        with annotate(SCOPES["minibatch_shuffle"]):
            minibatch = _minibatch(samples)
        return step(carry, minibatch)

    def epoch(carry: Any, shuffle_key: jax.Array) -> Tuple[Any, Any]:
        with annotate(SCOPES["minibatch_shuffle"]):
            permutation = _permutation(shuffle_key, num_samples)
        return jax.lax.scan(_body, carry, permutation.reshape(num_minibatches, -1))

    return epoch
