"""Small JAX helpers (reference stoix/utils/jax_utils.py:12-115)."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def scale_gradient(x: jax.Array, scale: float) -> jax.Array:
    """Identity forward, gradient scaled by `scale` on the way back."""
    return x * scale + jax.lax.stop_gradient(x) * (1.0 - scale)


def count_parameters(params: Any) -> int:
    return int(sum(jnp.size(leaf) for leaf in jax.tree.leaves(params)))


def merge_leading_dims(x: jax.Array, num_dims: int) -> jax.Array:
    return x.reshape((-1,) + x.shape[num_dims:])


def tree_merge_leading_dims(tree: Any, num_dims: int) -> Any:
    return jax.tree.map(lambda x: merge_leading_dims(x, num_dims), tree)


def select_pytree(pred: jax.Array, on_true: Any, on_false: Any) -> Any:
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), on_true, on_false)


def aot_warmup(jit_fn: Any, *example_args: Any) -> Any:
    """AOT-compile an ALREADY-jitted callable for the given example arguments
    and return the compiled executable. A lowering or compile error RAISES
    here, at the warmup site: returning the un-compiled function instead would
    move the failure (a Mosaic refusal of a Pallas kernel, an HBM overflow) to
    the first call inside the timed loop, where it reads as something else.

    A plain Python wrapper with no `.lower` (a recording wrapper around the
    jitted learner) has nothing to compile ahead of time and is returned as
    is; the jit inside it compiles — and fails, if it fails — on its first
    call. Wrappers that want the warmup forward `.lower` to their inner jit
    (population/runner.py does).

    Donation declared on the jit (donate_argnums) is preserved by the compiled
    executable. The Anakin runner uses this to pay the learner's XLA compile
    BEFORE the timed host loop, so the first eval window's steps_per_second is
    a real throughput number rather than compile time."""
    if not hasattr(jit_fn, "lower"):
        return jit_fn
    return jit_fn.lower(*example_args).compile()
