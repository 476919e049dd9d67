"""What every configuration's reference file shares: the comparison that
decides `correct`, and the seeded inputs. The references themselves — one
plain implementation a configuration, with its tolerances — are the files
under benchmarks/references/, which a configuration names in its
`reference.module`."""

from __future__ import annotations

from typing import Any, List

import numpy as np

REFERENCE_BATCH = 4096


def max_scaled_error(got: Any, want: Any) -> float:
    """max |got - want| / max(1, |want|); inf on a shape mismatch or a
    non-finite value."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.max(np.abs(got - want) / np.maximum(1.0, np.abs(want))))


def seeded_observations(seed: int, obs_dim: int, batch: int = REFERENCE_BATCH) -> np.ndarray:
    return np.random.default_rng(seed).normal(size=(batch, obs_dim)).astype(np.float32)


def matmul_operand_dtypes(fn: Any, *args: Any) -> List[str]:
    """The dtypes of the operands of every `dot_general` and convolution in
    `fn(*args)`, read off its jaxpr (nested calls included): what the
    program's network multiplies in, whatever its inputs and outputs are."""
    import jax

    found: List[str] = []

    def walk(jaxpr: Any) -> None:
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("dot_general", "conv_general_dilated"):
                found.extend(str(v.aval.dtype) for v in eqn.invars)
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else (value,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sorted(set(found))
