"""Evaluation kernels for the linear recurrence behind every multistep estimator.

Every return/advantage estimator in ops/multistep.py reduces to ONE first-order
linear recurrence, scanned backwards over time:

    acc_t = delta_t + weight_t * acc_{t+1},        acc_T = init.

Each step is the affine map f_t(x) = delta_t + weight_t * x, and the answer at
time t is the suffix composition (f_t ∘ f_{t+1} ∘ ... ∘ f_{T-1})(init).
Composition of affine maps is associative —

    (w, d) ∘ (w', d') = (w·w', d + w·d')

— so the whole suffix family is computable in O(log T) depth instead of the
O(T) sequential chain a `lax.scan` emits. On a TPU the scan's T dependent
steps serialize the VPU; the log-depth form trades ~2x the flops for parallel
depth, which wins whenever T is larger than a few vector widths.

Three interchangeable implementations, selected per call or process-wide:

    scan    sequential `lax.scan` — the reference semantics, bit-identical to
            what every system shipped with (the default).
    assoc   `jax.lax.associative_scan` over the (weight, delta) pairs —
            log-depth, pure XLA, differs from `scan` only by float reassociation
            (float32 ≤1e-5 relative on RL-scale inputs; see tests).
    pallas  time-blocked Pallas TPU kernel: the sequential recurrence runs in
            VMEM block_t rows at a time with a cross-block carry, so HBM sees
            one stream read + one stream write instead of scan's per-step
            dispatch. Within a block the op ORDER is exactly `scan`'s, so
            float32 results are bit-identical to `scan` — compiled by Mosaic
            on the v5e and checked bitwise there at [16, 2048] and
            [128, 2048] by chip_smoke.py. The kernel is float32 only: other
            dtypes are widened around it (an fp32 accumulator, which `scan`
            does not have — documented divergence for low-precision inputs).
            The dispatch takes the kernel exactly when the backend is TPU;
            elsewhere `pallas` evaluates `scan` (same values; the Pallas
            interpreter is far slower than XLA's scan on CPU). Interpret mode
            is something a test asks for by calling the kernel directly.

`n`-step windowed folds (n_step_bootstrapped_returns) are not a suffix scan —
each output composes exactly n maps — so the `assoc`/`pallas` route uses
binary doubling over the window instead: O(log n) shifted compositions rather
than the reference's n unrolled vector passes.

The process-wide default is set once per run from `system.multistep_impl`
(`run_host.RunHost`, which both runners build first, calls
`configure_from_config` before any learner is traced); estimators also accept an explicit `impl=`
override. The default read is trace-time static: changing it never triggers a
recompile of an already-traced program.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stoix_tpu.ops.pallas_attention import _out_struct

Array = jax.Array

VALID_IMPLS = ("scan", "assoc", "pallas")

_DEFAULT_IMPL = "scan"


def _validate_impl(impl: str) -> str:
    if impl not in VALID_IMPLS:
        raise ValueError(
            f"unknown multistep impl {impl!r}; valid: {', '.join(VALID_IMPLS)}"
        )
    return impl


def set_default_impl(impl: str) -> str:
    """Set the process-wide default implementation; returns the previous one."""
    global _DEFAULT_IMPL
    previous = _DEFAULT_IMPL
    _DEFAULT_IMPL = _validate_impl(str(impl))
    return previous


def get_default_impl() -> str:
    return _DEFAULT_IMPL


def resolve_impl(impl: Optional[str]) -> str:
    """An explicit per-call impl wins; None means the process-wide default."""
    return _DEFAULT_IMPL if impl is None else _validate_impl(str(impl))


def configure_from_config(config: Any) -> str:
    """Read `system.multistep_impl` (default `scan`) and install it as the
    process default. Called by both architectures' run entry points BEFORE the
    learner is traced, so the estimators inside the jitted learner pick the
    configured kernel at trace time."""
    impl = str(config.system.get("multistep_impl", "scan"))
    set_default_impl(impl)
    return impl


@contextlib.contextmanager
def use_impl(impl: str) -> Iterator[str]:
    """Scoped default override (tests and benchmarks)."""
    previous = set_default_impl(impl)
    try:
        yield impl
    finally:
        set_default_impl(previous)


# ---------------------------------------------------------------------------
# scan: the reference sequential recurrence (bit-identity anchor)
# ---------------------------------------------------------------------------


def _scan_reverse(weight_t: Array, delta_t: Array, init: Array) -> Array:
    """acc_t = delta_t + weight_t * acc_{t+1}, scanned from T-1 down to 0.

    This is verbatim the pre-dispatch `multistep._reverse_scan` body; the
    `scan` impl must stay byte-for-byte this program (tests pin bitwise
    equality against an inlined copy)."""

    def body(acc: Array, inputs: Tuple[Array, Array]) -> Tuple[Array, Array]:
        delta, weight = inputs
        acc = delta + weight * acc
        return acc, acc

    _, out = jax.lax.scan(body, init, (delta_t, weight_t), reverse=True)
    return out


# ---------------------------------------------------------------------------
# assoc: log-depth suffix composition via jax.lax.associative_scan
# ---------------------------------------------------------------------------


def _suffix_compose(a: Tuple[Array, Array], b: Tuple[Array, Array]) -> Tuple[Array, Array]:
    """Combine for the REVERSE associative scan. With reverse=True the left
    argument is the already-combined suffix of LATER timesteps and the right
    argument is the current (earlier) element, whose map applies OUTERMOST:
    f_b ∘ f_a = (w_b·w_a, d_b + w_b·d_a)."""
    w_a, d_a = a
    w_b, d_b = b
    return w_b * w_a, d_b + w_b * d_a


def _assoc_reverse(weight_t: Array, delta_t: Array, init: Array) -> Array:
    w_cum, d_cum = jax.lax.associative_scan(
        _suffix_compose, (weight_t, delta_t), reverse=True, axis=0
    )
    # acc_t = F_t(init) where F_t is the composed suffix map at t.
    return d_cum + w_cum * init


# ---------------------------------------------------------------------------
# pallas: time-blocked sequential recurrence with a cross-block VMEM carry
# ---------------------------------------------------------------------------


def _recurrence_kernel(w_ref, d_ref, init_ref, o_ref, acc_ref, *, block_t: int):
    """One float32 time block, walked bottom row up.

    The grid's time axis is iterated LAST-block-first (the index_map reverses
    it), and TPU grids execute sequentially, so `acc_ref` legally carries the
    accumulator across blocks; it is (re)seeded from `init_ref` at the first
    grid step of each batch block. Rows are read and written as 2-D
    (1, block_b) slices: Mosaic addresses a dynamic sublane of a 32-bit ref
    directly, and rank-1 vectors are avoided."""

    @pl.when(pl.program_id(1) == 0)
    def _seed():
        acc_ref[...] = init_ref[...]

    def body(j, acc):
        row = pl.ds(block_t - 1 - j, 1)
        acc = d_ref[row, :] + w_ref[row, :] * acc
        o_ref[row, :] = acc
        return acc

    acc_ref[...] = jax.lax.fori_loop(0, block_t, body, acc_ref[...])


def _pad_tail(x: Array, axis: int, multiple: int, value: float) -> Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


_SUBLANES = 8  # rows of one float32 VMEM tile; time blocks are whole tiles


@functools.partial(jax.jit, static_argnames=("block_t", "block_b", "interpret"))
def pallas_linear_recurrence_reverse(
    weight_t: Array,
    delta_t: Array,
    init: Array,
    block_t: int = 128,
    block_b: int = 128,
    interpret: bool = False,
) -> Array:
    """Time-blocked Pallas evaluation of the reverse linear recurrence.

    Accepts [T, ...] inputs (trailing dims flattened to one lane axis) with
    `init` shaped like one timestep. The kernel itself is float32 only: other
    dtypes are widened before the call and the result narrowed after it, which
    is the fp32 accumulator the module docstring promises and keeps every
    block a whole (8, 128) float32 tile (a bfloat16 tile is 16 rows and its
    rows are packed in pairs, so a per-row walk over it is not compiled).
    Time is padded with identity maps (w=1, d=0) — the padded rows are
    processed first and leave the carry at `init` — and the batch axis is
    padded to the lane width; `block_t` is rounded up to whole tiles. The
    in-block op order is exactly `_scan_reverse`'s.

    `interpret=True` runs the Pallas interpreter and is something a test asks
    for; no training path sets it.
    """
    orig_shape = delta_t.shape
    out_dtype = delta_t.dtype
    t_len = orig_shape[0]
    w2 = weight_t.reshape(t_len, -1).astype(jnp.float32)
    d2 = delta_t.reshape(t_len, -1).astype(jnp.float32)
    init2 = init.reshape(1, -1).astype(jnp.float32)
    b_len = d2.shape[1]

    block_t = -(-min(block_t, t_len) // _SUBLANES) * _SUBLANES
    w2 = _pad_tail(w2, 0, block_t, 1.0)  # identity maps keep acc = init
    d2 = _pad_tail(d2, 0, block_t, 0.0)
    w2 = _pad_tail(w2, 1, block_b, 1.0)
    d2 = _pad_tail(d2, 1, block_b, 0.0)
    init2 = _pad_tail(init2, 1, block_b, 0.0)
    t_pad, b_pad = d2.shape
    n_t, n_b = t_pad // block_t, b_pad // block_b

    out = pl.pallas_call(
        functools.partial(_recurrence_kernel, block_t=block_t),
        # Batch blocks outer, time blocks inner (reversed by the index_map):
        # each batch block finishes its full time walk before the next starts,
        # so the single scratch row is a valid carry for all of them.
        grid=(n_b, n_t),
        in_specs=[
            pl.BlockSpec((block_t, block_b), lambda i, j, nt=n_t: (nt - 1 - j, i)),
            pl.BlockSpec((block_t, block_b), lambda i, j, nt=n_t: (nt - 1 - j, i)),
            pl.BlockSpec((1, block_b), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_t, block_b), lambda i, j, nt=n_t: (nt - 1 - j, i)),
        out_shape=_out_struct((t_pad, b_pad), jnp.float32, w2, d2, init2),
        scratch_shapes=[pltpu.VMEM((1, block_b), jnp.float32)],
        # Both grid axes carry state through the scratch accumulator; neither
        # may be parallelized across cores.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        name="linear_recurrence_reverse",
        interpret=interpret,
    )(w2, d2, init2)
    return out[:t_len, :b_len].astype(out_dtype).reshape(orig_shape)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def linear_recurrence_reverse(
    weight_t: Array, delta_t: Array, init: Array, impl: Optional[str] = None
) -> Array:
    """Suffix evaluation of acc_t = delta_t + weight_t * acc_{t+1} (acc_T =
    init) under the selected implementation. `impl=None` uses the process
    default (`system.multistep_impl`)."""
    impl = resolve_impl(impl)
    if impl == "assoc":
        return _assoc_reverse(weight_t, delta_t, init)
    if impl == "pallas":
        if jax.default_backend() == "tpu":
            return pallas_linear_recurrence_reverse(weight_t, delta_t, init)
        # Off-TPU: same values (the kernel's op order IS the scan's), and
        # XLA's scan beats the Pallas interpreter by orders of magnitude — the
        # same posture as pallas_attention.best_attention. Whether a run took
        # the kernel is read from its jaxpr (chip_smoke.py), not from here.
        return _scan_reverse(weight_t, delta_t, init)
    return _scan_reverse(weight_t, delta_t, init)


# ---------------------------------------------------------------------------
# windowed n-step folds: binary doubling over the window length
# ---------------------------------------------------------------------------


def _shift_maps(w: Array, d: Array, k: int) -> Tuple[Array, Array]:
    """Maps advanced k steps toward the future, identity-padded at the tail."""
    if k == 0:
        return w, d
    ones = jnp.ones((k,) + w.shape[1:], w.dtype)
    zeros = jnp.zeros((k,) + d.shape[1:], d.dtype)
    return (
        jnp.concatenate([w[k:], ones], axis=0),
        jnp.concatenate([d[k:], zeros], axis=0),
    )


def affine_window_fold(weight: Array, delta: Array, boot: Array, n: int) -> Array:
    """targets[t] = (f_t ∘ f_{t+1} ∘ ... ∘ f_{t+n-1})(boot[t]) in O(log n)
    passes via binary doubling, where f_j(x) = delta[j] + weight[j]·x and maps
    past the end of `weight`/`delta` are identity.

    `weight`/`delta` are time-major of length L ≥ len(boot); the output has
    `boot`'s length. Matches the reference n-step unrolled loop (which is n
    sequential vector passes) up to float reassociation.
    """
    out_len = boot.shape[0]
    # R: composed prefix of the window (span r_span); P: stride-doubling maps.
    r_w = jnp.ones_like(weight)
    r_d = jnp.zeros_like(delta)
    r_span = 0
    p_w, p_d, p_span = weight, delta, 1
    remaining = int(n)
    while remaining:
        if remaining & 1:
            # Append P AFTER R's span: R'[t] = R[t] ∘ P[t + r_span].
            s_w, s_d = _shift_maps(p_w, p_d, r_span)
            r_w, r_d = r_w * s_w, r_d + r_w * s_d
            r_span += p_span
        remaining >>= 1
        if remaining:
            s_w, s_d = _shift_maps(p_w, p_d, p_span)
            p_w, p_d = p_w * s_w, p_d + p_w * s_d
            p_span *= 2
    return r_d[:out_len] + r_w[:out_len] * boot
