"""What the token policy's per-layer readers share (a sibling of
program_reads.py, which stays as it is): device seconds of the learner
program under SEVERAL scopes of the program's name table at once — the
block's `moe_experts` under `rollout` is the decode, under `update_epoch` the
teacher-forced update — and the roofline share of such a part. A program
without these scopes (the tree before PR 25) gives None, and the metric is
left out."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from benchmarks.harness import peaks, program_reads, trace_reduce

# XLA:TPU emits `jax.lax.ragged_dot` as grouped-matmul kernels whose trace
# events carry no framework path: their op_name is the kernel's own name,
# `ragged-dot-none`, with no scope before it. They are found by that name,
# belong to the scopes below, and are placed
# in a phase by the scoped loop they run inside (the rollout's scan and the
# epoch's minibatch scan both carry their scope on the loop op itself).
PATHLESS_KERNELS = {"ragged-dot": ("moe", "moe_experts")}


def learner_windows(ctx: Any) -> Optional[Tuple[trace_reduce.Trace, Dict[str, list], float]]:
    """(trace with transform wrappers stripped, the learner program's whole
    executions by chip, their number as a mean over chips)."""
    patterns = ctx.cell.config.get("programs", {}).get("learn")
    if ctx.trace_data is None or not patterns:
        return None
    trace = program_reads.unwrapped(ctx.trace_data)
    windows = trace_reduce.program_windows(trace, patterns, whole_only=True)
    executions = sum(len(v) for v in windows.values()) / max(1, len(trace.planes))
    return (trace, windows, executions) if executions else None


def _union(trace: trace_reduce.Trace, plane: str, flags: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    chip = trace.ops[plane]
    mask = flags[chip.kind] if chip.kind.size else np.zeros(0, bool)
    return trace_reduce.merged_arrays(chip.start[mask], chip.end[mask])


def _flags(trace: trace_reduce.Trace, wanted: Callable[[trace_reduce.OpKind], bool]) -> np.ndarray:
    return np.fromiter((bool(wanted(k)) for k in trace.kinds), bool, len(trace.kinds))


def scoped_seconds(
    trace: trace_reduce.Trace, windows: Dict[str, list], keys: Sequence[str],
    only: Optional[Callable[[trace_reduce.OpKind], bool]] = None,
) -> Optional[float]:
    """Device seconds (mean over chips) inside `windows` covered by ops whose
    framework path has EVERY scope of `keys` as a component (and for which
    `only(kind)` holds, if given), plus the pathless kernels that belong to
    the LAST key, where they run inside the other keys' scopes. None if the
    program's table lacks a key."""
    scopes = [program_reads.program_scope(key) for key in keys]
    if not all(scopes) or not trace_reduce.has_paths(trace):
        return None
    parts = lambda kind: kind.path.split("/")
    by_path = _flags(trace, lambda k: all(s in parts(k) for s in scopes) and (only is None or only(k)))
    kernels = tuple(name for name, owners in PATHLESS_KERNELS.items() if keys[-1] in owners)
    pathless = _flags(
        trace, lambda k: "/" not in k.path and (k.path or k.name).startswith(kernels)
        and (only is None or only(k))
    ) if kernels else None
    around = _flags(trace, lambda k: all(s in parts(k) for s in scopes[:-1])) if len(scopes) > 1 else None
    sums = []
    for plane in trace.planes:
        starts, ends = _union(trace, plane, by_path)
        if pathless is not None:
            extra = _union(trace, plane, pathless)
            if around is not None:  # only the part inside the enclosing scopes
                extra = _intersection(extra, _union(trace, plane, around))
            starts, ends = trace_reduce.merged_arrays(
                np.concatenate([starts, extra[0]]), np.concatenate([ends, extra[1]])
            )
        inside = trace_reduce.merge(windows.get(plane, []))
        bounds = (
            np.asarray([s for s, _ in inside], np.int64), np.asarray([e for _, e in inside], np.int64)
        )
        sums.append(float(trace_reduce.overlap((starts, ends), bounds)))
    return sum(sums) / len(sums) * 1e-12 if sums else None


def _intersection(a: Tuple[np.ndarray, np.ndarray], b: Tuple[np.ndarray, np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
    """The merged union `a` cut to the merged union `b` (both sorted)."""
    starts, ends = [], []
    j = 0
    for start, end in zip(a[0].tolist(), a[1].tolist()):
        while j < len(b[0]) and b[1][j] <= start:
            j += 1
        k = j
        while k < len(b[0]) and b[0][k] < end:
            starts.append(max(start, int(b[0][k])))
            ends.append(min(end, int(b[1][k])))
            k += 1
    return np.asarray(starts, np.int64), np.asarray(ends, np.int64)


def learner_share(ctx: Any, keys: Sequence[str]) -> Optional[float]:
    """Percent of the learner program's device time under all of `keys`."""
    found = learner_windows(ctx)
    if found is None:
        return None
    trace, windows, _ = found
    whole = sum(end - start for spans in windows.values() for start, end in spans)
    scoped = scoped_seconds(trace, windows, keys)
    if not whole or scoped is None:
        return None
    return 100.0 * scoped / (whole * 1e-12 / len(trace.planes))


def roofline_share(
    ctx: Any, keys: Sequence[str], cost_key: str, calls_per_update: float = 1.0,
    only: Optional[Callable[[trace_reduce.OpKind], bool]] = None,
) -> Optional[float]:
    """Least seconds of `ctx.shapes[cost_key]` (a {"flops", "bytes"} of one
    call; `calls_per_update` calls an update) on the chip's peaks, over the
    device time under `keys`, over the learner's whole executions."""
    found = learner_windows(ctx)
    cost = ctx.shapes.get(cost_key)
    if found is None or not cost:
        return None
    trace, windows, executions = found
    scoped = scoped_seconds(trace, windows, keys, only)
    if not scoped:
        return None
    calls = executions * ctx.shapes.get("updates_per_tick", 1) * calls_per_update
    # Each call is bound by its own slower peak; the calls are alike.
    least = peaks.least_seconds(cost["flops"], cost["bytes"], ctx.device["kind"])
    return 100.0 * least["seconds"] * calls / scoped
