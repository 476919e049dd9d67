"""Kernels (XLA fusions; no Pallas kernel is on these cells' default path):
the least time the update's forward and backward matmuls need on the chip's
peaks (the driver's `shapes["update_cost"]`, from benchmarks/harness/flops.py;
peaks.json by device kind; the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s), over the device time under the configuration's update
scope (`scopes.update`, e.g. `ppo_epoch`), over the learner executions that
lie whole inside the traced window. `peaks.least_seconds` says which peak
binds (PERF.md records it: memory, for these float32 256-wide layers)."""

from benchmarks.harness import peaks, trace_reduce


def read(ctx):
    config = ctx.cell.config
    patterns = config.get("programs", {}).get("learn")
    scope = config.get("scopes", {}).get("update")
    cost = ctx.shapes.get("update_cost")
    if ctx.trace_data is None or not ctx.trace_data.planes or not (patterns and scope and cost):
        return None
    windows = trace_reduce.program_windows(ctx.trace_data, patterns, whole_only=True)
    # Mean over chips, like the scoped seconds below: the chips' traces need
    # not hold the same number of whole executions.
    executions = sum(len(v) for v in windows.values()) / len(ctx.trace_data.planes)
    scoped = trace_reduce.scope_seconds(ctx.trace_data, scope, within=windows)
    if not executions or not scoped:
        return None
    updates = executions * ctx.shapes.get("updates_per_tick", 1)
    least = peaks.least_seconds(cost["flops"] * updates, cost["bytes"] * updates, ctx.device["kind"])
    return 100.0 * least["seconds"] / scoped
