"""Collectives: collective ops executed on a chip for each PPO update (an
async start/done pair counts once): collective calls in the traced window
over the learner executions in it (one seen in part counts in part)."""

from benchmarks.harness import trace_reduce


def read(ctx):
    if ctx.trace_data is None:
        return None
    patterns = ctx.cell.config.get("programs", {}).get("learn")
    stats = trace_reduce.collective_stats(ctx.trace_data)
    if not patterns or stats is None or stats["calls"] == 0:
        return None
    # Executions seen in part count in part: program seconds in the window
    # over the seconds of one whole execution.
    whole = trace_reduce.program_windows(ctx.trace_data, patterns, whole_only=True)
    spans = [end - start for v in whole.values() for start, end in v]
    seconds = trace_reduce.program_seconds(ctx.trace_data, patterns)
    if not spans or not seconds:
        return None
    executions = seconds / (sum(spans) / len(spans) * 1e-12)
    return stats["calls"] / (executions * ctx.shapes.get("updates_per_tick", 1))
