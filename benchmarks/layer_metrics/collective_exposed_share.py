"""Collectives (`parallel/`): the share of the traced window in which a
chip ran a collective op (all-reduce and kin) and no other op — mean over
chips. On one chip there are none and the metric is left out, as it is
where the profiler damaged the window (`trace_reduce.sound_window`)."""

from benchmarks.harness import trace_reduce


def read(ctx):
    if ctx.trace_data is None:
        return None
    stats = trace_reduce.collective_stats(ctx.trace_data)
    learn = ctx.cell.config.get("programs", {}).get("learn")
    busy = trace_reduce.sound_window(ctx.trace_data, learn)
    if stats is None or busy is None or stats["calls"] == 0:
        return None
    return 100.0 * stats["exposed_s"] / busy["window_s"]
