"""Device: 1 - (union of device-op intervals) / (traced window), mean over
chips, in percent. The driver works the same share out of `device.busy_s`
and `device.window_s`. A share of the window: left out where the profiler
damaged the window (`trace_reduce.sound_window`)."""

from benchmarks.harness import trace_reduce


def read(ctx):
    if ctx.trace_data is None:
        return None
    learn = ctx.cell.config.get("programs", {}).get("learn")
    busy = trace_reduce.sound_window(ctx.trace_data, learn)
    if busy is None or busy["window_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - busy["busy_s"] / busy["window_s"])
