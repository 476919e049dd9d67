"""Evaluator (`evaluator.py`): the share of the traced window in which the
evaluator's XLA program ran on the device (mean over chips). The program is
found by the name patterns in the configuration's `programs.eval`. A share
of the window: left out where the profiler damaged the window
(`trace_reduce.sound_window`)."""

from benchmarks.harness import trace_reduce


def read(ctx):
    if ctx.trace_data is None:
        return None
    programs = ctx.cell.config.get("programs", {})
    patterns = programs.get("eval")
    busy = trace_reduce.sound_window(ctx.trace_data, programs.get("learn"))
    if not patterns or busy is None:
        return None
    seconds = trace_reduce.program_seconds(ctx.trace_data, patterns)
    return None if seconds is None else 100.0 * seconds / busy["window_s"]
