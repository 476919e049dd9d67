"""Learner program: the share of the traced window in which the device ran
ops under the program's `prefill` scope — the teacher-forced pass over every
sequence's prompt that writes the rings and the caches, in the learner
(beside `rollout`) and in the evaluator together (mean over chips). A share of
the window: left out where the profiler damaged the window
(`trace_reduce.sound_window`); a program without the scope gives None."""

from benchmarks.harness import program_reads, trace_reduce


def read(ctx):
    scope = program_reads.program_scope("prefill")
    if ctx.trace_data is None or not scope:
        return None
    busy = trace_reduce.sound_window(ctx.trace_data, ctx.cell.config.get("programs", {}).get("learn"))
    if busy is None:
        return None
    seconds = trace_reduce.scope_seconds(program_reads.unwrapped(ctx.trace_data), scope)
    return 100.0 * seconds / busy["window_s"] if seconds else None
