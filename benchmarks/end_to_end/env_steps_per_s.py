"""Training env steps per second per chip through `run_experiment`: the
program's own step count between the first and the last completion ("tick")
inside the measured interval, over the host-clock time between those two
completions, over the cell's chips. Whole ticks only (harness/clock.py), so
evaluation, fetch and logging are inside the wall and the estimate does not
depend on where the interval's edges fall."""


def read(ctx):
    return ctx.rate.steps_per_s / ctx.cell.chips if ctx.rate is not None else None
