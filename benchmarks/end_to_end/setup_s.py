"""Process start to the start of the measured interval on the host clock:
imports, compose, env and pool build, network init, the reference check made
before the run, AOT compile or cache load, evaluator compile and the cell's
warm-up tick(s)."""


def read(ctx):
    return ctx.clock.setup_s if ctx.clock.start is not None else None
