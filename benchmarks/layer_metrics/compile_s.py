"""Compile economy (`utils/compilecache.py`): seconds this process spent in
backend compilation, persistent-cache loads included — the sum of
`jax.monitoring`'s backend-compile durations. Moves `setup_s`."""


def read(ctx):
    return float(sum(ctx.compiles.durations)) if ctx.compiles.durations else None
