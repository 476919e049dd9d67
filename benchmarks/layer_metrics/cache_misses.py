"""Compile economy: programs this process compiled because the persistent
cache did not hold them (`compilecache.cache_stats()["misses"]`). 0 on a
warm checkout; anything else on a second run of a cell is set-up that could
have been cached. Moves `setup_s`."""


def read(ctx):
    return float(ctx.cache_stats["misses"]) if ctx.cache_stats else None
