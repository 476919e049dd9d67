"""Compile economy: seconds this process spent reading executables back from
the persistent compilation cache (the part of `compile_s` that is no
compilation), from the program's counter
`stoix_tpu_compile_cache_retrieval_seconds_total`
(`stoix_tpu/utils/compilecache.py`) in the newest registry mark. 0 in a run that read
nothing from the cache (a call's first); None on a program without the
counter. Moves `setup_s`."""

COUNTER = ("stoix_tpu_compile_cache_retrieval_seconds_total", (), "value")


def read(ctx):
    if not ctx.registry_marks:
        return None
    return ctx.registry_marks[-1][2].get(COUNTER)
