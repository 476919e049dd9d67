"""Compile economy: seconds this process spent tracing Python functions to
jaxprs and lowering those to StableHLO — the part of every compilation that
no cache saves and `compile_s` does not count — over all programs, from the
program's counter `stoix_tpu_compile_seconds_total{program, stage}`
(`stoix_tpu/utils/compilecache.py`; stages `trace` and `lower`) in the newest
registry mark. None on a program without the counter. Moves `setup_s`."""

COUNTER = "stoix_tpu_compile_seconds_total"


def read(ctx):
    if not ctx.registry_marks:
        return None
    found = [
        value
        for (name, labels, field), value in ctx.registry_marks[-1][2].items()
        if name == COUNTER and field == "value" and dict(labels).get("stage") in ("trace", "lower")
    ]
    return sum(found) if found else None
