"""Set-up: what of `setup_s` no phase of the program's set-up gauge covers —
`setup_s`, less every phase of `stoix_tpu_setup_phase_seconds` (the program's
own remainder, `unspanned`, among them), less the warm-up ticks after the
first (steady state that this harness counts as set-up: from the first tick
to the tick that ended set-up). The one number that says whether the phases
add up to `setup_s`: the OS's and the harness's idea of the process's start,
and the first tick's stamp against the close of `first_tick`, are all that is
meant to be left. None on a program whose clock publishes no remainder.
Moves `setup_s`."""

GAUGE = "stoix_tpu_setup_phase_seconds"


def read(ctx):
    if not ctx.registry_marks or ctx.clock.start is None or not ctx.clock.ticks:
        return None
    phases = {
        dict(labels).get("phase"): value
        for (name, labels, field), value in ctx.registry_marks[-1][2].items()
        if name == GAUGE and field == "value"
    }
    if "unspanned" not in phases:
        return None
    warm_up_ticks = ctx.clock.start - ctx.clock.ticks[0].time
    return ctx.clock.setup_s - sum(phases.values()) - warm_up_ticks
