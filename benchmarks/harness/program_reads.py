"""What the per-layer readers added with the program's span and scope names
(PR 23) share: the share of the learner program's device time under a named
scope, means of the Sebulba actors' MISC timings, and the set-up gauge. The
names come from the program's own tables (`stoix_tpu.observability.trace`),
not from the config files; a program that has no such table, scope, timing
or gauge (the tree before PR 23) gives None, and the metric is left out."""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence

from benchmarks.harness import trace_reduce

# JAX writes a scope entered directly under a transform as `vmap(gae)` or
# `transpose(jvp(ppo_minibatch))`: the scope is what is inside.
_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+$")


def program_scope(key: str) -> Optional[str]:
    """The scope's name in the program's own name table."""
    try:
        from stoix_tpu.observability.trace import SCOPES
    except ImportError:
        return None
    return SCOPES.get(key)


def unwrapped(trace: trace_reduce.Trace) -> trace_reduce.Trace:
    """The trace with the transform wrappers taken off every component of
    every op's framework path, so that `scope_seconds` finds `gae` in
    `.../vmap(gae)/...`."""
    strip = lambda path: "/".join(_WRAPPERS.sub("", part) for part in path.split("/"))
    return trace._replace(kinds=[k._replace(path=strip(k.path)) for k in trace.kinds])


def learner_scope_share(ctx: Any, key: str) -> Optional[float]:
    """Percent of the learner program's device time (its executions that lie
    whole inside the traced window, mean over chips) under the scope `key`
    of the program's name table. `update_share` is the same arithmetic for
    the config's update scope."""
    scope = program_scope(key)
    patterns = ctx.cell.config.get("programs", {}).get("learn")
    if ctx.trace_data is None or not (scope and patterns):
        return None
    trace = unwrapped(ctx.trace_data)
    windows = trace_reduce.program_windows(trace, patterns, whole_only=True)
    whole = sum(end - start for spans in windows.values() for start, end in spans)
    scoped = trace_reduce.scope_seconds(trace, scope, within=windows)
    if not whole or scoped is None:
        return None
    return 100.0 * scoped / (whole * 1e-12 / len(trace.planes))


def actor_timing_ms(ctx: Any, suffix: str) -> Optional[float]:
    """Mean, in milliseconds, of the actors' `actor<i><suffix>` rolling means
    in the MISC log events inside the interval (over actors and events), as
    `sebulba_actor_step_ms` reads `_rollout_time`."""
    if ctx.clock.start is None:
        return None
    end = ctx.clock.start + ctx.clock.seconds
    readings = [
        value
        for at, metrics in ctx.misc
        if ctx.clock.start <= at <= end
        for key, value in metrics.items()
        if key.startswith("actor") and key.endswith(suffix)
    ]
    return 1000.0 * sum(readings) / len(readings) if readings else None


def setup_phase_seconds(ctx: Any, phases: Sequence[str]) -> Optional[float]:
    """Sum of the named phases of `stoix_tpu_setup_phase_seconds{phase=...}`
    in the newest registry mark (the gauge is set during set-up, before the
    first mark). None if the program published none of them."""
    if not ctx.registry_marks:
        return None
    registry = ctx.registry_marks[-1][2]
    found = [
        value
        for (name, labels, field), value in registry.items()
        if name == "stoix_tpu_setup_phase_seconds" and field == "value"
        and dict(labels).get("phase") in phases
    ]
    return sum(found) if found and sum(found) > 0.0 else None
