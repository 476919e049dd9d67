"""Learner program: the share of its device time spent under the
configuration's update scope (`scopes.update`; `ppo_epoch`, the minibatch SGD
steps, for PPO); the rest is the rollout (env physics, policy inference), GAE
and bookkeeping, which no scope names yet. Over the learner executions that
lie whole inside the traced window."""

from benchmarks.harness import trace_reduce


def read(ctx):
    config = ctx.cell.config
    patterns = config.get("programs", {}).get("learn")
    scope = config.get("scopes", {}).get("update")
    if ctx.trace_data is None or not (patterns and scope):
        return None
    windows = trace_reduce.program_windows(ctx.trace_data, patterns, whole_only=True)
    whole = sum(end - start for spans in windows.values() for start, end in spans)
    scoped = trace_reduce.scope_seconds(ctx.trace_data, scope, within=windows)
    if not whole or scoped is None:
        return None
    return 100.0 * scoped / (whole * 1e-12 / len(ctx.trace_data.planes))
