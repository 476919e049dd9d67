"""STX011 — shard_map contract checks (arity + replication claims).

Two contracts every `shard_map(fn, mesh=..., in_specs=..., out_specs=...)`
must honor, both checked statically against the mesh model:

  1. **in_specs arity vs the wrapped function's signature.** A literal
     `in_specs` tuple must be satisfiable by `fn`'s positional parameters
     (resolved module-locally like jitreach does, `functools.partial`-aware:
     bound arguments drop out of the count). Passing 2 specs to a 3-arg
     per-shard function is a TypeError only at trace time — on the
     multi-device launch, after minutes of setup.

  2. **out_specs replication claims.** An out leaf that is a CLOSED literal
     spec not naming mesh axis A claims the output is REPLICATED over A. If
     any in leaf shards over A and the wrapped function's body (transitively
     through module-local helpers) contains no collective reduction over A
     (`psum`/`pmean`/... with axis A, or any helper taking an
     `axis_name(s)=` literal naming A), each shard computes its own value and
     jax stitches shard 0's — the silent-wrong-answer class. `check_vma=True`
     catches this at trace time; this rule catches it at lint time, and
     `check_vma=False` sites (the Anakin update-batch pattern) have no other
     net at all.

Conservative by construction: unresolvable `fn` expressions, opaque/variable
specs, and bodies containing a collective with a VARIABLE axis (axis-generic
library code like ring_attention) skip the corresponding check rather than
guess.
"""

from __future__ import annotations

import ast
import os
from typing import FrozenSet, List, Optional, Set, Tuple

from stoix_tpu.analysis import meshmodel
from stoix_tpu.analysis.core import FileContext, Finding, Rule, register
from stoix_tpu.analysis.jitreach import _ModuleIndex, callee_name as _callee_name
from stoix_tpu.analysis.rules.stx007_collective_axes import _AXIS_KWARGS, _COLLECTIVES


def _resolve_wrapped(
    index: _ModuleIndex, expr: Optional[ast.AST]
) -> Tuple[Optional[List[ast.AST]], int, FrozenSet[str]]:
    """(function nodes, n positional args partial-bound, kw names bound)."""
    if expr is None:
        return None, 0, frozenset()
    if isinstance(expr, ast.Lambda):
        return [expr], 0, frozenset()
    if isinstance(expr, ast.Name):
        defs = index.functions.get(expr.id)
        if defs:
            return list(defs), 0, frozenset()
        return None, 0, frozenset()
    if (
        isinstance(expr, ast.Call)
        and _callee_name(expr.func) == "partial"
        and expr.args
    ):
        inner, n_pos, kws = _resolve_wrapped(index, expr.args[0])
        if inner is None:
            return None, 0, frozenset()
        bound_kws = frozenset(kw.arg for kw in expr.keywords if kw.arg)
        return inner, n_pos + len(expr.args) - 1, kws | bound_kws
    return None, 0, frozenset()


def _param_bounds(
    fn: ast.AST, n_bound_pos: int, bound_kws: FrozenSet[str]
) -> Tuple[int, Optional[int]]:
    """(required, maximum) positional-arg count after partial binding;
    maximum is None for *args."""
    args = fn.args
    params = list(getattr(args, "posonlyargs", [])) + list(args.args)
    n_defaults = len(args.defaults)
    flagged = [
        (p.arg, i >= len(params) - n_defaults) for i, p in enumerate(params)
    ]
    flagged = flagged[n_bound_pos:]
    flagged = [(name, has_default) for name, has_default in flagged if name not in bound_kws]
    required = sum(1 for _name, has_default in flagged if not has_default)
    maximum = None if args.vararg else len(flagged)
    return required, maximum


def _fn_label(expr: Optional[ast.AST]) -> str:
    if isinstance(expr, ast.Name):
        return f"'{expr.id}'"
    if isinstance(expr, ast.Lambda):
        return "<lambda>"
    if isinstance(expr, ast.Call) and expr.args and isinstance(expr.args[0], ast.Name):
        return f"'{expr.args[0].id}'"
    return "<wrapped function>"


def _axis_value_literals(node: ast.AST) -> Tuple[List[str], bool]:
    """(axis literals, fully_literal) for an axis_name(s) value. A variable
    (or a tuple with variable entries) is not fully literal — the body may
    reduce over ANY axis through it."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value], True
    if isinstance(node, (ast.Tuple, ast.List)):
        literals = [
            elt.value
            for elt in node.elts
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str)
        ]
        return literals, len(literals) == len(node.elts)
    return [], False


def _collective_axes(
    index: _ModuleIndex, roots: List[ast.AST]
) -> Tuple[Set[str], bool]:
    """(axis literals reduced over, wildcard) reachable from `roots`.

    Walks each root's whole subtree (nested defs included — the minibatch/
    epoch closures live inside the per-shard body) and follows references to
    module-local functions (the reward-stats-helper idiom). A collective or
    axis_name(s)= kwarg holding a VARIABLE sets wildcard: the body may reduce
    over any axis, so no replication claim can be disproved.
    """
    axes: Set[str] = set()
    wildcard = False
    visited: Set[int] = set()
    stack = list(roots)
    while stack:
        fn = stack.pop()
        if id(fn) in visited:
            continue
        visited.add(id(fn))
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                stack.extend(index.functions.get(node.id, []))
            if not isinstance(node, ast.Call):
                continue
            callee = _callee_name(node.func)
            if callee in _COLLECTIVES and len(node.args) >= 2:
                literals, fully = _axis_value_literals(node.args[1])
                axes.update(literals)
                if not fully:
                    wildcard = True
            for kw in node.keywords:
                if kw.arg in _AXIS_KWARGS:
                    literals, fully = _axis_value_literals(kw.value)
                    axes.update(literals)
                    if not fully:
                        wildcard = True
    return axes, wildcard


def _check(rule: Rule, ctx: FileContext) -> List[Finding]:
    if not ctx.rel.startswith("stoix_tpu" + os.sep):
        return []
    model = meshmodel.for_context(ctx)
    if not model.shard_map_sites:
        return []
    index = ctx.memo("module_index", lambda: _ModuleIndex(ctx.tree))
    findings: List[Finding] = []
    for site in model.shard_map_sites:
        lineno = site.call.lineno
        if ctx.noqa(lineno, rule.id):
            continue
        defs, n_pos, bound_kws = _resolve_wrapped(index, site.fn_expr)
        label = _fn_label(site.fn_expr)

        # 1. in_specs tuple arity vs the wrapped signature. Flag only when
        # EVERY resolved candidate def rejects the arity (same-name redefs).
        if site.in_top_arity is not None and defs:
            bounds = [_param_bounds(fn, n_pos, bound_kws) for fn in defs]
            arity = site.in_top_arity
            if all(
                arity < required or (maximum is not None and arity > maximum)
                for required, maximum in bounds
            ):
                required, maximum = bounds[0]
                expect = (
                    str(required)
                    if maximum == required
                    else f"{required}..{maximum if maximum is not None else '*'}"
                )
                findings.append(
                    Finding(
                        rule.id,
                        ctx.rel,
                        lineno,
                        f"shard_map in_specs has {arity} entries but {label} "
                        f"takes {expect} positional argument(s) — this "
                        f"TypeErrors only at trace time on the real launch "
                        f"(STX011)",
                    )
                )

        # 2. out_specs replication claims vs reductions in the body.
        in_axes = {a for leaf in site.in_leaves for a, _ in leaf.literal_axes()}
        if not in_axes or not defs:
            continue
        closed_out = [leaf for leaf in site.out_leaves if leaf.closed]
        if not closed_out:
            continue
        body_axes, wildcard = _collective_axes(index, defs)
        if wildcard:
            continue
        unreduced = sorted(
            axis
            for axis in in_axes
            if axis not in body_axes
            and any(not leaf.mentions(axis) for leaf in closed_out)
        )
        for axis in unreduced:
            findings.append(
                Finding(
                    rule.id,
                    ctx.rel,
                    lineno,
                    f"shard_map out_specs claim replication over mesh axis "
                    f"'{axis}' but {label} contains no collective reduction "
                    f"over '{axis}' — each shard computes a different value "
                    f"and the result is silently wrong on a multi-device "
                    f"run (STX011)",
                )
            )
    return findings


RULE = register(
    Rule(
        id="STX011",
        order=97,
        title="shard_map contract (arity + replication claims)",
        rationale="An in_specs tuple the wrapped signature cannot accept "
        "TypeErrors at trace time; an out_specs claiming replication with "
        "no reduction over the sharded axis returns shard-0's value as if "
        "it were global — the silent-wrong-answer class check_vma=False "
        "sites have no other net for.",
        check_file=_check,
        flag_snippets=(
            # Arity: two specs into a three-arg per-shard function.
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n\n\n"
            "def per_shard(state, batch, key):\n"
            "    return state\n\n\n"
            "def build(mesh):\n"
            "    return shard_map(per_shard, mesh=mesh,\n"
            '                     in_specs=(P(), P("data")), out_specs=P())\n',
            # Replication claimed with no reduction over the sharded axis.
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n\n\n"
            "def per_shard(batch):\n"
            "    return batch.mean()\n\n\n"
            "def build(mesh):\n"
            "    return shard_map(per_shard, mesh=mesh,\n"
            '                     in_specs=(P("data"),), out_specs=P())\n',
        ),
        clean_snippets=(
            # The blessed pattern: pmean over the sharded axis before a
            # replicated output; arity satisfiable via the default.
            "import jax\nfrom jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n\n\n"
            "def per_shard(batch, scale=1.0):\n"
            '    return jax.lax.pmean(batch.mean() * scale, axis_name="data")\n\n\n'
            "def build(mesh):\n"
            "    return shard_map(per_shard, mesh=mesh,\n"
            '                     in_specs=(P("data"),), out_specs=P())\n',
            # Output stays sharded: no replication claim to prove.
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n\n\n"
            "def per_shard(batch):\n"
            "    return batch * 2\n\n\n"
            "def build(mesh):\n"
            "    return shard_map(per_shard, mesh=mesh,\n"
            '                     in_specs=(P("data"),), out_specs=P("data"))\n',
            # Reduction via a module-local helper taking axis_names=.
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n"
            "from stoix_tpu.resilience import guards\n\n\n"
            "def per_shard(batch):\n"
            '    out, _ = guards.guard_update("skip", new=batch, old=batch,\n'
            '                                 axis_names=("data",))\n'
            "    return out\n\n\n"
            "def build(mesh):\n"
            "    return shard_map(per_shard, mesh=mesh,\n"
            '                     in_specs=(P("data"),), out_specs=P())\n',
        ),
    )
)
