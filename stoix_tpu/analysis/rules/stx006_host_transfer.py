"""STX006 — no host transfers inside jit-reachable code.

Inside a function that flows into `jax.jit`/`shard_map`/`lax.scan`/`jax.pmap`
(resolved per module by stoix_tpu.analysis.jitreach), the following force a
device→host sync or a trace-time error and must not appear:

  - `.item()` on anything (concrete-value readback),
  - `float(x)` / `int(x)` / `bool(x)` on a traced value (Python scalar
    coercion aborts tracing; static config scalars — `float(config.a.b)`,
    literals — are exempt),
  - `np.*(...)` calls on traced arrays (numpy forces materialization; dtype
    constructors like `np.float32(...)` are static and exempt),
  - `jax.device_get(...)`,
  - `jax.debug.print/callback/breakpoint(...)` without a reasoned noqa (they
    are legal but insert host callbacks on the accelerator critical path —
    the one-jitted-program design makes that a silent pipeline stall).

The jit-reachability resolution and its blind spots are documented in
docs/DESIGN.md §2.5.
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional

from stoix_tpu.analysis import jitreach
from stoix_tpu.analysis.core import FileContext, Finding, Rule, register

# np.* callees that produce static scalars/dtypes, not array materialization.
_NP_STATIC = {
    "float16",
    "float32",
    "float64",
    "int8",
    "int16",
    "int32",
    "int64",
    "uint8",
    "uint16",
    "uint32",
    "uint64",
    "bool_",
    "dtype",
    "finfo",
    "iinfo",
}
_SCALAR_CASTS = {"float", "int", "bool"}
_CONFIG_ROOTS = {"config", "cfg", "self"}


def _root_name(node: ast.AST) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _scope_bound_names(fn: ast.AST) -> set:
    """Names bound INSIDE this function's own scope: parameters plus any
    assignment/loop/with target. A name bound here holds (potentially) traced
    data; a free variable closed over from a non-traced setup scope is a
    trace-time constant (`num_samples`, `eval_max_steps`, ...)."""
    bound = set()
    args = getattr(fn, "args", None)
    if args is not None:
        for a in args.args + args.posonlyargs + args.kwonlyargs:
            bound.add(a.arg)
        if args.vararg:
            bound.add(args.vararg.arg)
        if args.kwarg:
            bound.add(args.kwarg.arg)
    for node in jitreach.walk_scope(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, (ast.Store,)):
            bound.add(node.id)
    return bound


def _is_static_cast_arg(arg: ast.AST, bound: set) -> bool:
    """True when float()/int() is provably operating on a static host value:
    literals, attribute chains rooted at a config object (hyperparameters
    read at trace time — `float(config.system.gamma)`), and free variables
    captured from an enclosing non-traced setup scope."""
    if isinstance(arg, ast.Constant):
        return True
    if isinstance(arg, ast.Name):
        return arg.id not in bound
    if isinstance(arg, (ast.Attribute, ast.Subscript)):
        # Shape/dtype metadata of a traced array is a trace-time static —
        # int(x.shape[0]) is the standard static-shape idiom, not a readback.
        probe = arg
        while isinstance(probe, (ast.Attribute, ast.Subscript)):
            if isinstance(probe, ast.Attribute) and probe.attr in (
                "shape",
                "ndim",
                "dtype",
                "size",
            ):
                return True
            probe = probe.value
        root = _root_name(arg)
        return root in _CONFIG_ROOTS or (root is not None and root not in bound)
    if isinstance(arg, ast.Call):
        # float(config.system.get("x", 1.0)), int(len(...)), int(np.prod(shape))
        root = _root_name(arg.func)
        callee = arg.func.attr if isinstance(arg.func, ast.Attribute) else (
            arg.func.id if isinstance(arg.func, ast.Name) else ""
        )
        return root in _CONFIG_ROOTS or callee in {"len", "get", "prod"}
    if isinstance(arg, ast.BinOp):
        return _is_static_cast_arg(arg.left, bound) and _is_static_cast_arg(arg.right, bound)
    if isinstance(arg, ast.BoolOp):
        return all(_is_static_cast_arg(v, bound) for v in arg.values)
    return False


def _findings_in_function(rule: Rule, ctx: FileContext, fn: ast.AST) -> List[Finding]:
    findings: List[Finding] = []
    bound = _scope_bound_names(fn)

    def flag(node: ast.AST, what: str) -> None:
        if ctx.noqa(node.lineno, rule.id):
            return
        findings.append(
            Finding(
                rule.id,
                ctx.rel,
                node.lineno,
                f"{what} inside a jit-reachable function — forces a host "
                f"sync/transfer inside the compiled program (STX006)",
            )
        )

    for node in jitreach.walk_scope(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute):
            if func.attr == "item" and not node.args and not node.keywords:
                flag(node, "`.item()` readback")
                continue
            root = _root_name(func)
            if root in ("np", "numpy") and func.attr not in _NP_STATIC:
                flag(node, f"numpy call `np.{func.attr}(...)` on traced values")
                continue
            if root == "jax" and func.attr == "device_get":
                flag(node, "`jax.device_get(...)`")
                continue
            receiver = func.value
            if (
                isinstance(receiver, ast.Attribute)
                and receiver.attr == "debug"
                and _root_name(receiver) == "jax"
            ):
                flag(node, f"`jax.debug.{func.attr}(...)` host callback")
                continue
        elif isinstance(func, ast.Name) and func.id in _SCALAR_CASTS:
            if len(node.args) == 1 and not node.keywords:
                if not _is_static_cast_arg(node.args[0], bound):
                    flag(node, f"`{func.id}(...)` scalar coercion of a traced value")
    return findings


def _check(rule: Rule, ctx: FileContext) -> List[Finding]:
    if not ctx.rel.startswith("stoix_tpu" + os.sep):
        return []
    findings: List[Finding] = []
    for fn in sorted(
        jitreach.reachable_jit_functions(ctx.tree), key=lambda n: n.lineno
    ):
        findings.extend(_findings_in_function(rule, ctx, fn))
    # One finding per line (a reachable helper can be reached twice).
    seen = set()
    unique = []
    for f in sorted(findings, key=lambda f: f.line):
        if (f.line, f.message) not in seen:
            seen.add((f.line, f.message))
            unique.append(f)
    return unique


RULE = register(
    Rule(
        id="STX006",
        order=80,
        title="no host transfers in jit",
        rationale="A hidden .item()/float()/np.* inside the jitted learn step "
        "either aborts tracing or, worse, inserts a device→host sync per step "
        "that serializes the whole pipeline.",
        check_file=_check,
        flag_snippets=(
            # .item() inside a scanned step function.
            "import jax\n\n\ndef build(step):\n"
            "    def _step(state, _):\n"
            "        loss = state.loss.item()\n"
            "        return state, loss\n"
            "    return jax.lax.scan(_step, step, None, 8)\n",
            # float() on a traced value inside a jitted function.
            "import jax\n\n\n@jax.jit\ndef f(x):\n"
            "    return float(x) + 1.0\n",
            # np.* materialization inside a shard_mapped learner.
            "import numpy as np\nfrom jax import shard_map\n\n\n"
            "def make(mesh, specs):\n"
            "    def learner(state):\n"
            "        return np.asarray(state)\n"
            "    return shard_map(learner, mesh=mesh, in_specs=specs, out_specs=specs)\n",
        ),
        clean_snippets=(
            # Static config scalars at trace time are fine.
            "import jax\n\n\n@jax.jit\ndef f(x, config):\n"
            "    return x * float(config.system.gamma)\n",
            # Host code (not jit-reachable) may do host things.
            "import numpy as np\n\n\ndef metrics(state):\n"
            "    return float(np.asarray(state.loss).item())\n",
            # A reasoned noqa keeps an intentional debug callback.
            "import jax\n\n\n@jax.jit\ndef f(x):\n"
            "    jax.debug.print('x={x}', x=x)  # noqa: STX006 — temp debug\n"
            "    return x\n",
        ),
    )
)
