"""The plain reference of the feed-forward PPO configurations whose actor and
critic are MLPs (`ppo_ant_mlp256`, `sebulba_ppo_cartpole_mlp`), independent
of the program's code, and what `correct` holds such a run to.

`mlp_reference` is the configuration's actor/critic forward pass in
straightforward float32 `jax.numpy` at the highest matmul precision: a stack
of `x @ W + b` with the activation between, then the head's readout. It reads
the weights out of the program's flax parameter tree by position (torso Dense
layers in order, then the head's) and shares no code with
`stoix_tpu/networks`. `gae_reference` is generalized advantage estimation
with truncation-aware resets as a Python loop over time, in numpy float64.

The configuration's `reference` block states activation, heads and the two
tolerances; `check_before` and `check_after` return {name: (error,
tolerance)} and name in `ctx.problems` whatever the run contradicts of what
the configuration file states (widths, dtypes, PPO's loop counts).

Tolerances (stated in the configuration file, `reference.mlp_tol` and
`reference.gae_tol`, because a configuration in another dtype needs others),
and why:

* MLP: the program multiplies float32 matrices at XLA's DEFAULT precision,
  which on the TPU's MXU is one bfloat16 pass with float32 accumulation
  (relative error about 2^-8 a product); the reference sets
  `jax.default_matmul_precision("highest")`. The gap depends on the weights:
  as the critic trains its values grow and the head's sum cancels more. Read
  on the chip (my chip runs, PR 22): at most 1.2e-2 in 36 runs of the cells
  (about 100 SGD steps into training), and 4.4e-2 for the critic after 768
  SGD steps at 2,048 envs, where a tolerance of 3e-2, tried after review,
  failed a run that was right. 5e-2 * max(1, |ref|) passes both and fails a
  wrong activation, a missing bias, a swapped layer or a head read in the
  wrong order (errors of order 1e-1 to 1). No tolerance can tell
  float32-at-default from bfloat16 activations, which on this chip are the
  same multiplications: the dtype checks below do that.
* GAE: the same recurrence in float32 against float64 over 16 steps:
  1e-4 * max(1, |ref|), two orders above float32 rounding (measured 2.3e-6)
  and far below any wrong discount, lambda or reset.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from benchmarks.harness import reference as compare

GAE_SHAPE = (16, 2048)


def _activation(name: str) -> Callable:
    import jax
    import jax.numpy as jnp

    table = {
        "silu": lambda x: x * jax.nn.sigmoid(x),
        "relu": lambda x: jnp.maximum(x, 0.0),
        "tanh": jnp.tanh,
    }
    if name not in table:
        raise KeyError(f"no reference activation {name!r} (known: {sorted(table)})")
    return table[name]


def dense_stack(params: Dict[str, Any]) -> List[Tuple[Any, Any]]:
    """[(kernel, bias), ...] of a flax module whose children are Dense_<i>."""
    names = sorted((k for k in params if k.startswith("Dense_")), key=lambda k: int(k[6:]))
    if not names:
        raise ValueError(f"no Dense_<i> children in {sorted(params)}")
    return [(params[k]["kernel"], params[k]["bias"]) for k in names]


def mlp_reference(
    variables: Dict[str, Any], observations: Any, spec: Dict[str, Any], head_key: str
) -> Dict[str, Any]:
    """The network's outputs on `observations` [B, obs_dim], from its flax
    variables and the configuration's `reference` block. `head_key` is
    "action_head" or "critic_head"."""
    import jax
    import jax.numpy as jnp

    tree = variables["params"]
    act = _activation(spec["activation"])
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(observations, jnp.float32)
        for kernel, bias in dense_stack(tree["torso"]):
            h = act(h @ jnp.asarray(kernel, jnp.float32) + jnp.asarray(bias, jnp.float32))
        outs = [
            h @ jnp.asarray(kernel, jnp.float32) + jnp.asarray(bias, jnp.float32)
            for kernel, bias in dense_stack(tree[head_key])
        ]
    head = spec[head_key]
    if head == "tanh_normal":
        loc, raw_scale = outs
        return {"loc": loc, "scale": jnp.logaddexp(raw_scale, 0.0) + float(spec["min_scale"])}
    if head == "categorical":
        (logits,) = outs
        return {"logits": logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)}
    if head == "scalar":
        (value,) = outs
        return {"value": value[..., 0]}
    raise KeyError(f"no reference head {head!r}")


def program_outputs(output: Any, head: str) -> Dict[str, Any]:
    """The same quantities out of what the program's network returned."""
    if head == "tanh_normal":
        base = output.distribution.base
        return {"loc": base.loc, "scale": base.scale}
    if head == "categorical":
        return {"logits": output.logits}
    if head == "scalar":
        return {"value": output}
    raise KeyError(f"no reference head {head!r}")


def check_networks(
    actor_apply: Callable, critic_apply: Callable, actor_vars: Any, critic_vars: Any,
    make_observation: Callable[[Any], Any], obs_dim: int, spec: Dict[str, Any], seed: int,
) -> Tuple[Dict[str, float], Dict[str, List[str]]]:
    """Max scaled error of each output of the program's actor and critic,
    applied to a seeded batch, against the reference; and the dtypes seen on
    the program's side ("outputs", "matmul_operands"). `make_observation`
    wraps the [B, obs_dim] array in whatever the program's network takes."""
    import jax

    obs = compare.seeded_observations(seed, obs_dim)
    wrapped = make_observation(obs)
    errors: Dict[str, float] = {}
    dtypes: Dict[str, List[str]] = {"outputs": [], "matmul_operands": []}
    for name, apply, variables, head_key in (
        ("actor", actor_apply, actor_vars, "action_head"),
        ("critic", critic_apply, critic_vars, "critic_head"),
    ):
        head = spec[head_key]
        program = lambda v, o, apply=apply, head=head: program_outputs(apply(v, o), head)
        got = jax.jit(program)(variables, wrapped)
        want = jax.jit(
            lambda v, o, head_key=head_key: mlp_reference(v, o, spec, head_key)
        )(variables, obs)
        for key in want:
            errors[f"{name}_{key}"] = compare.max_scaled_error(got[key], want[key])
        dtypes["outputs"] += [str(np.asarray(v).dtype) for v in got.values()]
        dtypes["matmul_operands"] += compare.matmul_operand_dtypes(program, variables, wrapped)
    return errors, {k: sorted(set(v)) for k, v in dtypes.items()}


def gae_reference(
    r_t: np.ndarray, discount_t: np.ndarray, lambda_: float,
    v_tm1: np.ndarray, v_t: np.ndarray, truncation_t: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """A_t = delta_t + discount_t * lambda * (1 - truncation_t) * A_{t+1},
    delta_t = r_t + discount_t * v_t - v_tm1; targets = v_tm1 + A. Time-major
    [T, B], float64, one Python step a time index."""
    r_t, discount_t, v_tm1, v_t, truncation_t = (
        np.asarray(x, np.float64) for x in (r_t, discount_t, v_tm1, v_t, truncation_t)
    )
    advantages = np.zeros_like(r_t)
    carry = np.zeros_like(r_t[0])
    for t in reversed(range(r_t.shape[0])):
        delta = r_t[t] + discount_t[t] * v_t[t] - v_tm1[t]
        carry = delta + discount_t[t] * lambda_ * (1.0 - truncation_t[t]) * carry
        advantages[t] = carry
    return advantages, v_tm1 + advantages


def check_gae(gae_fn: Callable, seed: int, impl: str, shape: Tuple[int, int] = GAE_SHAPE) -> Dict[str, float]:
    """The program's `ops/multistep` GAE (un-standardized) on a seeded batch
    with terminations and truncations, against the loop."""
    import jax

    rng = np.random.default_rng(seed)
    r_t = rng.normal(size=shape).astype(np.float32)
    done = rng.random(shape) < 0.05
    truncation_t = ((rng.random(shape) < 0.05) & ~done).astype(np.float32)
    discount_t = (0.99 * (1.0 - done)).astype(np.float32)
    v_tm1 = rng.normal(size=shape).astype(np.float32)
    v_t = rng.normal(size=shape).astype(np.float32)
    lambda_ = 0.95
    run = jax.jit(
        lambda r, d, a, b, tr: gae_fn(
            r, d, lambda_, v_tm1=a, v_t=b, truncation_t=tr,
            standardize_advantages=False, impl=impl,
        )
    )
    got_adv, got_tgt = run(r_t, discount_t, v_tm1, v_t, truncation_t)
    want_adv, want_tgt = gae_reference(r_t, discount_t, lambda_, v_tm1, v_t, truncation_t)
    return {
        "gae_advantages": compare.max_scaled_error(got_adv, want_adv),
        "gae_targets": compare.max_scaled_error(got_tgt, want_tgt),
    }


def stated_mismatches(
    config: Dict[str, Any], actor_vars: Any, critic_vars: Any,
    dtypes: Dict[str, List[str]], shapes: Dict[str, Any],
) -> List[str]:
    """What the run contradicts of what the configuration file states: the
    Dense kernels' shapes against `observation_dim`, `actor_hidden_sizes`,
    `critic_hidden_sizes` and `action_dim`; every parameter leaf's dtype
    against `parameter_dtype`; the dtypes the program's networks multiply in
    and return against `compute_dtype`; and `rollout_length`, `epochs`,
    `num_minibatches` against what the composed config resolved to."""
    import jax

    out: List[str] = []
    heads = {"tanh_normal": 2, "categorical": 1, "scalar": 1}
    for name, variables, hidden_key, head_key, width in (
        ("actor", actor_vars, "actor_hidden_sizes", "action_head", int(config["action_dim"])),
        ("critic", critic_vars, "critic_hidden_sizes", "critic_head", 1),
    ):
        sizes = [int(config["observation_dim"])] + [int(h) for h in config[hidden_key]]
        want = list(zip(sizes[:-1], sizes[1:]))
        want += [(sizes[-1], width)] * heads[config["reference"][head_key]]
        tree = variables["params"]
        got = [tuple(k.shape) for k, _ in dense_stack(tree["torso"]) + dense_stack(tree[head_key])]
        if got != want:
            out.append(f"{name} kernels {got} are not the stated {want}")
        leaf_dtypes = sorted({str(np.asarray(x).dtype) for x in jax.tree.leaves(variables)})
        if leaf_dtypes != [config["parameter_dtype"]]:
            out.append(f"{name} parameters are {leaf_dtypes}, stated {config['parameter_dtype']}")
    for what, seen in dtypes.items():
        if seen != [config["compute_dtype"]]:
            out.append(f"network {what} are {seen}, stated {config['compute_dtype']}")
    for key in ("rollout_length", "epochs", "num_minibatches"):
        if key in config and int(shapes.get(key, -1)) != int(config[key]):
            out.append(f"{key} resolved to {shapes.get(key)}, stated {config[key]}")
    return out


def check_before(ctx: Any) -> Dict[str, Tuple[float, float]]:
    """The `ops/multistep` function the learner uses, in the configuration's
    `multistep_impl`, against the loop. Part of set-up."""
    from stoix_tpu.ops import multistep

    config = ctx.cell.config
    errors = check_gae(
        multistep.truncated_generalized_advantage_estimation, ctx.seed,
        impl=str(config.get("multistep_impl", "scan")),
    )
    tol = float(config["reference"]["gae_tol"])
    return {name: (error, tol) for name, error in errors.items()}


def check_after(ctx: Any) -> Dict[str, Tuple[float, float]]:
    """The run's final actor and critic, applied by the program's own
    network objects to a seeded batch, against the plain MLP; and the run
    against what the configuration file states."""
    from stoix_tpu.envs.types import Observation

    config, nets = ctx.cell.config, ctx.networks
    if not nets or nets.get("actor_vars") is None:
        ctx.problems.append("the run's final parameters were not observed")
        return {}
    obs_dim, action_width = int(nets["obs_dim"]), int(nets["action_width"])

    def make_observation(obs: np.ndarray) -> Any:
        return Observation(
            agent_view=obs,
            action_mask=np.ones((obs.shape[0], action_width), np.float32),
            step_count=np.zeros((obs.shape[0],), np.int32),
        )

    errors, dtypes = check_networks(
        nets["actor_apply"], nets["critic_apply"], nets["actor_vars"], nets["critic_vars"],
        make_observation, obs_dim, config["reference"], ctx.seed,
    )
    ctx.problems.extend(
        stated_mismatches(config, nets["actor_vars"], nets["critic_vars"], dtypes, ctx.shapes)
    )
    tol = float(config["reference"]["mlp_tol"])
    return {name: (error, tol) for name, error in errors.items()}
