"""The plain reference of `ppo_olmoe_1layer_tokens` and what `correct` holds
such a run to. The forward and the loss below are the benchmark's own copy of
stoix_tpu/reference/olmoe.py (kept equal by tests/test_lm_ppo.py): the
published OLMoE forward in straightforward float32 `jax.numpy` at the highest
matmul precision — experts as a loop over dense SwiGLUs with a one-hot
combine, attention as an explicit [T, T] masked softmax, no cache, no sort,
no kernel — reading the weights out of the program's parameter tree by name
and sharing no code with the program. The departures from the HF forward are
listed in that file's header. Beside it, as plainly: the token task's
verifier, GAE as a float64 loop, global-norm clipping and Adam.

`check_after` runs on the chip, outside the timed window, and compares WHAT
WAS TIMED at the timed sizes. It copies the run's final learner state to the
host, calls the timed learner — the executable every window of the interval
ran — once more on it, and holds what that window produced to the reference
replayed on the state it started from:
  the rollout (the cached decode, every slot live): the stored log-probs
      and values of `reference.sample_sequences` of its sequences against the
      reference forward on the generated tokens; every sequence's return
      against the verifier; routed (token, slot) pairs a token = top-k;
  the update: the window's parameter change, leaf by leaf, against the
      reference's own — GAE and standardisation over the stored rollout, the
      shuffle the learner state's key gives (ops/minibatch's contract:
      minibatch m of an epoch is `permutation(shuffle_key, N)[m*B:(m+1)*B]`;
      ff_lm_ppo splits the state's key once a rollout step, then once an
      epoch), and for each of the minibatches in turn `jax.grad` of the
      reference loss, the clip and Adam; the window's logged loss parts and
      expert load against the reference's; Adam's count; pairs a token.
Beside these, on the same generated tokens and the same starting parameters,
the two entry points as programs of their own (they give what the learner
does not keep): teacher-forced logits, values and expert sets of the sampled
sequences, and the cached decode at EVERY slot of the rollout with the
sampled rows' logits, values and expert sets; (a)-(c) of ISSUE 25. Logits and
values are compared on the tokens whose expert sets agree, so that a near-tie
can neither hide a wrong layer nor excuse one. Logits are compared, never
sampled tokens. It also names in `ctx.problems` whatever the run contradicts
of what the configuration file states: widths (kernel shapes), parameter
dtypes, the dtype and precision of every `dot_general` and `ragged_dot` of
both entry points, and PPO's loop counts.

Tolerances are in the configuration file (`reference.*_tol`) with their
reasons; the readings they were set from are in PERF.md section 6 (PR 25).
Every run also makes the second reading the contract asks for — the same
reference with bfloat16 parameters and activations against itself in float32,
which has to come out as not correct — and prints it with the run
(`health.reference.lower_precision`); the update's second reading replays the
whole window again and is made only where `reference.lower_precision_update`
says so.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference as compare

_HIGHEST = "highest"


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Computed in float32 whatever `x` is, returned in x's dtype."""
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [B, H, T, head_dim], positions 0..T-1."""
    head_dim, length = x.shape[-1], x.shape[-2]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [T, head_dim]
    return (x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)).astype(x.dtype)


def attention(layer: Dict[str, Any], x: jax.Array, spec: Dict[str, Any]) -> jax.Array:
    batch, length, _ = x.shape
    heads = int(spec["num_attention_heads"])
    head_dim = int(spec.get("head_dim") or int(spec["hidden_size"]) // heads)
    eps = float(spec["rms_norm_eps"])
    q = rms_norm(x @ layer["wq"], layer["q_norm"], eps)
    k = rms_norm(x @ layer["wk"], layer["k_norm"], eps)
    v = x @ layer["wv"]
    split = lambda t: t.reshape(batch, length, heads, head_dim).transpose(0, 2, 1, 3)
    theta = float(spec["rope_theta"])
    q, k, v = _rope(split(q), theta), _rope(split(k), theta), split(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(jnp.float32(head_dim))
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)
    return out.transpose(0, 2, 1, 3).reshape(batch, length, heads * head_dim) @ layer["wo"]


def moe(
    layer: Dict[str, Any], x: jax.Array, spec: Dict[str, Any]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x [N, D] -> (y [N, D], {"probs" [N, E], "index" [N, k]})."""
    experts, top_k = int(spec["num_experts"]), int(spec["num_experts_per_tok"])
    probs = jax.nn.softmax((x @ layer["router"]).astype(jnp.float32), axis=-1)
    weights, index = jax.lax.top_k(probs, top_k)  # not renormalised
    chosen = jax.nn.one_hot(index, experts, dtype=jnp.float32)  # [N, k, E]
    combine = jnp.sum(chosen * weights[..., None], axis=1)
    combine = combine.astype(x.dtype)

    def expert(out: jax.Array, weights: Tuple[jax.Array, ...]) -> Tuple[jax.Array, None]:
        gate, up, down, share = weights
        hidden = jax.nn.silu(x @ gate) * (x @ up)
        return out + share[:, None] * (hidden @ down), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x), (layer["gate"], layer["up"], layer["down"], combine.T)
    )
    return out, {"probs": probs, "index": index}


def forward(
    actor_params: Dict[str, Any], critic_params: Dict[str, Any], tokens: jax.Array,
    spec: Dict[str, Any], dtype: Any = jnp.float32,
) -> Dict[str, jax.Array]:
    """tokens int [B, T] -> logits [B, T, V] (un-normalised), values [B, T],
    and per layer the router's probabilities [L, B*T, E] and chosen experts
    [L, B*T, k]. `dtype` is float32 for the reference; bfloat16 (parameters
    and activations, norms and softmaxes still computed in float32, as the HF
    model does in that dtype) is the lower-precision reading."""
    tree = jax.tree.map(lambda w: jnp.asarray(w, dtype), actor_params["params"])
    critic_params = jax.tree.map(lambda w: jnp.asarray(w, dtype), critic_params)
    eps = float(spec["rms_norm_eps"])
    with jax.default_matmul_precision(_HIGHEST):
        x = tree["embed"][tokens]
        batch, length, width = x.shape
        probs, index = [], []
        for i in range(int(spec["num_hidden_layers"])):
            layer = tree[f"layer_{i}"]
            x = x + attention(layer, rms_norm(x, layer["input_norm"], eps), spec)
            normed = rms_norm(x, layer["post_attn_norm"], eps)
            routed, router = moe(layer, normed.reshape(batch * length, width), spec)
            x = x + routed.reshape(batch, length, width)
            probs.append(router["probs"])
            index.append(router["index"])
        hidden = rms_norm(x, tree["final_norm"], eps)
        logits = hidden @ tree["lm_head"]
        head = critic_params["params"]
        values = (hidden @ head["kernel"])[..., 0] + head["bias"][0]
    return {
        "logits": logits.astype(jnp.float32), "values": values.astype(jnp.float32),
        "router_probs": jnp.stack(probs), "expert_index": jnp.stack(index),
    }


def loss_sums(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float], dtype: Any = jnp.float32,
) -> Dict[str, jax.Array]:
    """Sums over the tokens of `batch` (leaves [B, T]: tokens — the policy's
    inputs —, action, log_prob and value — the rollout's —, advantage, target)
    of what the loss is a mean of: the clipped surrogate, the entropy of the
    full categorical, the clipped value error; and over tokens and layers the
    router's probabilities [E] and the (token, slot) pairs routed to each
    expert [E]. Sums add over parts of a minibatch, so a minibatch too large
    to differentiate at once is differentiated a few sequences at a time."""
    out = forward(params[0], params[1], batch["tokens"], spec, dtype)
    log_probs = jax.nn.log_softmax(out["logits"], axis=-1)
    log_prob = jnp.take_along_axis(log_probs, batch["action"][..., None], axis=-1)[..., 0]
    ratio = jnp.exp(log_prob - batch["log_prob"])
    eps = hyper["clip_eps"]
    surrogate = jnp.minimum(
        ratio * batch["advantage"], jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * batch["advantage"]
    )
    clipped = batch["value"] + jnp.clip(out["values"] - batch["value"], -eps, eps)
    value_error = jnp.maximum(
        (out["values"] - batch["target"]) ** 2, (clipped - batch["target"]) ** 2
    )
    experts = int(spec["num_experts"])
    routed = jax.nn.one_hot(out["expert_index"].reshape(-1), experts, dtype=jnp.float32)
    return {
        "surrogate": jnp.sum(surrogate),
        "entropy": jnp.sum(-jnp.sum(jnp.exp(log_probs) * log_probs, axis=-1)),
        "value_error": jnp.sum(value_error),
        "router_prob": jnp.sum(out["router_probs"].reshape(-1, experts), axis=0),
        "routed": jnp.sum(routed, axis=0),
    }


def loss_of_sums(
    sums: Dict[str, jax.Array], tokens: int, spec: Dict[str, Any], hyper: Dict[str, float]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The PPO loss of `tokens` tokens from their sums: clip, value, entropy,
    and the HF load-balancing loss num_experts * sum_e (share of the (token,
    slot) pairs of all layers routed to e, summed over the slots) * (mean
    router probability of e)."""
    rows = int(spec["num_hidden_layers"]) * tokens
    actor_loss = -sums["surrogate"] / tokens
    entropy = sums["entropy"] / tokens
    value_loss = sums["value_error"] / tokens
    aux = int(spec["num_experts"]) * jnp.sum((sums["routed"] / rows) * (sums["router_prob"] / rows))
    total = (
        actor_loss - hyper["ent_coef"] * entropy + hyper["vf_coef"] * value_loss
        + hyper["aux_coef"] * aux
    )
    parts = {
        "total_loss": total, "actor_loss": actor_loss, "entropy": entropy,
        "value_loss": value_loss, "aux_loss": aux,
        "expert_load_max_over_mean": jnp.max(sums["routed"]) / jnp.mean(sums["routed"]),
        "routed_pairs_per_token": jnp.sum(sums["routed"]) / rows,
    }
    return total, parts


def verifier_returns(first: np.ndarray, actions: np.ndarray, modulus: int) -> np.ndarray:
    """The token task's return of each sequence, from the tokens alone:
    `first` [E] task tokens, `actions` [T, E]. The share of the actions whose
    residue equals that of the token before them."""
    before = np.concatenate([first[None], actions[:-1]], axis=0)
    return np.mean((actions % modulus) == (before % modulus), axis=0)


def gae(
    rewards: np.ndarray, values: np.ndarray, gamma: float, lam: float, standardize: bool
) -> Tuple[np.ndarray, np.ndarray]:
    """[T, E] of one whole episode a column (the last step terminates):
    advantages by a float64 loop, targets = values + advantages, advantages
    standardised over the whole batch afterwards."""
    advantages = np.zeros(rewards.shape, np.float64)
    carry = np.zeros(rewards.shape[1], np.float64)
    for t in reversed(range(rewards.shape[0])):
        last = t == rewards.shape[0] - 1
        next_value = 0.0 if last else values[t + 1].astype(np.float64)
        delta = rewards[t] + (0.0 if last else gamma) * next_value - values[t]
        carry = delta + (0.0 if last else gamma * lam) * carry
        advantages[t] = carry
    targets = values + advantages
    if standardize:
        advantages = (advantages - advantages.mean()) / (advantages.std() + 1e-8)
    return advantages.astype(np.float32), targets.astype(np.float32)


def clip_and_adam(
    params: Any, grads: Any, moments: Dict[str, Any], lr: float, max_norm: float,
    adam: Dict[str, float],
) -> Tuple[Any, Dict[str, Any]]:
    """One optimiser step as the configuration states it: gradients scaled
    to a global norm of at most `max_norm`, then Adam with bias correction."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = max_norm / jnp.maximum(norm, max_norm)
    b1, b2, eps = adam["b1"], adam["b2"], adam["eps"]
    count = moments["count"] + 1
    steps = count.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * (g * scale), moments["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * (g * scale) ** 2, moments["nu"], grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1.0 - b1**steps)) / (jnp.sqrt(v / (1.0 - b2**steps)) + eps),
        params, mu, nu,
    )
    return params, {"count": count, "mu": mu, "nu": nu}


# --------------------------------------------------------------------------- #
# What `correct` holds a run to
# --------------------------------------------------------------------------- #

_MATMULS = ("dot_general", "ragged_dot_general")


def _errors(got: jax.Array, want: jax.Array, rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Over the rows that `rows` selects: (max of |got - want| / max(1,
    |want|), root-mean-square of got - want over that of want). The maximum
    catches a fault in one place; the RMS, a mean over millions of entries
    with next to no spread from seed to seed, is what tells one precision
    from the next. inf where a value is not finite."""
    while rows.ndim < got.ndim:
        rows = rows[..., None]
    finite = jnp.all(jnp.isfinite(got) & jnp.isfinite(want))
    diff = jnp.where(rows, got - want, 0.0)
    worst = jnp.max(jnp.abs(diff) / jnp.maximum(1.0, jnp.abs(want)))
    rms = jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(jnp.where(rows, want * want, 0.0)))
    return jnp.where(finite, worst, jnp.inf), jnp.where(finite, rms, jnp.inf)


def _set_agreement(got_index: jax.Array, want_index: jax.Array, num_experts: int) -> Tuple[jax.Array, jax.Array]:
    """Chosen expert sets [L, N, k] on both sides -> (share of (token, slot)
    pairs that agree, [N] bool: the token's sets agree in every layer)."""
    member = lambda index: jnp.any(jax.nn.one_hot(index, num_experts, dtype=bool), axis=-2)
    both = member(got_index) & member(want_index)  # [L, N, E]
    pairs = jnp.sum(both, axis=-1)  # [L, N]
    return jnp.mean(pairs / got_index.shape[-1]), jnp.all(pairs == got_index.shape[-1], axis=0)


def program_outputs(
    nets: Dict[str, Any], params: Any, tokens: jax.Array, rows: np.ndarray
) -> Dict[str, Dict[str, jax.Array]]:
    """The program's two entry points as programs of their own, on `tokens`
    [E, T] (what a rollout generated): teacher-forced on the sequences `rows`,
    and T cached steps from an empty cache at EVERY slot, as the rollout runs
    them (teacher-forced INPUTS, so that both are compared with one reference
    forward), of which the sampled rows' outputs are kept."""
    rows = jnp.asarray(rows)

    def teacher_forced(params, tokens):
        logits, hidden, stats = nets["forward"](params.actor_params, tokens)
        return {
            "logits": logits, "values": nets["value"](params.critic_params, hidden),
            "expert_index": stats["expert_index"],
            "pairs_per_token": jnp.sum(stats["expert_count"]) / (stats["expert_count"].shape[0] * tokens.size),
        }

    def decoded(params, tokens):
        def one(cache, token):
            logits, hidden, cache, stats = nets["step"](params.actor_params, cache, token)
            out = {
                "logits": logits[rows], "values": nets["value"](params.critic_params, hidden)[rows],
                "expert_index": stats["expert_index"][:, rows],
                "pairs": jnp.sum(stats["expert_count"]),
            }
            return cache, out

        _, out = jax.lax.scan(one, nets["init_cache"](tokens.shape[0]), tokens.T)
        layers, top_k = out["expert_index"].shape[1], out["expert_index"].shape[-1]
        return {
            "logits": jnp.swapaxes(out["logits"], 0, 1),  # [rows, T, V]
            "values": jnp.swapaxes(out["values"], 0, 1),
            # [T, L, rows, k] -> [L, rows*T, k], the teacher-forced token order
            "expert_index": jnp.transpose(out["expert_index"], (1, 2, 0, 3)).reshape(layers, -1, top_k),
            "pairs_per_token": jnp.sum(out["pairs"]) / (layers * tokens.size),
        }

    return {
        "tf": jax.jit(teacher_forced)(params, tokens[rows]),
        "decode": jax.jit(decoded)(params, tokens),
    }


def compare_outputs(got: Dict[str, jax.Array], want: Dict[str, jax.Array], spec: Dict[str, Any]) -> Dict[str, float]:
    """Errors of one entry point against the reference forward."""
    batch, length = want["values"].shape
    agreement, agree = _set_agreement(got["expert_index"], want["expert_index"], int(spec["num_experts"]))
    agree = agree.reshape(batch, length)
    logits_max, logits_rms = _errors(got["logits"], want["logits"], agree)
    values_max, values_rms = _errors(got["values"], want["values"], agree)
    return {
        "logits_max": float(logits_max), "logits_rms": float(logits_rms),
        "values_max": float(values_max), "values_rms": float(values_rms),
        "expert_set_disagreement": float(1.0 - agreement),
        "dropped_pairs": abs(float(got["pairs_per_token"]) - int(spec["num_experts_per_tok"])),
    }


def _log_prob_of(logits: jax.Array, actions: jax.Array) -> jax.Array:
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(log_probs, actions[..., None], axis=-1)[..., 0]


# Two compilations of the step gave the same routing where their log-probs and
# values differ by no more than this (nats; values over max(1, |value|)). Read
# on the chip (PERF.md section 6, PR 25): half the sampled tokens are equal to
# the bit, 99.9% lie within 0.0039 nats and 0.0013 in value (the compilations
# round to bfloat16 at different points), and a token routed to another expert
# is 0.07 nats and 0.13-0.22 in value apart. What it lets through, added to the
# decode program's own largest error (0.024 nats, 0.029), stays under the
# extremes' limits (0.05, 0.1).
_SAME = 1e-2


def _compilation_gap(record: Dict[str, Any], decode: Dict[str, jax.Array], actions: jax.Array) -> Dict[str, jax.Array]:
    """|stored - decoded| a token: log-prob in nats, value over max(1, |value|)."""
    return {
        "log_prob": jnp.abs(jnp.asarray(record["log_prob"]) - _log_prob_of(decode["logits"], actions)),
        "value": jnp.abs(jnp.asarray(record["value"]) - decode["values"])
        / jnp.maximum(1.0, jnp.abs(decode["values"])),
    }


def compare_record(
    record: Dict[str, Any], decode: Dict[str, jax.Array], want: Dict[str, jax.Array],
    actions: jax.Array, agree: jax.Array,
) -> Dict[str, float]:
    """What the timed rollout stored of the sampled sequences ([rows, T]:
    log_prob of the token it sampled, value) against the reference forward.
    The rollout keeps no expert sets, so its tokens count where `agree` (the
    sets of the same decode as a program of its own agree with the
    reference's) AND the stored numbers are that program's own to `_SAME`:
    there the two compilations demonstrably routed alike. Two compilations of
    one float32 program part at a near-tie now and then (a token or two of
    4,096 on the chip, one run in three, 0.07 nats and more apart; where they
    route alike, half the tokens are equal to the bit and 99.9% within 0.004
    nats); the share of tokens where they part is an
    error of its own (`differs_from_decode`), small next to the share a
    change of precision flips, and most tokens once the rollout's cache,
    positions or grouping are wrong. Log-probs in nats (root-mean-square and
    largest difference), values as everywhere."""
    stored_log_prob, stored_value = jnp.asarray(record["log_prob"]), jnp.asarray(record["value"])
    gap = _compilation_gap(record, decode, actions)
    same = (gap["log_prob"] <= _SAME) & (gap["value"] <= _SAME)
    rows = agree & same
    diff = jnp.where(rows, stored_log_prob - _log_prob_of(want["logits"], actions), 0.0)
    values_max, values_rms = _errors(stored_value, want["values"], rows)
    finite = bool(jnp.all(jnp.isfinite(stored_log_prob)))
    return {
        "log_prob_rms": float(jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(rows))) if finite else float("inf"),
        "log_prob_max": float(jnp.max(jnp.abs(diff))) if finite else float("inf"),
        "values_max": float(values_max), "values_rms": float(values_rms),
        "differs_from_decode": float(1.0 - jnp.mean(same)),
    }


def matmuls_of(fn: Any, *args: Any) -> List[Dict[str, Any]]:
    """Every `dot_general` and `ragged_dot_general` of `fn(*args)` (nested
    calls, scans and kernels included): operand dtypes, the precision it was
    asked for, and the right operand's shape."""
    found: List[Dict[str, Any]] = []

    def walk(jaxpr: Any) -> None:
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _MATMULS:
                precision = eqn.params.get("precision")
                found.append({
                    "dtypes": sorted({str(v.aval.dtype) for v in eqn.invars[:2]}),
                    "precision": "DEFAULT" if precision is None else
                    "/".join(sorted({str(getattr(p, "name", p)) for p in np.ravel(precision)})),
                    "rhs": tuple(eqn.invars[1].aval.shape),
                })
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else (value,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def stated_mismatches(config: Dict[str, Any], nets: Dict[str, Any], params: Any, shapes: Dict[str, Any], tokens: jax.Array) -> List[str]:
    """What the run contradicts of what the configuration file states."""
    out: List[str] = []
    tree = params.actor_params["params"]
    d, heads, head_dim = int(config["hidden_size"]), int(config["num_attention_heads"]), int(config["head_dim"])
    e, f, v = int(config["num_experts"]), int(config["intermediate_size"]), int(config["vocab_size"])
    layer_names = sorted(k for k in tree if k.startswith("layer_"))
    if len(layer_names) != int(config["num_hidden_layers"]):
        out.append(f"{len(layer_names)} layers, stated {config['num_hidden_layers']}")
    want = {
        "embed": (v, d), "lm_head": (d, v), "final_norm": (d,),
        **{
            f"{layer}/{name}": shape
            for layer in layer_names
            for name, shape in {
                "wq": (d, heads * head_dim), "wk": (d, heads * head_dim), "wv": (d, heads * head_dim),
                "wo": (heads * head_dim, d), "q_norm": (heads * head_dim,), "k_norm": (heads * head_dim,),
                "input_norm": (d,), "post_attn_norm": (d,), "router": (d, e),
                "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d),
            }.items()
        },
    }
    got = {
        "/".join(str(k.key) for k in path): tuple(leaf.shape)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }
    if got != want:
        wrong = sorted(k for k in set(got) | set(want) if got.get(k) != want.get(k))
        out.append(f"parameter shapes differ from the stated widths at {wrong[:6]}: "
                   f"{[got.get(k) for k in wrong[:6]]} vs {[want.get(k) for k in wrong[:6]]}")
    leaf_dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    if leaf_dtypes != [config["parameter_dtype"]]:
        out.append(f"parameters are {leaf_dtypes}, stated {config['parameter_dtype']}")

    cache = jax.eval_shape(lambda: nets["init_cache"](tokens.shape[0]))
    programs = {
        "forward": matmuls_of(nets["forward"], params.actor_params, tokens),
        "step": matmuls_of(nets["step"], params.actor_params, cache, tokens[:, 0]),
    }
    for name, matmuls in programs.items():
        routers = [m for m in matmuls if m["rhs"] == (d, e)]
        if not routers:
            out.append(f"{name}: no router matmul [{d}, {e}] found")
        for matmul in matmuls:
            stated = config["router_precision"] if matmul["rhs"] == (d, e) else config["matmul_precision"]
            if matmul["dtypes"] != [config["compute_dtype"]] or matmul["precision"] != stated:
                out.append(
                    f"{name}: a matmul with right operand {matmul['rhs']} multiplies {matmul['dtypes']} at "
                    f"{matmul['precision']}, stated {config['compute_dtype']} at {stated}"
                )
        if not any(len(m["rhs"]) == 3 and m["rhs"][0] == e for m in matmuls):
            out.append(f"{name}: no grouped matmul over {e} experts found")
    for key in ("rollout_length", "epochs", "num_minibatches"):
        if int(shapes.get(key, -1)) != int(config[key]):
            out.append(f"{key} resolved to {shapes.get(key)}, stated {config[key]}")
    return out


def check_before(ctx: Any) -> Dict[str, Tuple[float, float]]:
    """The learner's GAE (`ops/multistep`, the configuration's
    `multistep_impl`) against a float64 loop: part of set-up."""
    from stoix_tpu.ops import multistep

    length = int(ctx.cell.config["rollout_length"])
    rng = np.random.default_rng(ctx.seed)
    shape = (length, 64)
    r_t = rng.normal(size=shape).astype(np.float32)
    done = np.zeros(shape, bool)
    done[-1] = True
    discount_t = (1.0 - done).astype(np.float32)
    v_tm1 = rng.normal(size=shape).astype(np.float32)
    v_t = np.concatenate([v_tm1[1:], np.zeros_like(v_tm1[:1])])
    got_adv, got_tgt = jax.jit(
        lambda r, d, a, b: multistep.truncated_generalized_advantage_estimation(
            r, d, 0.95, v_tm1=a, v_t=b, truncation_t=jnp.zeros_like(r),
            standardize_advantages=False, impl=str(ctx.cell.config.get("multistep_impl", "scan")),
        )
    )(r_t, discount_t, v_tm1, v_t)
    want, _ = gae(r_t, v_tm1, 1.0, 0.95, standardize=False)
    tol = float(ctx.cell.config["reference"]["gae_tol"])
    return {
        "gae_advantages": (compare.max_scaled_error(got_adv, want), tol),
        "gae_targets": (compare.max_scaled_error(got_tgt, v_tm1 + want), tol),
    }


# --------------------------------------------------------------------------- #
# One more window of the timed learner, and the reference's replay of it
# --------------------------------------------------------------------------- #


def _adam_moments(opt_state: Any) -> Dict[str, Any]:
    """count, mu, nu of the one Adam state in an optimiser's state tree."""
    is_adam = lambda node: hasattr(node, "mu") and hasattr(node, "nu")
    (adam,) = [n for n in jax.tree.leaves(opt_state, is_leaf=is_adam) if is_adam(n)]
    return {"count": adam.count, "mu": adam.mu, "nu": adam.nu}


def timed_window(nets: Dict[str, Any]) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Copies the run's final learner state to the host, calls the timed
    learner once more on it (which donates it) and copies what that window
    produced; then frees the device. -> (before, after), numpy leaves."""
    state = nets["state"]
    before = jax.device_get({
        "params": (state.params.actor_params, state.params.critic_params),
        "moments": (
            _adam_moments(state.opt_states.actor_opt_state),
            _adam_moments(state.opt_states.critic_opt_state),
        ),
        "key": state.key,
        "first_token": state.timestep.observation.agent_view[..., 0],
    })
    output = nets["learn"](state)
    new = output.learner_state
    after = jax.device_get({
        "params": (new.params.actor_params, new.params.critic_params),
        "count": (
            _adam_moments(new.opt_states.actor_opt_state)["count"],
            _adam_moments(new.opt_states.critic_opt_state)["count"],
        ),
        "episode": dict(output.episode_metrics),
        "train": dict(output.train_metrics),
    })
    for leaf in jax.tree.leaves((new.params, new.opt_states)):
        leaf.delete()
    return before, after


def shuffle_keys(key: jax.Array, rollout_length: int, epochs: int) -> List[jax.Array]:
    """The shuffle key of each epoch of the update that follows a rollout
    from `key`: ff_lm_ppo splits its key once a rollout step and once an
    epoch, keeping the first half."""
    for _ in range(rollout_length):
        key = jax.random.split(key)[0]
    keys = []
    for _ in range(epochs):
        key, shuffle_key = jax.random.split(key)
        keys.append(shuffle_key)
    return keys


def make_replay(spec: Dict[str, Any], hyper: Dict[str, Any], adam: Dict[str, float], dtype: Any) -> Tuple[Callable, Callable, Callable]:
    """(sums, add_gradient, step) of the reference's update, each one
    program. `sums(params, parts)`: the loss's sums of every part of a
    minibatch (`parts` leaves [parts, sequences, T]). The loss is a function
    of sums over tokens, so its gradient is the sum over parts of the parts'
    sums weighted by the loss's derivative in them:
    `add_gradient(total, params, part, weight)` adds one part's. `step`: the
    clip and Adam, actor and critic each their own."""
    part_sums = lambda params, part: loss_sums(params, part, spec, hyper, dtype)

    @jax.jit
    def sums(params, parts):
        return jax.lax.map(lambda part: part_sums(params, part), parts)

    @functools.partial(jax.jit, donate_argnums=0)
    def add_gradient(total, params, part, weight):
        weighted = lambda p: sum(jnp.vdot(weight[k], v) for k, v in part_sums(p, part).items())
        return jax.tree.map(jnp.add, total, jax.grad(weighted)(params))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, moments, grads):
        stepped = [
            clip_and_adam(params[i], grads[i], moments[i], hyper[lr], hyper["max_grad_norm"], adam)
            for i, lr in enumerate(("actor_lr", "critic_lr"))
        ]
        return tuple(p for p, _ in stepped), tuple(m for _, m in stepped)

    return sums, add_gradient, step


def replay_update(
    before: Dict[str, Any], rollout: Dict[str, np.ndarray], spec: Dict[str, Any],
    hyper: Dict[str, Any], ref: Dict[str, Any], shards: int, dtype: Any = jnp.float32,
) -> Tuple[Any, Dict[str, float]]:
    """The reference's own update from the state the timed window started
    from and the rollout it stored (`rollout` leaves [T, E]: tokens, action,
    log_prob, value, reward) -> (parameters afterwards on the host, the loss
    parts as the learner logs them: means over shards and minibatches).
    Shard s holds the sequences [s*E/S, (s+1)*E/S), standardises its own
    advantages, shuffles with its own key; gradients are means over shards."""
    length, envs = rollout["action"].shape
    per_shard = envs // shards
    minibatches, epochs = int(hyper["num_minibatches"]), int(hyper["epochs"])
    size = per_shard // minibatches  # sequences of one shard in a minibatch
    part = min(int(ref["replay_part_sequences"]), size)
    while size % part:
        part -= 1
    data: List[Dict[str, np.ndarray]] = []
    for s in range(shards):
        cols = slice(s * per_shard, (s + 1) * per_shard)
        advantage, target = gae(
            rollout["reward"][:, cols], rollout["value"][:, cols], hyper["gamma"],
            hyper["gae_lambda"], hyper["standardize_advantages"],
        )
        data.append({
            "tokens": rollout["tokens"][:, cols].T, "action": rollout["action"][:, cols].T,
            "log_prob": rollout["log_prob"][:, cols].T, "value": rollout["value"][:, cols].T,
            "advantage": advantage.T, "target": target.T,
        })
    keys = [shuffle_keys(jnp.asarray(before["key"][s]), length, epochs) for s in range(shards)]

    sums, add_gradient, step = make_replay(spec, hyper, ref["adam"], dtype)
    params = jax.device_put(before["params"])
    moments = jax.device_put(before["moments"])
    tokens = size * length
    logged: List[Dict[str, float]] = []
    for epoch in range(epochs):
        orders = [np.asarray(jax.random.permutation(keys[s][epoch], per_shard)) for s in range(shards)]
        for m in range(minibatches):
            picked = [orders[s][m * size:(m + 1) * size] for s in range(shards)]
            # [shards * parts a shard, sequences a part, T]
            parts = {
                name: jnp.asarray(np.concatenate([
                    data[s][name][picked[s]].reshape(size // part, part, length) for s in range(shards)
                ]))
                for name in data[0]
            }
            part_sums = sums(params, parts)
            of_shard = lambda s: jax.tree.map(
                lambda x: jnp.sum(x[s * (size // part):(s + 1) * (size // part)], axis=0), part_sums
            )
            loss = lambda shard_sums: loss_of_sums(shard_sums, tokens, spec, hyper)
            shard_sums = [of_shard(s) for s in range(shards)]
            logged.append({
                k: float(np.mean([float(loss(x)[1][k]) for x in shard_sums]))
                for k in loss(shard_sums[0])[1]
            })
            # d(mean over shards of the loss) / d(each part's sums)
            weights = [jax.grad(lambda x: loss(x)[0] / shards)(x) for x in shard_sums]
            grads = jax.tree.map(jnp.zeros_like, params)
            for index in range(shards * (size // part)):
                one = jax.tree.map(lambda x: x[index], parts)
                grads = add_gradient(grads, params, one, weights[index // (size // part)])
            params, moments = step(params, moments, grads)
    after = jax.device_get(params)
    for leaf in jax.tree.leaves((params, moments)):
        leaf.delete()
    return after, {k: float(np.mean([rec[k] for rec in logged])) for k in logged[0]}


def update_errors(before: Any, got: Any, want: Any) -> Tuple[Dict[str, float], Dict[str, List[float]]]:
    """A window's parameter change, program against reference, leaf by leaf:
    |change_got - change_want| over |change_want| (Euclidean norms) ->
    ({worst leaf, all leaves together}, {leaf: [|change_want|, error]})."""
    named = lambda tree: {
        ("actor/" if i == 0 else "critic/") + "/".join(str(k.key) for k in path if str(k.key) != "params"): leaf
        for i, side in enumerate(tree)
        for path, leaf in jax.tree_util.tree_leaves_with_path(side)
    }
    before, got, want = named(before), named(got), named(want)
    leaves: Dict[str, List[float]] = {}
    square_diff = square_want = 0.0
    for name in before:
        change_got = got[name].astype(np.float64) - before[name]
        change_want = want[name].astype(np.float64) - before[name]
        diff, norm = float(np.sum((change_got - change_want) ** 2)), float(np.sum(change_want**2))
        square_diff, square_want = square_diff + diff, square_want + norm
        finite = np.isfinite(diff) and np.isfinite(norm) and norm > 0.0
        leaves[name] = [float(np.sqrt(norm)), float(np.sqrt(diff / norm)) if finite else float("inf")]
    return {
        "worst_leaf": max(error for _, error in leaves.values()),
        "all_leaves": float(np.sqrt(square_diff / square_want)) if square_want > 0.0 else float("inf"),
    }, leaves


def stored_rollout(before: Dict[str, Any], after: Dict[str, Any], modulus: int) -> Dict[str, np.ndarray]:
    """The window's rollout as the learner handed it out, [T, E] a leaf: the
    tokens the policy was given (the task token, then its own actions), what
    it stored of them, and the reward the verifier gives (terminal)."""
    episode = {k: np.asarray(v)[0] for k, v in after["episode"].items()}
    actions = episode["rollout_action"]
    first = np.asarray(before["first_token"]).reshape(actions.shape[1])
    reward = np.zeros(actions.shape, np.float32)
    reward[-1] = verifier_returns(first, actions, modulus)
    return {
        "tokens": np.concatenate([first[None], actions[:-1]], axis=0), "action": actions,
        "log_prob": episode["rollout_log_prob"], "value": episode["rollout_value"],
        "reward": reward, "logged_return": episode["episode_return"][-1],
    }


def sampled_errors(
    ctx: Any, before: Dict[str, Any], rollout: Dict[str, np.ndarray], rows: np.ndarray
) -> Tuple[Dict[str, Tuple[float, float]], Dict[str, float], Dict[str, float]]:
    """On the sequences `rows` of the window's rollout and the parameters it
    started from: both entry points as programs, and what the timed rollout
    stored, against the reference forward -> (errors with their tolerances,
    the bfloat16 reference's errors: the second reading, quantiles of what the
    two compilations of the decode differ by)."""
    config, nets = ctx.cell.config, ctx.networks
    ref, top_k = config["reference"], int(config["num_experts_per_tok"])
    tokens = jnp.asarray(rollout["tokens"].T)  # [E, T]
    params = jax.device_put(type(nets["state"].params)(*before["params"]))
    ctx.problems.extend(stated_mismatches(config, nets, params, ctx.shapes, tokens[rows]))
    outputs = program_outputs(nets, params, tokens, rows)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    reference_params = jax.device_put(before["params"])
    run = lambda dtype: jax.jit(functools.partial(forward, spec=config, dtype=dtype))(
        *reference_params, tokens[rows]
    )
    want, low = run(jnp.float32), run(jnp.bfloat16)
    for leaf in jax.tree.leaves(reference_params):
        leaf.delete()
    tolerances = {
        "logits_max": float(ref["max_tol"]), "values_max": float(ref["max_tol"]),
        "logits_rms": float(ref["logits_rms_tol"]), "values_rms": float(ref["values_rms_tol"]),
        "expert_set_disagreement": float(ref["expert_set_tol"]),
        "dropped_pairs": float(ref["dropped_tol"]),
        "log_prob_rms": float(ref["log_prob_rms_tol"]), "log_prob_max": float(ref["log_prob_max_tol"]),
        "differs_from_decode": float(ref["rollout_decode_tol"]),
    }
    errors = {
        f"{entry}_{name}": (error, tolerances[name])
        for entry, got in outputs.items()
        for name, error in compare_outputs(got, want, config).items()
    }
    agreeing = lambda got: _set_agreement(
        got["expert_index"], want["expert_index"], int(config["num_experts"])
    )[1].reshape(want["values"].shape)
    actions = jnp.asarray(rollout["action"].T[rows])
    record = {"log_prob": rollout["log_prob"].T[rows], "value": rollout["value"].T[rows]}
    stored = compare_record(record, outputs["decode"], want, actions, agreeing(outputs["decode"]))
    errors.update({f"rollout_{name}": (error, tolerances[name]) for name, error in stored.items()})
    # The second reading of the record: the bfloat16 reference's own.
    low_record = {"log_prob": _log_prob_of(low["logits"], actions), "value": low["values"]}
    second = {
        **compare_outputs({**low, "pairs_per_token": top_k}, want, config),
        **{
            f"record_{k}": v
            for k, v in compare_record(low_record, low, want, actions, agreeing(low)).items()
        },
    }
    gap = {
        f"{name}_{label}": float(jnp.quantile(x, q))
        for name, x in _compilation_gap(record, outputs["decode"], actions).items()
        for label, q in (("p50", 0.5), ("p99", 0.99), ("p999", 0.999), ("max", 1.0))
    }
    return errors, second, gap


_LOSS_PARTS = ("total_loss", "actor_loss", "value_loss", "entropy", "aux_loss")


def check_after(ctx: Any) -> Dict[str, Tuple[float, float]]:
    config, nets = ctx.cell.config, ctx.networks
    if not nets or nets.get("state") is None or nets.get("learn") is None:
        ctx.problems.append("the run's timed learner and final state were not observed")
        return {}
    ref, hyper, shards = config["reference"], nets["hyper"], int(nets["shards"])
    if int(ctx.shapes.get("updates_per_tick", 1)) != 1 or hyper["decay_learning_rates"]:
        ctx.problems.append("the reference replays one update a window at a constant learning rate")
        return {}
    before, after = timed_window(nets)
    rollout = stored_rollout(before, after, int(hyper["env_modulus"]))
    train = {k: float(np.mean(v)) for k, v in after["train"].items()}
    top_k, steps = int(config["num_experts_per_tok"]), int(hyper["epochs"]) * int(hyper["num_minibatches"])
    errors: Dict[str, Tuple[float, float]] = {
        "rollout_returns": (
            compare.max_scaled_error(rollout["logged_return"], rollout["reward"][-1]),
            float(ref["returns_tol"]),
        ),
        "rollout_dropped_pairs": (
            abs(train["rollout_routed_pairs_per_token"] - top_k), float(ref["dropped_tol"])
        ),
        "update_dropped_pairs": (abs(train["routed_pairs_per_token"] - top_k), float(ref["dropped_tol"])),
        "update_adam_steps": (
            float(max(
                abs(int(got) - int(m["count"]) - steps)
                for got, m in zip(after["count"], before["moments"])
            )), 0.0,
        ),
    }
    envs = rollout["action"].shape[1]
    rows = np.sort(np.random.default_rng(ctx.seed).choice(envs, int(ref["sample_sequences"]), replace=False))
    sampled, second, gap = sampled_errors(ctx, before, rollout, rows)
    errors.update(sampled)

    # The update: the window's parameter change and logged loss parts
    # against the reference's replay.
    replayed, logged = replay_update(before, rollout, config, hyper, ref, shards)
    update, leaves = update_errors(before["params"], after["params"], replayed)
    errors["update_params_worst_leaf"] = (update["worst_leaf"], float(ref["update_worst_leaf_tol"]))
    errors["update_params_all_leaves"] = (update["all_leaves"], float(ref["update_all_leaves_tol"]))
    for name in _LOSS_PARTS:
        errors[f"update_{name}"] = (compare.max_scaled_error(train[name], logged[name]), float(ref["loss_tol"]))
    errors["update_expert_load"] = (
        compare.max_scaled_error(train["expert_load_max_over_mean"], logged["expert_load_max_over_mean"]),
        float(ref["expert_load_tol"]),
    )
    if ref.get("lower_precision_update"):
        low_replayed, low_logged = replay_update(before, rollout, config, hyper, ref, shards, jnp.bfloat16)
        low_update, low_leaves = update_errors(before["params"], low_replayed, replayed)
        second.update({f"update_params_{k}": v for k, v in low_update.items()})
        second.update({f"update_{k}": compare.max_scaled_error(low_logged[k], logged[k]) for k in _LOSS_PARTS})
        second["update_leaves"] = low_leaves
    ctx.health["reference"] = {
        "update_leaves": leaves, "lower_precision": second, "stored_minus_decoded": gap,
        "rows": rows.tolist(),
    }
    return errors
