"""The plain reference of `ppo_sdar_moe_ep8_share` and what `correct` holds
such a run to. The layer, the forward under an explicit mask matrix, the
denoise pass as a full forward over the whole prefix and the loss below are
the benchmark's own copy of stoix_tpu/reference/sdar.py (kept equal, function
by function, by tests/test_sdar_ppo.py): the published SDAR mixture-of-experts
layer for one expert-parallel rank's share, in straightforward `jax.numpy` at
the highest matmul precision — a loop over the held experts on all tokens with
a weight mask, key/value heads repeated for their query heads, no cache, no
sort, no grouped matmul, no kernel — reading the weights out of the program's
parameter tree by name and sharing no code with the program. The departures
from the published forward are listed in that file's header. Beside it, as
plainly: the block token task's verifier, GAE as a float64 loop over denoise
steps, global-norm clipping and Adam.

`check_after` runs on the chip, outside the timed window, and compares WHAT
WAS TIMED at the timed sizes, as references/ppo_olmoe.py does. It copies the
run's final learner state to the host, calls the timed learner — the
executable every window of the interval ran — once more on it, and holds
what that window produced to the reference replayed from the same state:
  the rollout (block steps through the cache): of `reference.sample_sequences`
      of its sequences, every denoise step's stored log-prob and value
      against the reference's forward over `[clean ; noisy copies]` — each
      pass as a full forward over its whole prefix — and each stored commit
      set against the top-k of the reference's confidences at the stored
      tokens (`commit_flips`, a share with its own limit); every sequence's
      logged return against the verifier; no mask id among the tokens; routed
      pairs a token = top-k over all experts; model passes a token;
  the teacher-forced entry point as a program of its own on the same
      sequences: logits and values at every noisy position and the expert
      sets at every position, on the positions whose sets agree;
  the update: the window's parameter change, leaf by leaf, against the
      reference's own — GAE and standardisation over the stored rollout, the
      shuffle the learner state's key gives (ops/minibatch's contract;
      ff_sdar_ppo splits the key once a denoise pass, then once an epoch), and
      for each minibatch in turn `jax.grad` of the reference loss a sequence
      at a time, the clip and Adam; the logged loss parts, the held experts'
      load and `held_pairs_per_token` against the reference's; Adam's count.
It also names in `ctx.problems` whatever the run contradicts of what the
configuration file states: widths (kernel shapes, the held experts and the
router's width among them), parameter dtypes, the dtype and precision of
every `dot_general` and `ragged_dot` of the teacher-forced pass, the denoise
pass and the commit pass, and the loop counts (steps, passes, minibatches).

Tolerances are in the configuration file (`reference.*_tol`) with their
reasons and the readings they were set from. Every run also makes the second
reading — the same reference with bfloat16 parameters and activations against
itself in float32, which has to come out as not correct — and prints it
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


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [N, H, T, head_dim] at `positions` [T]."""
    head_dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [T, head_dim]
    return (x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)).astype(x.dtype)


def layout(num_blocks: int, block_length: int, copies: int) -> Dict[str, np.ndarray]:
    """`[clean ; noisy 1 .. noisy S]` of a sequence of `num_blocks` blocks
    (the first is the prompt): each element's position, block and copy. The
    clean copy holds every block, a noisy copy the response blocks."""
    clean = np.arange(num_blocks * block_length)
    response = clean[block_length:]
    position = np.concatenate([clean] + [response] * copies)
    noisy = [np.full_like(response, c + 1) for c in range(copies)]
    copy = np.concatenate([np.zeros_like(clean)] + noisy)
    return {"position": position, "block": position // block_length, "copy": copy}


def block_mask(block: np.ndarray, copy: np.ndarray) -> np.ndarray:
    """allowed[q, k]: (copy_k = 0 and block_k < block_q) or (copy_k = copy_q
    and block_k = block_q)."""
    earlier_clean = (copy[None, :] == 0) & (block[None, :] < block[:, None])
    own = (copy[None, :] == copy[:, None]) & (block[None, :] == block[:, None])
    return earlier_clean | own


def attention(
    layer: Dict[str, Any], x: jax.Array, positions: jax.Array, allowed: jax.Array,
    spec: Dict[str, Any],
) -> jax.Array:
    batch, length, _ = x.shape
    heads, kv_heads = int(spec["num_attention_heads"]), int(spec["num_key_value_heads"])  # noqa: STX006 — spec holds Python numbers, none traced
    head_dim, eps = int(spec["head_dim"]), float(spec["rms_norm_eps"])  # noqa: STX006 — spec holds Python numbers, none traced
    theta = float(spec["rope_theta"])  # noqa: STX006 — spec holds Python numbers, none traced
    split = lambda t, n: t.reshape(batch, length, n, head_dim).transpose(0, 2, 1, 3)
    q = _rope(rms_norm(split(x @ layer["wq"], heads), layer["q_norm"], eps), positions, theta)
    k = _rope(rms_norm(split(x @ layer["wk"], kv_heads), layer["k_norm"], eps), positions, theta)
    v = split(x @ layer["wv"], kv_heads)
    # query head h reads key/value head h // (heads / kv_heads)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(head_dim))
    scores = jnp.where(allowed, scores, jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)
    return out.transpose(0, 2, 1, 3).reshape(batch, length, heads * head_dim) @ layer["wo"]


def moe(
    layer: Dict[str, Any], x: jax.Array, spec: Dict[str, Any]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x [M, D] -> (the held experts' part of the result [M, D], {"probs" [M,
    E], "index" [M, k]} over all E experts of the router)."""
    top_k, held = int(spec["num_experts_per_tok"]), int(spec["num_experts"])  # noqa: STX006 — spec holds Python numbers, none traced
    offset = int(spec.get("expert_offset", 0))
    probs = jax.nn.softmax((x @ layer["router"]).astype(jnp.float32), axis=-1)
    weights, index = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)  # norm_topk_prob
    mine = offset + jnp.arange(held)
    # [M, held]: the weight of each held expert for each token, 0 where not chosen
    combine = jnp.sum(
        jnp.where(index[..., None] == mine, weights[..., None], 0.0), axis=1
    ).astype(x.dtype)

    def expert(out: jax.Array, weights: Tuple[jax.Array, ...]) -> Tuple[jax.Array, None]:
        gate, up, down, share = weights
        hidden = jax.nn.silu(x @ gate) * (x @ up)
        return out + share[:, None] * (hidden @ down), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x), (layer["gate"], layer["up"], layer["down"], combine.T)
    )
    return out, {"probs": probs, "index": index}


def layer_forward(
    layer: Dict[str, Any], x: jax.Array, positions: jax.Array, allowed: jax.Array,
    spec: Dict[str, Any],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decoder layer: x [N, T, D] -> (y [N, T, D], the router's outputs)."""
    eps = float(spec["rms_norm_eps"])  # noqa: STX006 — spec holds Python numbers, none traced
    batch, length, width = x.shape
    x = x + attention(layer, rms_norm(x, layer["input_norm"], eps), positions, allowed, spec)
    normed = rms_norm(x, layer["post_attn_norm"], eps)
    routed, router = moe(layer, normed.reshape(batch * length, width), spec)
    return x + routed.reshape(batch, length, width), router


def forward(
    actor_params: Dict[str, Any], critic_params: Dict[str, Any], tokens: jax.Array,
    positions: Any, allowed: Any, spec: Dict[str, Any], dtype: Any = jnp.float32,
) -> Dict[str, jax.Array]:
    """tokens int [N, T] at `positions` [T] under `allowed` [T, T] -> logits
    [N, T, V] (un-normalised, the mask id's still finite), values [N, T] (the
    value head a position), and per layer the router's probabilities [L, N*T,
    E] and chosen experts [L, N*T, k]."""
    tree = jax.tree.map(lambda w: jnp.asarray(w, dtype), actor_params["params"])
    head = jax.tree.map(lambda w: jnp.asarray(w, dtype), critic_params["params"])
    positions, allowed = jnp.asarray(positions), jnp.asarray(allowed)
    with jax.default_matmul_precision(_HIGHEST):
        x = tree["embed"][tokens]
        probs, index = [], []
        # (rematerialised in a backward pass, so that one sequence's gradient
        # fits beside the optimiser's state on the chip: the same arithmetic)
        one_layer = jax.checkpoint(
            lambda layer, x: layer_forward(layer, x, positions, allowed, spec)
        )
        for i in range(int(spec["num_hidden_layers"])):
            x, router = one_layer(tree[f"layer_{i}"], x)
            probs.append(router["probs"])
            index.append(router["index"])
        hidden = rms_norm(x, tree["final_norm"], float(spec["rms_norm_eps"]))
        logits = hidden @ tree["lm_head"]
        values = (hidden @ head["kernel"])[..., 0] + head["bias"][0]
    return {
        "logits": logits.astype(jnp.float32), "values": values.astype(jnp.float32),
        "router_probs": jnp.stack(probs), "expert_index": jnp.stack(index),
    }


def policy_log_probs(logits: jax.Array, spec: Dict[str, Any]) -> jax.Array:
    """log p over the slice with the mask id excluded (its log p is -inf)."""
    ids = jnp.arange(logits.shape[-1])
    masked = jnp.where(ids == int(spec["mask_token_id"]), -jnp.inf, logits)
    return jax.nn.log_softmax(masked, axis=-1)


def denoise_pass(
    actor_params: Dict[str, Any], critic_params: Dict[str, Any], prefix: jax.Array,
    block: jax.Array,
    spec: Dict[str, Any], dtype: Any = jnp.float32,
) -> Dict[str, jax.Array]:
    """One denoise pass WITHOUT a cache: a full forward over the committed
    prefix [N, P] (prompt block and finished blocks) and the block as it
    stands [N, B] -> log p at the block's positions [N, B, V], the step's
    value [N] (mean over the block), the block's chosen experts [L, N*B, k]."""
    size = int(spec["block_length"])
    tokens = jnp.concatenate([prefix, block], axis=1)
    length = tokens.shape[1]
    blocks = np.arange(length) // size
    out = forward(
        actor_params, critic_params, tokens, np.arange(length),
        block_mask(blocks, np.zeros_like(blocks)), spec, dtype,
    )
    layers, top_k = out["expert_index"].shape[0], out["expert_index"].shape[-1]
    index = out["expert_index"].reshape(layers, tokens.shape[0], length, top_k)[:, :, -size:]
    return {
        "log_probs": policy_log_probs(out["logits"][:, -size:], spec),
        "value": jnp.mean(out["values"][:, -size:], axis=-1),
        "expert_index": index.reshape(layers, -1, top_k),
    }


def commit_set(confidence: jax.Array, masked: jax.Array, count: int) -> jax.Array:
    """[.., B] bool: the `count` still-masked positions of largest
    confidence, ties to the lower position (a stable descending sort)."""
    order = jnp.argsort(-jnp.where(masked, confidence, -1.0), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < count) & masked


def record_inputs(batch: Dict[str, jax.Array], spec: Dict[str, Any]) -> Dict[str, jax.Array]:
    """`[clean ; noisy copies]` of the stored record: `batch` holds prompt [N,
    B] and, a denoise step (block-major: step t is pass t % S of response block
    t // S), block [N, T, B] before the pass, commit [N, T, B], token [N, T,
    B]. Noisy copy s holds every block as it stood before its pass s; the
    clean copy holds the prompt and every block after its last pass."""
    passes = int(spec["denoise_passes"])
    n, steps, size = batch["block"].shape
    blocks = steps // passes
    by_pass = lambda x: x.reshape(n, blocks, passes, size)
    before, commit, token = (by_pass(batch[name]) for name in ("block", "commit", "token"))
    final = jnp.where(commit[:, :, -1], token[:, :, -1], before[:, :, -1])
    clean = jnp.concatenate([batch["prompt"], final.reshape(n, -1)], axis=1)
    noisy = [before[:, :, s].reshape(n, -1) for s in range(passes)]
    return {"tokens": jnp.concatenate([clean] + noisy, axis=1), "clean_length": clean.shape[1]}


def loss_sums(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float], dtype: Any = jnp.float32,
) -> Dict[str, jax.Array]:
    """Sums over the sequences of `batch` of what the loss is a mean of: over
    denoise steps the clipped surrogate and the clipped value error, over
    committed tokens the entropy of the categorical they were drawn from, and
    over all positions of `[clean ; noisy copies]` and layers the router's
    probabilities [E] and the pairs routed to each expert [E]. Sums add over
    parts of a minibatch."""
    passes, size = int(spec["denoise_passes"]), int(spec["block_length"])
    n, steps, _ = batch["block"].shape
    blocks = steps // passes
    inputs = record_inputs(batch, spec)
    where = layout(blocks + 1, size, passes)
    out = forward(
        params[0], params[1], inputs["tokens"], where["position"],
        block_mask(where["block"], where["copy"]), spec, dtype,
    )
    clean = inputs["clean_length"]
    # the noisy copies, [N, S, blocks, B, ...] -> step order [N, blocks, S, B, ...]
    steps_of = lambda x: jnp.swapaxes(
        x[:, clean:].reshape((n, passes, blocks, size) + x.shape[2:]), 1, 2
    )
    log_probs = steps_of(policy_log_probs(out["logits"], spec))  # [N, blocks, S, B, V]
    commit = batch["commit"].reshape(n, blocks, passes, size)
    token = batch["token"].reshape(n, blocks, passes, size)
    token_log_prob = jnp.take_along_axis(log_probs, token[..., None], axis=-1)[..., 0]
    log_prob = jnp.sum(jnp.where(commit, token_log_prob, 0.0), axis=-1).reshape(n, steps)
    value = jnp.mean(steps_of(out["values"]), axis=-1).reshape(n, steps)
    probs = jnp.exp(log_probs)
    # 0 * -inf at the mask id: its log p is left out of the product, not multiplied
    entropy = -jnp.sum(probs * jnp.where(probs > 0, log_probs, 0.0), axis=-1)  # [N, blocks, S, B]

    ratio = jnp.exp(log_prob - batch["log_prob"])
    eps = hyper["clip_eps"]
    surrogate = jnp.minimum(
        ratio * batch["advantage"], jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * batch["advantage"]
    )
    clipped = batch["value"] + jnp.clip(value - batch["value"], -eps, eps)
    value_error = jnp.maximum((value - batch["target"]) ** 2, (clipped - batch["target"]) ** 2)
    experts = out["router_probs"].shape[-1]
    routed = jax.nn.one_hot(out["expert_index"].reshape(-1), experts, dtype=jnp.float32)
    return {
        "surrogate": jnp.sum(surrogate),
        "entropy": jnp.sum(jnp.where(commit, entropy, 0.0)),
        "value_error": jnp.sum(value_error),
        "router_prob": jnp.sum(out["router_probs"].reshape(-1, experts), axis=0),
        "routed": jnp.sum(routed, axis=0),
        "log_prob": log_prob, "value": value,
    }


def loss_of_sums(
    sums: Dict[str, jax.Array], sequences: int, spec: Dict[str, Any], hyper: Dict[str, float]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The PPO loss of `sequences` whole sequences from their sums: clip and
    value as means over denoise steps, entropy as a mean over committed
    tokens, and the HF load-balancing loss E * sum_e (share of the routed
    pairs of all layers that went to e, summed over the slots) * (mean router
    probability of e) over every position of `[clean ; noisy copies]`."""
    passes, size = int(spec["denoise_passes"]), int(spec["block_length"])
    response = int(spec["response_length"])
    steps = sequences * (response // size) * passes
    rows = int(spec["num_hidden_layers"]) * sequences * (size + (1 + passes) * response)
    actor_loss = -sums["surrogate"] / steps
    entropy = sums["entropy"] / (sequences * response)
    value_loss = sums["value_error"] / steps
    experts = sums["routed"].shape[0]
    aux = experts * jnp.sum((sums["routed"] / rows) * (sums["router_prob"] / rows))
    total = (
        actor_loss - hyper["ent_coef"] * entropy + hyper["vf_coef"] * value_loss
        + hyper["aux_coef"] * aux
    )
    offset, held = int(spec.get("expert_offset", 0)), int(spec["num_experts"])
    mine = sums["routed"][offset:offset + held]
    parts = {
        "total_loss": total, "actor_loss": actor_loss, "entropy": entropy,
        "value_loss": value_loss, "aux_loss": aux,
        "expert_load_max_over_mean": jnp.max(mine) / jnp.mean(mine),
        "routed_pairs_per_token": jnp.sum(sums["routed"]) / rows,
        "held_pairs_per_token": jnp.sum(mine) / rows,
    }
    return total, parts


def ppo_loss(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """`params` = (actor_params, critic_params); `batch` as `record_inputs`
    says, with log_prob and value (the rollout's), advantage, target [N, T].
    `hyper`: clip_eps, ent_coef, vf_coef, aux_coef."""
    sums = loss_sums(params, batch, spec, hyper)
    return loss_of_sums(sums, batch["block"].shape[0], spec, hyper)


def ppo_loss_and_grads(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float],
) -> Tuple[jax.Array, Dict[str, jax.Array], Any]:
    (total, parts), grads = jax.value_and_grad(ppo_loss, has_aux=True)(params, batch, spec, hyper)
    return total, parts, grads


# --------------------------------------------------------------------------- #
# The verifier, GAE, the optimiser: as plainly
# --------------------------------------------------------------------------- #


def verifier_returns(prompt: np.ndarray, response: np.ndarray, modulus: int, mask_id: int) -> np.ndarray:
    """The block token task's return of each sequence, from the tokens alone:
    `prompt` [E, B], `response` [E, L]. The share of the response tokens
    whose residue equals that of the token before them (the last prompt token
    for the first); a position left masked is a miss."""
    before = np.concatenate([prompt[:, -1:], response[:, :-1]], axis=1)
    return np.mean((response != mask_id) & ((response % modulus) == (before % modulus)), axis=1)


def gae(
    rewards: np.ndarray, values: np.ndarray, gamma: float, lam: float, standardize: bool
) -> Tuple[np.ndarray, np.ndarray]:
    """[T, E] of one whole episode a column (the last step terminates):
    advantages by a float64 loop over the denoise steps, targets = values +
    advantages, advantages standardised over the whole batch afterwards."""
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

# A denoise step whose stored log-prob and value are the teacher-forced
# program's own to this much (nats; value over max(1, |value|)) was routed
# alike by the two compilations (ppo_olmoe.py's `_SAME`, read there on the
# chip: where two compilations of one float32 program route alike they are
# 0.004 apart, a token routed to another expert 0.07 and more).
_SAME = 1e-2


def _rms_and_max(got: jax.Array, want: jax.Array, rows: jax.Array) -> Tuple[float, float]:
    """Over the entries `rows` selects: (root-mean-square of got - want over
    that of want, max of |got - want| / max(1, |want|)); inf where a value is
    not finite."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    while rows.ndim < got.ndim:
        rows = rows[..., None]
    finite = bool(jnp.all(jnp.isfinite(jnp.where(rows, got, 0.0)) & jnp.isfinite(jnp.where(rows, want, 0.0))))
    diff = jnp.where(rows, got - want, 0.0)
    rms = jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(jnp.where(rows, want * want, 0.0)))
    worst = jnp.max(jnp.abs(diff) / jnp.maximum(1.0, jnp.abs(jnp.where(rows, want, 0.0))))
    return (float(rms), float(worst)) if finite else (float("inf"), float("inf"))


def _set_agreement(got_index: jax.Array, want_index: jax.Array, num_experts: int) -> Tuple[jax.Array, jax.Array]:
    """Chosen expert sets [L, N, k] on both sides -> (share of (token, slot)
    pairs that agree, [N] bool: the token's sets agree in every layer)."""
    member = lambda index: jnp.any(jax.nn.one_hot(index, num_experts, dtype=bool), axis=-2)
    both = member(got_index) & member(want_index)  # [L, N, E]
    pairs = jnp.sum(both, axis=-1)  # [L, N]
    return jnp.mean(pairs / got_index.shape[-1]), jnp.all(pairs == got_index.shape[-1], axis=0)


def steps_of_record(out: Dict[str, jax.Array], batch: Dict[str, jax.Array], spec: Dict[str, Any]) -> Dict[str, jax.Array]:
    """What a forward over `[clean ; noisy copies]` (`out`: logits [N, P, V],
    values [N, P]) says of every denoise step of the stored record, [N,
    blocks, S(, B)]: log p of the sampled token at every position of the
    block, the step's value, the commit set that the confidences p_i(a_i)
    give among the positions the record says were still masked."""
    passes, size = int(spec["denoise_passes"]), int(spec["block_length"])
    n, steps, _ = batch["block"].shape
    blocks = steps // passes
    clean = out["logits"].shape[1] - passes * blocks * size
    steps_of = lambda x: jnp.swapaxes(x[:, clean:].reshape((n, passes, blocks, size) + x.shape[2:]), 1, 2)
    by_step = lambda x: jnp.asarray(x).reshape(n, blocks, passes, size)
    log_probs = steps_of(policy_log_probs(out["logits"], spec))
    token_log_prob = jnp.take_along_axis(log_probs, by_step(batch["token"])[..., None], axis=-1)[..., 0]
    masked = by_step(batch["block"]) == int(spec["mask_token_id"])
    return {
        "token_log_prob": token_log_prob,
        "log_prob": jnp.sum(jnp.where(by_step(batch["commit"]), token_log_prob, 0.0), axis=-1),
        "value": jnp.mean(steps_of(out["values"]), axis=-1),
        "commit": commit_set(jnp.exp(token_log_prob), masked, size // passes),
    }


def program_forward(nets: Dict[str, Any], params: Any, batch: Dict[str, jax.Array], spec: Dict[str, Any]) -> Dict[str, jax.Array]:
    """The program's teacher-forced entry point as a program of its own on
    the sampled sequences, one at a time: logits and values at every position
    of the noisy copies (the clean copy's are never computed), expert sets at
    every position of `[clean ; noisy copies]`."""
    passes, size = int(spec["denoise_passes"]), int(spec["block_length"])
    n, steps, _ = batch["block"].shape
    blocks = steps // passes
    before = batch["block"].reshape(n, blocks, passes, size)
    inputs = record_inputs(batch, spec)
    clean = inputs["tokens"][:, :inputs["clean_length"]]
    noisy = jnp.swapaxes(before, 1, 2).reshape(n, passes, blocks * size)

    @jax.jit
    def one(params, clean, noisy):
        hidden, stats = nets["trunk_copies"](params.actor_params, clean[None], noisy[None])
        flat = hidden.reshape(-1, hidden.shape[-1])
        return {
            "logits": nets["head"](params.actor_params, flat),
            "values": nets["value"](params.critic_params, flat),
            "expert_index": stats["expert_index"],
            "expert_count": stats["expert_count"],
        }

    outs = [one(params, clean[i], noisy[i]) for i in range(n)]
    stack = lambda key, axis: jnp.concatenate([o[key] for o in outs], axis=axis)
    layers = outs[0]["expert_count"].shape[0]
    return {
        "logits": jnp.stack([o["logits"] for o in outs]), "values": jnp.stack([o["values"] for o in outs]),
        "expert_index": stack("expert_index", 1),
        "pairs_per_token": sum(jnp.sum(o["expert_count"]) for o in outs) / (layers * inputs["tokens"].size),
    }


def reference_forward(params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any], dtype: Any) -> Dict[str, jax.Array]:
    """The plain forward over `[clean ; noisy copies]` of the sampled
    sequences, one at a time — every denoise pass of the record as a full
    forward over its whole prefix, without a cache."""
    inputs = record_inputs(batch, spec)
    passes, size = int(spec["denoise_passes"]), int(spec["block_length"])
    where = layout(batch["block"].shape[1] // passes + 1, size, passes)
    allowed = block_mask(where["block"], where["copy"])
    one = jax.jit(lambda actor, critic, tokens: forward(actor, critic, tokens[None], where["position"], allowed, spec, dtype))
    outs = [one(params[0], params[1], inputs["tokens"][i]) for i in range(inputs["tokens"].shape[0])]
    return {
        "logits": jnp.concatenate([o["logits"] for o in outs]),
        "values": jnp.concatenate([o["values"] for o in outs]),
        "expert_index": jnp.concatenate([o["expert_index"] for o in outs], axis=1),
    }


def compare_forward(got: Dict[str, jax.Array], want: Dict[str, jax.Array], clean: int, spec: Dict[str, Any], top_k: int) -> Dict[str, float]:
    """The teacher-forced program (noisy positions) against the reference, on
    the positions whose expert sets agree in every layer."""
    experts = int(spec["router_experts"])
    agreement, agree = _set_agreement(got["expert_index"], want["expert_index"], experts)
    agree = agree.reshape(want["values"].shape)[:, clean:]
    logits_rms, logits_max = _rms_and_max(got["logits"], want["logits"][:, clean:], agree)
    values_rms, values_max = _rms_and_max(got["values"], want["values"][:, clean:], agree)
    return {
        "logits_max": logits_max, "logits_rms": logits_rms, "values_max": values_max,
        "values_rms": values_rms, "expert_set_disagreement": float(1.0 - agreement),
        "dropped_pairs": abs(float(got["pairs_per_token"]) - top_k),
    }


def compare_record(
    stored: Dict[str, jax.Array], program: Dict[str, jax.Array], want: Dict[str, jax.Array],
    agree: jax.Array,
) -> Dict[str, float]:
    """What the TIMED rollout stored of the sampled sequences' denoise steps
    ([N, blocks, S]: log-prob of its commit set, value; its commit sets [N,
    blocks, S, B]) against the reference's full-prefix forward. The rollout
    keeps no expert sets, so a step counts where `agree` (the sets of the
    teacher-forced program at the block's positions agree with the
    reference's) AND the stored numbers are that program's own to `_SAME`:
    there the rollout's compilation demonstrably routed alike; the share of
    steps where it did not is an error of its own (`differs_from_program`).
    `commit_flips`: the share of steps whose stored commit set is not the
    top-k of the reference's confidences at the stored tokens (a near-tie of
    two confidences flips one now and then; a wrong rule flips most)."""
    gap_log_prob = jnp.abs(stored["log_prob"] - program["log_prob"])
    gap_value = jnp.abs(stored["value"] - program["value"]) / jnp.maximum(1.0, jnp.abs(program["value"]))
    same = (gap_log_prob <= _SAME) & (gap_value <= _SAME)
    rows = agree & same
    diff = jnp.where(rows, stored["log_prob"] - want["log_prob"], 0.0)
    values_rms, values_max = _rms_and_max(stored["value"], want["value"], rows)
    finite = bool(jnp.all(jnp.isfinite(stored["log_prob"])))
    flips = jnp.any(stored["commit"] != want["commit"], axis=-1)
    return {
        "log_prob_rms": float(jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(rows))) if finite else float("inf"),
        "log_prob_max": float(jnp.max(jnp.abs(diff))) if finite else float("inf"),
        "values_max": values_max, "values_rms": values_rms,
        "differs_from_program": float(1.0 - jnp.mean(same)),
        "commit_flips": float(jnp.mean(flips)),
    }


def matmuls_of(fn: Any, *args: Any) -> List[Dict[str, Any]]:
    """Every `dot_general` and `ragged_dot_general` of `fn(*args)` (nested
    calls, loops and custom rules included): operand dtypes, the precision it
    was asked for, and the right operand's shape."""
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


def stated_mismatches(config: Dict[str, Any], nets: Dict[str, Any], params: Any, shapes: Dict[str, Any], batch: Dict[str, jax.Array]) -> List[str]:
    """What the run contradicts of what the configuration file states."""
    out: List[str] = []
    tree = params.actor_params["params"]
    d, heads, kv, hd = (int(config[k]) for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    held, f, v = int(config["num_experts"]), int(config["moe_intermediate_size"]), int(config["vocab_size"])
    e = int(config["router_experts"])
    layer_names = sorted(k for k in tree if k.startswith("layer_"))
    if len(layer_names) != int(config["num_hidden_layers"]):
        out.append(f"{len(layer_names)} layers, stated {config['num_hidden_layers']}")
    want = {
        "embed": (v, d), "lm_head": (d, v), "final_norm": (d,),
        **{
            f"{layer}/{name}": shape
            for layer in layer_names
            for name, shape in {
                "wq": (d, heads * hd), "wk": (d, kv * hd), "wv": (d, kv * hd), "wo": (heads * hd, d),
                "q_norm": (hd,), "k_norm": (hd,), "input_norm": (d,), "post_attn_norm": (d,),
                "router": (d, e), "gate": (held, d, f), "up": (held, d, f), "down": (held, f, d),
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

    size, passes = int(config["block_length"]), int(config["denoise_passes"])
    inputs = record_inputs(batch, config)
    clean = inputs["tokens"][:1, :inputs["clean_length"]]
    noisy = inputs["tokens"][:1, inputs["clean_length"]:].reshape(1, passes, -1)
    cache = jax.eval_shape(lambda: nets["init_cache"](1))
    programs = {
        "trunk_copies": matmuls_of(nets["trunk_copies"], params.actor_params, clean, noisy),
        "block_step": matmuls_of(
            lambda p, c, t: nets["block_step"](p, c, t, 1, False), params.actor_params, cache, clean[:, :size]
        ),
        "block_commit": matmuls_of(
            lambda p, c, t: nets["block_step"](p, c, t, 1, True), params.actor_params, cache, clean[:, :size]
        ),
    }
    for name, matmuls in programs.items():
        if not [m for m in matmuls if m["rhs"] == (d, e)]:
            out.append(f"{name}: no router matmul [{d}, {e}] found")
        for matmul in matmuls:
            stated = config["router_precision"] if matmul["rhs"] == (d, e) else config["matmul_precision"]
            if matmul["dtypes"] != [config["compute_dtype"]] or matmul["precision"] != stated:
                out.append(
                    f"{name}: a matmul with right operand {matmul['rhs']} multiplies {matmul['dtypes']} at "
                    f"{matmul['precision']}, stated {config['compute_dtype']} at {stated}"
                )
        if not any(len(m["rhs"]) == 3 and m["rhs"][0] == held for m in matmuls):
            out.append(f"{name}: no grouped matmul over {held} held experts found")
    stated_steps = int(config["response_length"]) // size * passes
    for key, stated in (
        ("rollout_length", stated_steps), ("epochs", config["epochs"]), ("num_minibatches", config["num_minibatches"]),
        ("model_passes", 1 + int(config["response_length"]) // size * (passes + 1)),
    ):
        if int(shapes.get(key, -1)) != int(stated):
            out.append(f"{key} resolved to {shapes.get(key)}, stated {stated}")
    model = shapes.get("model", {})
    for key, stated in (("block_length", size), ("passes", passes), ("response_length", config["response_length"])):
        if int(model.get(key, -1)) != int(stated):
            out.append(f"{key} resolved to {model.get(key)}, stated {stated}")
    return out


def check_before(ctx: Any) -> Dict[str, Tuple[float, float]]:
    """The learner's GAE (`ops/multistep`, the configuration's
    `multistep_impl`) against a float64 loop over the episode's denoise
    steps: part of set-up."""
    from stoix_tpu.ops import multistep

    config = ctx.cell.config
    length = int(config["response_length"]) // int(config["block_length"]) * int(config["denoise_passes"])
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
            standardize_advantages=False, impl=str(config.get("multistep_impl", "scan")),
        )
    )(r_t, discount_t, v_tm1, v_t)
    want, _ = gae(r_t, v_tm1, 1.0, 0.95, standardize=False)
    tol = float(config["reference"]["gae_tol"])
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


def timed_window(nets: Dict[str, Any], size: int) -> Tuple[Dict[str, Any], Dict[str, Any]]:
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
        "prompt": state.timestep.observation.agent_view[..., size:2 * size],
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


def shuffle_keys(key: jax.Array, blocks: int, passes: int, epochs: int) -> List[jax.Array]:
    """The shuffle key of each epoch of the update that follows a rollout
    from `key`: ff_sdar_ppo splits its key once a denoise pass (S a block)
    and once an epoch, keeping the first half."""
    for _ in range(blocks * passes):
        key = jax.random.split(key)[0]
    keys = []
    for _ in range(epochs):
        key, shuffle_key = jax.random.split(key)
        keys.append(shuffle_key)
    return keys


_SUMMED = ("surrogate", "entropy", "value_error", "router_prob", "routed")


def make_replay(spec: Dict[str, Any], hyper: Dict[str, Any], adam: Dict[str, float], dtype: Any) -> Tuple[Callable, Callable, Callable]:
    """(sums, add_gradient, step) of the reference's update, each one
    program. The loss is a function of sums over sequences, so its gradient
    is the sum over parts of a minibatch of the parts' sums weighted by the
    loss's derivative in them: `add_gradient(total, params, part, weight)`
    adds one part's. `step`: the clip and Adam, actor and critic each."""
    part_sums = lambda params, part: {
        k: v for k, v in loss_sums(params, part, spec, hyper, dtype).items() if k in _SUMMED
    }

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
    from and the rollout it stored (`rollout` leaves [T, E(, B)]: block,
    commit, token, log_prob, value, reward; prompt [E, B]) -> (parameters
    afterwards on the host, the loss parts as the learner logs them: means
    over shards and minibatches). Shard s holds the sequences [s*E/S,
    (s+1)*E/S), standardises its own advantages, shuffles with its own key;
    gradients are means over shards."""
    steps, envs = rollout["log_prob"].shape
    passes = int(spec["denoise_passes"])
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
        by_sequence = lambda x: np.swapaxes(x[:, cols], 0, 1)
        data.append({
            **{k: by_sequence(rollout[k]) for k in ("block", "commit", "token", "log_prob", "value")},
            "prompt": rollout["prompt"][cols], "advantage": advantage.T, "target": target.T,
        })
    keys = [shuffle_keys(jnp.asarray(before["key"][s]), steps // passes, passes, epochs) for s in range(shards)]

    sums, add_gradient, step = make_replay(spec, hyper, ref["adam"], dtype)
    params = jax.device_put(before["params"])
    moments = jax.device_put(before["moments"])
    logged: List[Dict[str, float]] = []
    for epoch in range(epochs):
        orders = [np.asarray(jax.random.permutation(keys[s][epoch], per_shard)) for s in range(shards)]
        for m in range(minibatches):
            picked = [orders[s][m * size:(m + 1) * size] for s in range(shards)]
            # [shards * parts a shard, sequences a part, ...]
            parts = {
                name: jnp.asarray(np.concatenate([
                    data[s][name][picked[s]].reshape((size // part, part) + data[s][name].shape[1:])
                    for s in range(shards)
                ]))
                for name in data[0]
            }
            part_sums = sums(params, parts)
            of_shard = lambda s: jax.tree.map(
                lambda x: jnp.sum(x[s * (size // part):(s + 1) * (size // part)], axis=0), part_sums
            )
            loss = lambda shard_sums: loss_of_sums(shard_sums, size, spec, hyper)
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
            for leaf in jax.tree.leaves(grads):
                leaf.delete()
    after = jax.device_get(params)
    for leaf in jax.tree.leaves((params, moments)):
        leaf.delete()
    return after, {k: float(np.mean([rec[k] for rec in logged])) for k in logged[0]}


def update_errors(before: Any, got: Any, want: Any) -> Tuple[Dict[str, float], Dict[str, List[float]]]:
    """A window's parameter change, program against reference, leaf by leaf:
    |change_got - change_want| over |change_want| (Euclidean norms) ->
    ({worst leaf, all leaves together, |change_want| over |parameters|},
    {leaf: [|change_want|, error]})."""
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


def stored_rollout(before: Dict[str, Any], after: Dict[str, Any], spec: Dict[str, Any], modulus: int) -> Dict[str, np.ndarray]:
    """The window's rollout as the learner handed it out, [T, E(, B)] a
    leaf, with the prompts it started from [E, B], the finished responses [E,
    L] and the reward the verifier gives them (terminal)."""
    episode = {k: np.asarray(v)[0] for k, v in after["episode"].items()}
    passes, mask_id = int(spec["denoise_passes"]), int(spec["mask_token_id"])
    block, commit, token = (episode[f"rollout_{k}"] for k in ("block", "commit", "token"))
    steps, envs, size = block.shape
    prompt = np.asarray(before["prompt"]).reshape(envs, size)
    last = slice(passes - 1, None, passes)  # every block's last pass: [blocks, E, B]
    final = np.where(commit[last], token[last], block[last])
    response = np.swapaxes(final, 0, 1).reshape(envs, -1)
    reward = np.zeros((steps, envs), np.float32)
    reward[-1] = verifier_returns(prompt, response, modulus, mask_id)
    return {
        "block": block, "commit": commit.astype(bool), "token": token, "prompt": prompt,
        "log_prob": episode["rollout_log_prob"], "value": episode["rollout_value"],
        "reward": reward, "logged_return": episode["episode_return"][-1], "response": response,
    }


def sampled_errors(
    ctx: Any, before: Dict[str, Any], rollout: Dict[str, np.ndarray], rows: np.ndarray
) -> Tuple[Dict[str, Tuple[float, float]], Dict[str, float], Dict[str, float]]:
    """On the sequences `rows` of the window's rollout and the parameters it
    started from: the teacher-forced entry point as a program, and what the
    timed rollout stored, against the reference's full-prefix forward ->
    (errors with their tolerances, the bfloat16 reference's errors: the
    second reading, quantiles of what the rollout's and the teacher-forced
    program's numbers differ by)."""
    config, nets = ctx.cell.config, ctx.networks
    ref, top_k = config["reference"], int(config["num_experts_per_tok"])
    batch = {
        **{k: jnp.asarray(np.swapaxes(rollout[k][:, rows], 0, 1)) for k in ("block", "commit", "token", "log_prob", "value")},
        "prompt": jnp.asarray(rollout["prompt"][rows]),
    }
    params = jax.device_put(type(nets["state"].params)(*before["params"]))
    ctx.problems.extend(stated_mismatches(config, nets, params, ctx.shapes, batch))
    program = program_forward(nets, params, batch, config)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    reference_params = jax.device_put(before["params"])
    want, low = (reference_forward(reference_params, batch, config, dtype) for dtype in (jnp.float32, jnp.bfloat16))
    for leaf in jax.tree.leaves(reference_params):
        leaf.delete()
    tolerances = {
        "logits_max": float(ref["max_tol"]), "values_max": float(ref["max_tol"]),
        "logits_rms": float(ref["logits_rms_tol"]), "values_rms": float(ref["values_rms_tol"]),
        "expert_set_disagreement": float(ref["expert_set_tol"]),
        "dropped_pairs": float(ref["dropped_tol"]),
        "log_prob_rms": float(ref["log_prob_rms_tol"]), "log_prob_max": float(ref["log_prob_max_tol"]),
        "differs_from_program": float(ref["rollout_program_tol"]),
        "commit_flips": float(ref["commit_flip_tol"]),
    }
    clean = want["values"].shape[1] - program["values"].shape[1]
    errors = {
        f"tf_{name}": (error, tolerances[name])
        for name, error in compare_forward(program, want, clean, config, top_k).items()
    }
    passes, size = int(config["denoise_passes"]), int(config["block_length"])
    n, steps = batch["log_prob"].shape
    blocks = steps // passes
    pad = lambda out: {  # the program computes nothing at the clean positions
        "logits": jnp.concatenate([jnp.zeros((n, clean) + out["logits"].shape[2:]), out["logits"]], axis=1),
        "values": jnp.concatenate([jnp.zeros((n, clean)), out["values"]], axis=1),
    }
    by_step = lambda x: jnp.asarray(x).reshape(n, blocks, passes)
    stored = {
        "log_prob": by_step(batch["log_prob"]), "value": by_step(batch["value"]),
        "commit": jnp.asarray(batch["commit"]).reshape(n, blocks, passes, size),
    }

    def agreeing(index: jax.Array) -> jax.Array:
        """[n, blocks, S]: the sets at the block's positions of copy s agree."""
        agree = _set_agreement(index, want["expert_index"], int(config["router_experts"]))[1]
        agree = agree.reshape(n, -1)[:, clean:].reshape(n, passes, blocks, size)
        return jnp.swapaxes(jnp.all(agree, axis=-1), 1, 2)

    want_steps = steps_of_record(want, batch, config)
    program_steps = steps_of_record(pad(program), batch, config)
    record = compare_record(stored, program_steps, want_steps, agreeing(program["expert_index"]))
    errors.update({f"rollout_{name}": (error, tolerances[name]) for name, error in record.items()})
    # The second reading: the bfloat16 reference as if it were the program.
    low_steps = steps_of_record(low, batch, config)
    low_stored = {"log_prob": low_steps["log_prob"], "value": low_steps["value"], "commit": low_steps["commit"]}
    low_program = {**low, "logits": low["logits"][:, clean:], "values": low["values"][:, clean:], "pairs_per_token": top_k}
    second = {
        **compare_forward(low_program, want, clean, config, top_k),
        **{f"record_{k}": v for k, v in compare_record(low_stored, low_steps, want_steps, agreeing(low["expert_index"])).items()},
    }
    gaps = {
        "log_prob": jnp.abs(stored["log_prob"] - program_steps["log_prob"]),
        "value": jnp.abs(stored["value"] - program_steps["value"]),
    }
    gap = {
        f"{name}_{label}": float(jnp.quantile(x, q))
        for name, x in gaps.items()
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
    before, after = timed_window(nets, int(config["block_length"]))
    rollout = stored_rollout(before, after, config, int(hyper["env_modulus"]))
    train = {k: float(np.mean(v)) for k, v in after["train"].items()}
    top_k, steps = int(config["num_experts_per_tok"]), int(hyper["epochs"]) * int(hyper["num_minibatches"])
    mask_id = int(config["mask_token_id"])
    errors: Dict[str, Tuple[float, float]] = {
        "rollout_returns": (
            compare.max_scaled_error(rollout["logged_return"], rollout["reward"][-1]),
            float(ref["returns_tol"]),
        ),
        # the mask id is never emitted, and no task token is it
        "rollout_mask_tokens": (
            float(np.sum(rollout["response"] == mask_id) + np.sum(rollout["prompt"] == mask_id)), 0.0
        ),
        "rollout_dropped_pairs": (
            abs(train["rollout_routed_pairs_per_token"] - top_k), float(ref["dropped_tol"])
        ),
        "update_dropped_pairs": (abs(train["routed_pairs_per_token"] - top_k), float(ref["dropped_tol"])),
        "rollout_passes_per_token": (
            abs(train["decode_passes_per_token"] - ctx.shapes["model_passes"] / int(config["response_length"])),
            1e-6,
        ),
        "update_adam_steps": (
            float(max(
                abs(int(got) - int(m["count"]) - steps)
                for got, m in zip(after["count"], before["moments"])
            )), 0.0,
        ),
    }
    envs = rollout["log_prob"].shape[1]
    rows = np.sort(np.random.default_rng(ctx.seed).choice(envs, int(ref["sample_sequences"]), replace=False))
    sampled, second, gap = sampled_errors(ctx, before, rollout, rows)
    errors.update(sampled)
    # The reading that parts the precisions on every seed: the expert sets the
    # program flips against the float32 reference, over those the bfloat16
    # reference flips on the same positions (which reads 1 of itself).
    errors["tf_expert_sets_over_lower_precision"] = (
        sampled["tf_expert_set_disagreement"][0] / max(second["expert_set_disagreement"], 1e-9),
        float(ref["expert_set_ratio_tol"]),
    )

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
    errors["update_held_pairs"] = (
        compare.max_scaled_error(train["held_pairs_per_token"], logged["held_pairs_per_token"]),
        float(ref["held_pairs_tol"]),
    )
    if ref.get("lower_precision_update"):
        low_replayed, low_logged = replay_update(before, rollout, config, hyper, ref, shards, jnp.bfloat16)
        low_update, low_leaves = update_errors(before["params"], low_replayed, replayed)
        second.update({f"update_params_{k}": v for k, v in low_update.items()})
        second.update({f"update_{k}": compare.max_scaled_error(low_logged[k], logged[k]) for k in _LOSS_PARTS})
        second["update_leaves"] = low_leaves
    ctx.health["reference"] = {
        "update_leaves": leaves, "lower_precision": second, "stored_minus_program": gap,
        "rows": rows.tolist(),
        "counters": {k: train[k] for k in (
            "held_pairs_per_token", "rollout_held_pairs_per_token", "commit_confidence_mean",
            "tokens_per_denoise_pass", "decode_passes_per_token", "expert_load_max_over_mean",
        )},
    }
    return errors
