"""Plain reference of the SDAR mixture-of-experts decoder as a PPO token
policy that generates by diffusion over blocks, for one expert-parallel
rank's share of each layer.

The published layer (`model_type` `sdar_moe`,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json; the
family's `modeling_sdar_moe.py`), in straightforward `jax.numpy`, float32 at
the highest matmul precision: the experts are a loop over the HELD experts on
ALL tokens with a weight mask (a `lax.scan` over the expert axis, one dense
SwiGLU a turn), attention is an explicit masked softmax under a boolean mask
MATRIX, key/value heads are repeated for their query heads, there is no
cache, no sort, no grouped matmul and no kernel. For `x [N, T, D]` at
positions `pos [T]` under `allowed [T, T]`:

    n = RMSNorm(x)
    q = RoPE(RMSNorm_hd(split_H(n Wq)));  k = RoPE(RMSNorm_hd(split_KV(n Wk)))
    v = split_KV(n Wv)                (RMSNorm_hd: over each head's head_dim, one
                                       weight vector [head_dim] for q, one for k;
                                       rotate-half RoPE at the token's own position)
    a = softmax(q k^T / sqrt(head_dim) + mask) v     (query head h reads kv head h // (H / KV))
    x' = x + concat(a) Wo
    p = softmax_float32(RMSNorm(x') Wr) over ALL experts;  (w, e) = top_k(p);  w <- w / sum(w)
    y = x' + sum_{j : e_j held} w_j * (silu(m Wgate[e_j]) * m Wup[e_j]) Wdown[e_j],  m = RMSNorm(x')
    out: RMSNorm(y) -> lm_head (untied) over the vocabulary slice

The chip's share: `spec["num_experts"]` experts from `spec["expert_offset"]`
on are held (the router's width is the `router` weight's own); what the
absent experts would add is left out of y, and that partial y goes on.

Generation (block length B, S denoise passes a block): `layout` gives the
positions, blocks and copies of `[clean ; noisy 1 .. noisy S]`, `block_mask`
the rule "a query in copy c, block b sees a key in copy c', block b' iff
(c' = 0 and b' < b) or (c' = c and b' = b)". A denoise pass over a block is
a full forward over `[committed prefix ; the block]` under that rule (all in
copy 0: every block sees itself and the blocks before it); the mask id's
logit is -inf; the commit set is the `B / S` still-masked positions of
largest confidence p_i(a_i), ties to the lower position.

It reads the weights out of the program's parameter tree by name
(`stoix_tpu/networks/sdar.py` says which) and shares no code with it.

Departures from the published forward, each one:
  * no padding and no attention-mask argument: every sequence is full;
  * no sliding window (`use_sliding_window` false), no dropout, no cache object;
  * the value head — one Dense [D -> 1] on the final-norm hidden state, its
    mean over a block's positions the value of a denoise step — is this
    repo's addition for PPO;
  * the mask token is the LAST id of the vocabulary slice (the published id
    lies outside any eighth of the vocabulary);
  * logits at position i predict position i (no shift), as the family's
    block-diffusion inference does;
  * `load_balancing_loss` is the HF `load_balancing_loss_func` for the
    unpadded case over ALL experts of the router;
  * each layer is wrapped in `jax.checkpoint`: its activations are computed
    again in a backward pass instead of kept (the benchmark differentiates a
    1,540-position sequence beside 0.46 G parameters' optimiser state).

`ppo_loss` is the learner's loss on one minibatch of whole sequences and
`ppo_loss_and_grads` its `jax.grad`. `dtype` is float32; bfloat16
(parameters and activations, norms and softmaxes still in float32) is the
benchmark's lower-precision reading.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
