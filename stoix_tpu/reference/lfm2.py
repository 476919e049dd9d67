"""Plain reference of the LFM2 mixture-of-experts decoder as a PPO token
policy, whole or one expert-parallel rank's share of it.

The published layer (`model_type` `lfm2_moe`,
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json; the
family's `modeling_lfm2_moe.py`), in straightforward `jax.numpy`, float32 at
the highest matmul precision, over whole sequences: the short convolution is
three shifted copies of its input added up, attention is an explicit `[T, T]`
masked softmax with the key/value heads repeated for their query heads, the
experts are a loop over the held experts on ALL tokens with a weight mask (a
`lax.scan` over the expert axis, one dense SwiGLU a turn); there is no cache,
no tail, no sort, no grouped matmul and no kernel. For `h [N, T, D]`, every
layer l, no bias anywhere:

    h = h + mixer_l(RMSNorm_op(h));   h = h + ffn_l(RMSNorm_ffn(h))
    conv:   [B ; C ; X] = u W_in;  z = B * X;  c_t = sum_j w_j * z_{t-K+1+j}
            (z before the sequence is 0; K = conv_L_cache);  (C * c) W_out
    full_attention:  q = RoPE(RMSNorm_hd(split_H(u Wq)));  k = RoPE(RMSNorm_hd(
            split_KV(u Wk)));  v = split_KV(u Wv);  causal softmax(q k^T /
            sqrt(head_dim)) v, query head h reads kv head h // (H / KV);  Wo
    ffn, l < num_dense_layers:  (silu(f W_1) * f W_3) W_2
    ffn, the others:  s = sigmoid_float32(f Wr) over ALL experts;  e = top_k(s
            + expert_bias);  w = s[e] / (sum(s[e]) + 1e-6) * routed_scaling_factor;
            sum_{j : e_j held} w_j * (silu(f Wgate[e_j]) * f Wup[e_j]) Wdown[e_j]
    out:    RMSNorm(h) E^T over the vocabulary slice (E the embedding: tied)

The share: `spec["num_experts"]` experts from `spec["expert_offset"]` on are
held (the router's width is the `router` weight's own) and
`spec["vocab_slice"]` = (first row, rows) of the vocabulary; a parameter tree
that holds more than the share is cut to it here, so the same function runs
the uncut model (`num_experts` = the router's width, no `vocab_slice`) and
any rank's share of it. What the absent experts would add is left out of the
layer's result, and that partial result goes on.

It reads the weights out of the program's parameter tree by name
(`stoix_tpu/networks/lfm2.py` says which) and shares no code with it.

Departures from the published forward, each one marked at its line:
  * no padding and no attention-mask argument: every sequence is full;
  * `head_dim` = hidden_size / num_attention_heads (the config has no key);
  * the head is the embedding's transpose (the family ties them);
  * the value head — one Dense [D -> 1] on the final-norm hidden state — is
    this repo's addition for PPO;
  * `expert_bias` is read from the parameter tree as a constant: it takes
    no gradient (only the choice reads it) and no rule updates it;
  * `load_balancing_loss` is the HF `load_balancing_loss_func` for the
    unpadded case over ALL experts of the router, on the sigmoid scores (the
    published config has no coefficient for it; it is logged, times 0).

`ppo_loss` is the learner's loss on one minibatch of whole sequences and
`ppo_loss_and_grads` its `jax.grad`. `dtype` is float32; bfloat16
(parameters and activations, norms and softmaxes still in float32) is the
benchmark's lower-precision reading.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

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
    """x [N, H, T, head_dim], positions 0..T-1."""
    head_dim, length = x.shape[-1], x.shape[-2]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [T, head_dim]
    return (x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)).astype(x.dtype)


def short_conv(mixer: Dict[str, Any], u: jax.Array) -> jax.Array:
    """u [N, T, D] -> the gated short convolution's result [N, T, D]."""
    length = u.shape[1]
    b, c, x = jnp.split(u @ mixer["in_proj"], 3, axis=-1)
    z = b * x
    taps = mixer["conv"].shape[0]  # conv_L_cache
    mixed = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads the position `back` before
        shifted = jnp.concatenate([jnp.zeros_like(z[:, :back]), z[:, :length - back]], axis=1)
        mixed = mixed + mixer["conv"][j] * shifted
    return (c * mixed) @ mixer["out_proj"]


def attention(mixer: Dict[str, Any], u: jax.Array, spec: Dict[str, Any]) -> jax.Array:
    batch, length, _ = u.shape
    heads, kv_heads = int(spec["num_attention_heads"]), int(spec["num_key_value_heads"])
    # head_dim is not a key of the published config: hidden_size / heads.
    head_dim = int(spec.get("head_dim") or int(spec["hidden_size"]) // heads)
    eps, theta = float(spec["norm_eps"]), float(spec["rope_theta"])
    split = lambda t, n: t.reshape(batch, length, n, head_dim).transpose(0, 2, 1, 3)
    q = _rope(rms_norm(split(u @ mixer["wq"], heads), mixer["q_norm"], eps), theta)
    k = _rope(rms_norm(split(u @ mixer["wk"], kv_heads), mixer["k_norm"], eps), theta)
    v = split(u @ mixer["wv"], kv_heads)
    k, v = jnp.repeat(k, heads // kv_heads, axis=1), jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(
        jnp.float32(head_dim)
    )
    # No padding and no mask argument: every sequence is full, the mask is causal.
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)
    return out.transpose(0, 2, 1, 3).reshape(batch, length, heads * head_dim) @ mixer["wo"]


def dense_mlp(ffn: Dict[str, Any], f: jax.Array) -> jax.Array:
    return (jax.nn.silu(f @ ffn["w1"]) * (f @ ffn["w3"])) @ ffn["w2"]


def _held(spec: Dict[str, Any]) -> Tuple[int, int]:
    return int(spec.get("expert_offset", 0)), int(spec["num_experts"])


def moe(
    ffn: Dict[str, Any], f: jax.Array, spec: Dict[str, Any]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """f [M, D] -> (the held experts' part of the result [M, D], {"probs" [M,
    E] the sigmoid scores, "index" [M, k], "plain_index" [M, k] the top-k of
    the scores alone} over all E experts of the router)."""
    top_k = int(spec["num_experts_per_tok"])
    offset, held = _held(spec)
    scores = jax.nn.sigmoid((f @ ffn["router"]).astype(jnp.float32))
    # expert_bias: a constant of the parameter tree that only the CHOICE reads.
    _, index = jax.lax.top_k(scores + ffn["expert_bias"].astype(jnp.float32), top_k)
    _, plain_index = jax.lax.top_k(scores, top_k)
    weights = jnp.take_along_axis(scores, index, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)  # norm_topk_prob
    weights = weights * float(spec.get("routed_scaling_factor", 1.0))
    mine = offset + jnp.arange(held)
    # [M, held]: the weight of each held expert for each token, 0 where not chosen
    combine = jnp.sum(
        jnp.where(index[..., None] == mine, weights[..., None], 0.0), axis=1
    ).astype(f.dtype)
    # A tree that holds every expert is cut to the share.
    share = lambda w: w if w.shape[0] == held else w[offset:offset + held]

    def expert(out: jax.Array, weights: Tuple[jax.Array, ...]) -> Tuple[jax.Array, None]:
        gate, up, down, weight = weights
        hidden = jax.nn.silu(f @ gate) * (f @ up)
        return out + weight[:, None] * (hidden @ down), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(f),
        (share(ffn["gate"]), share(ffn["up"]), share(ffn["down"]), combine.T),
    )
    return out, {"probs": scores, "index": index, "plain_index": plain_index}


def embedding(tree: Dict[str, Any], spec: Dict[str, Any]) -> jax.Array:
    """The rows of the embedding held here (`vocab_slice`), else all."""
    first, rows = spec.get("vocab_slice") or (0, tree["embed"].shape[0])
    return tree["embed"][int(first):int(first) + int(rows)]


def forward(
    actor_params: Dict[str, Any], critic_params: Dict[str, Any], tokens: jax.Array,
    spec: Dict[str, Any], dtype: Any = jnp.float32,
) -> Dict[str, jax.Array]:
    """tokens int [N, T] (ids inside the slice) -> logits [N, T, V] over the
    slice (un-normalised), values [N, T], and per ROUTED layer the router's
    scores [L, N*T, E], the chosen experts [L, N*T, k] and the top-k of the
    scores alone [L, N*T, k]."""
    tree = jax.tree.map(lambda w: jnp.asarray(w, dtype), actor_params["params"])
    critic_params = jax.tree.map(lambda w: jnp.asarray(w, dtype), critic_params)
    eps, dense_layers = float(spec["norm_eps"]), int(spec["num_dense_layers"])
    kinds = list(spec["layer_types"])[:int(spec["num_hidden_layers"])]
    with jax.default_matmul_precision(_HIGHEST):
        embed = embedding(tree, spec)
        x = embed[tokens]
        batch, length, width = x.shape
        probs, index, plain = [], [], []
        for i, kind in enumerate(kinds):
            layer = tree[f"layer_{i}"]
            u = rms_norm(x, layer["operator_norm"], eps)
            mixed = short_conv(layer["mixer"], u) if kind == "conv" else attention(
                layer["mixer"], u, spec
            )
            x = x + mixed
            f = rms_norm(x, layer["ffn_norm"], eps)
            if i < dense_layers:
                x = x + dense_mlp(layer["ffn"], f)
                continue
            routed, router = moe(layer["ffn"], f.reshape(batch * length, width), spec)
            x = x + routed.reshape(batch, length, width)
            probs.append(router["probs"])
            index.append(router["index"])
            plain.append(router["plain_index"])
        hidden = rms_norm(x, tree["final_norm"], eps)
        logits = hidden @ embed.T  # the head is the embedding's transpose (tied)
        head = critic_params["params"]  # the value head: this repo's addition for PPO
        values = (hidden @ head["kernel"])[..., 0] + head["bias"][0]
    return {
        "logits": logits.astype(jnp.float32), "values": values.astype(jnp.float32),
        "router_probs": jnp.stack(probs), "expert_index": jnp.stack(index),
        "plain_index": jnp.stack(plain),
    }


def loss_sums(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float], dtype: Any = jnp.float32,
) -> Dict[str, jax.Array]:
    """Sums over the tokens of `batch` (leaves [N, T]: tokens — the policy's
    inputs —, action, log_prob and value — the rollout's —, advantage, target)
    of what the loss is a mean of: the clipped surrogate, the entropy of the
    full categorical, the clipped value error; over tokens and routed layers
    the router's scores [E], the pairs routed to each expert [E] and the
    tokens whose chosen set is not the top-k of the scores alone. Sums add
    over parts of a minibatch."""
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
    experts = out["router_probs"].shape[-1]
    member = lambda index: jnp.any(jax.nn.one_hot(index, experts, dtype=bool), axis=-2)
    routed = jax.nn.one_hot(out["expert_index"].reshape(-1), experts, dtype=jnp.float32)
    return {
        "surrogate": jnp.sum(surrogate),
        "entropy": jnp.sum(-jnp.sum(jnp.exp(log_probs) * log_probs, axis=-1)),
        "value_error": jnp.sum(value_error),
        "router_prob": jnp.sum(out["router_probs"].reshape(-1, experts), axis=0),
        "routed": jnp.sum(routed, axis=0),
        "bias_changed": jnp.sum(
            jnp.any(member(out["expert_index"]) != member(out["plain_index"]), axis=-1)
        ).astype(jnp.float32),
    }


def loss_of_sums(
    sums: Dict[str, jax.Array], tokens: int, spec: Dict[str, Any], hyper: Dict[str, float]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The PPO loss of `tokens` tokens from their sums: clip, value, entropy,
    and the HF load-balancing loss E * sum_e (share of the routed pairs of
    all routed layers that went to e, summed over the slots) * (mean router
    score of e)."""
    routed_layers = int(spec["num_hidden_layers"]) - int(spec["num_dense_layers"])
    rows = routed_layers * tokens
    actor_loss = -sums["surrogate"] / tokens
    entropy = sums["entropy"] / tokens
    value_loss = sums["value_error"] / tokens
    experts = sums["routed"].shape[0]
    aux = experts * jnp.sum((sums["routed"] / rows) * (sums["router_prob"] / rows))
    total = (
        actor_loss - hyper["ent_coef"] * entropy + hyper["vf_coef"] * value_loss
        + hyper["aux_coef"] * aux
    )
    offset, held = _held(spec)
    mine = sums["routed"][offset:offset + held]
    parts = {
        "total_loss": total, "actor_loss": actor_loss, "entropy": entropy,
        "value_loss": value_loss, "aux_loss": aux,
        "expert_load_max_over_mean": jnp.max(mine) / jnp.mean(mine),
        "routed_pairs_per_token": jnp.sum(sums["routed"]) / rows,
        "held_pairs_per_token": jnp.sum(mine) / rows,
        "router_bias_changed_share": sums["bias_changed"] / rows,
    }
    return total, parts


def ppo_loss(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """`params` = (actor_params, critic_params); `batch` holds, each [N, T]:
    tokens (the policy's inputs), action, log_prob and value (the rollout's),
    advantage, target. `hyper`: clip_eps, ent_coef, vf_coef, aux_coef."""
    sums = loss_sums(params, batch, spec, hyper)
    return loss_of_sums(sums, batch["tokens"].size, spec, hyper)


def ppo_loss_and_grads(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float],
) -> Tuple[jax.Array, Dict[str, jax.Array], Any]:
    (total, parts), grads = jax.value_and_grad(ppo_loss, has_aux=True)(params, batch, spec, hyper)
    return total, parts, grads
