"""Plain reference of the Laguna-XS.2 decoder (`model_type` `laguna`) as a PPO
token policy, whole or one expert-parallel rank's share of it.

The published layers
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json), in
straightforward `jax.numpy`, float32 at the highest matmul precision, over
whole sequences: attention is an explicit `[T, T]` score matrix under the
causal or the BANDED mask, the experts are a loop over the held experts on ALL
tokens with a weight mask; there is no kernel, no cache, no ring, no sort and
no grouped matmul. For `h [N, T, D]`, every layer l, no bias anywhere (H_l
query heads and 8 key/value heads of d = `head_dim`):

    h = h + mixer_l(RMSNorm_op(h));   h = h + ffn_l(RMSNorm_ffn(h))
    mixer_l (`layer_types[l]`, H_l = `num_attention_heads_per_layer[l]`), u the
    normed input:
        q = u Wq [H_l, d], k = u Wk [8, d], v = u Wv [8, d]
        q_h <- RMSNorm_d(q_h; q_norm), k_g <- RMSNorm_d(k_g; k_norm)
        q_h <- R_l(t) q_h, k_g <- R_l(t) k_g          (rotations below)
        a_tj = softmax_j(q_th . k_jg(h) / sqrt(d)) over j <= t (full_attention)
               or over t - W < j <= t (sliding_attention, W = `sliding_window`)
        o_th = sum_j a_tj v_jg(h),  g(h) = h // (H_l / 8)
        Wo [ o_th * sigmoid(u Wg)_h ]
    R_l, from `rope_parameters[layer_types[l]]`: rotate-half over the FIRST r =
        `partial_rotary_factor` d dims of a head, the rest passing; f_i =
        theta^(-2i/r); `rope_type` `yarn`: c(beta) = r ln(L0 / (2 pi beta)) /
        (2 ln theta), low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
        ramp_i = clip((i - low) / (high - low), 0, 1), inv_freq_i = (f_i /
        factor) ramp_i + f_i (1 - ramp_i), and cos and sin times
        `attention_factor`; the blend does not depend on the sequence's length
    ffn, `mlp_layer_types[l]` dense:  (silu(f W_1) * f W_3) W_2
    ffn, sparse:  s = sigmoid_float32(f Wr) over ALL experts;  e = top_k(s +
        expert_bias);  w = s[e] / (sum(s[e]) + 1e-20) * moe_routed_scaling_factor;
        sum_{j : e_j held} w_j * (silu(f Wgate[e_j]) * f Wup[e_j]) Wdown[e_j]
        (the weights on the experts' OUTPUTS)  +  shared(f), one SwiGLU of width
        `shared_expert_intermediate_size`, ungated
    out:    RMSNorm(h) W_head over the vocabulary slice (untied)

The share: `spec["num_experts"]` experts from `spec["expert_offset"]` on are
held (the router's width is the `router` weight's own) and
`spec["vocab_slice"]` = (first row, rows) of the vocabulary; a parameter tree
that holds more than the share is cut to it here, so the same function runs
the uncut model and any rank's share of it. What the absent experts would add
is left out of the layer's result; the shared expert is what every rank
computes alike (`spec["shared_expert"]` false leaves it out: a rank that is
not the one it is counted on).

It reads the weights out of the program's parameter tree by name
(`stoix_tpu/networks/lfm2.py` says which) and shares no code with it.

Readings of what the published config does not spell out (`assumed` in
benchmarks/configs/ppo_laguna_xs2_ep32_share.json gives each its reason) and
departures from the published forward, each marked at its line:
  * `gating` true is ONE sigmoid gate a query head on the attention output
    before W_o, computed from the layer's normed input (W_g [D, H_l]); the
    nonlinearity is assumed;
  * a per-head RMSNorm of q and of k, one weight vector [d] each, before the
    rotation (no key says so: the lineage's convention);
  * sigmoid scores, renormalised, no soft-capping; the selection bias is the
    tree's `expert_bias`, read as a constant (zeros here: nothing publishes
    one): it takes no gradient (only the choice reads it);
  * the shared expert is ungated;
  * rotate-half pairs dims (i, i + r / 2) INSIDE the rotated part;
  * no padding and no attention-mask argument: every sequence is full;
  * the value head — one Dense [D -> 1] on the final-norm hidden state — is
    this repo's addition for PPO;
  * `load_balancing_loss` is the HF `load_balancing_loss_func` for the
    unpadded case over ALL experts of the router, on the sigmoid scores (the
    published config has no coefficient for it; it is logged, times 0);
  * an attention layer's scores are rematerialised in the backward pass
    (`jax.checkpoint`: memory alone — a layer's [H, T, T] scores are 268 MB a
    sequence at the published widths and T = 1,024).

`ppo_loss` is the learner's loss on one minibatch of whole sequences and
`ppo_loss_and_grads` its `jax.grad`. `dtype` is float32; bfloat16 (parameters
and activations; norms, softmaxes and the router still in float32) is the
benchmark's lower-precision reading. A `spec` without `sliding_window` reads
every layer causally: the benchmark's window-ignored reading.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

_HIGHEST = "highest"


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Computed in float32 whatever `x` is, returned in x's dtype."""
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def inverse_frequencies(rotary_dim: int, stated: Dict[str, Any]) -> Tuple[jax.Array, float]:
    """(inv_freq [rotary_dim / 2], the factor on cos and sin) of one layer
    kind's `rope_parameters` entry."""
    theta = float(stated["rope_theta"])
    index = jnp.arange(rotary_dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * index / rotary_dim)
    if stated.get("rope_type", "default") != "yarn":
        return plain, 1.0
    original = float(stated["original_max_position_embeddings"])
    turns = lambda beta: rotary_dim * math.log(original / (2.0 * math.pi * beta)) / (
        2.0 * math.log(theta)
    )
    low = max(math.floor(turns(float(stated["beta_fast"]))), 0)
    high = min(math.ceil(turns(float(stated["beta_slow"]))), rotary_dim - 1)
    ramp = jnp.clip((index - low) / (float(high - low) or 0.001), 0.0, 1.0)
    blended = plain / float(stated["factor"]) * ramp + plain * (1.0 - ramp)
    return blended, float(stated["attention_factor"])


def rotate(x: jax.Array, head_dim: int, stated: Dict[str, Any]) -> jax.Array:
    """x [N, H, T, d], positions 0..T-1: the first r dims of a head turned, the
    pair (i, i + r / 2) by the angle p * inv_freq_i; the other d - r pass."""
    rotary_dim = int(head_dim * float(stated.get("partial_rotary_factor", 1.0)))
    inv_freq, factor = inverse_frequencies(rotary_dim, stated)
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv_freq[None, :]  # [T, r/2]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    half = rotary_dim // 2
    first, second = x[..., :half].astype(jnp.float32), x[..., half:rotary_dim].astype(jnp.float32)
    turned = jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    ).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)


def masked_softmax_attention(q: jax.Array, k: jax.Array, v: jax.Array, seen: jax.Array) -> jax.Array:
    """q, k, v [N, H, T, d], seen [T, T] bool: the explicit score matrix, the
    softmax over the keys a query sees, the weighted values."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    scores = jnp.where(seen, scores, jnp.finfo(jnp.float32).min)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)


def attention(
    mixer: Dict[str, Any], u: jax.Array, spec: Dict[str, Any], kind: str, heads: int
) -> jax.Array:
    batch, length, _ = u.shape
    kv_heads, head_dim = int(spec["num_key_value_heads"]), int(spec["head_dim"])
    eps = float(spec["rms_norm_eps"])
    stated = spec["rope_parameters"][kind]
    split = lambda t, n: t.reshape(batch, length, n, head_dim).transpose(0, 2, 1, 3)
    # A per-head RMSNorm of q and of k, one weight vector each (assumed).
    q = rotate(rms_norm(split(u @ mixer["wq"], heads), mixer["q_norm"], eps), head_dim, stated)
    k = rotate(rms_norm(split(u @ mixer["wk"], kv_heads), mixer["k_norm"], eps), head_dim, stated)
    v = split(u @ mixer["wv"], kv_heads)
    k, v = jnp.repeat(k, heads // kv_heads, axis=1), jnp.repeat(v, heads // kv_heads, axis=1)
    # No padding and no mask argument: every sequence is full.
    at = jnp.arange(length)
    seen = at[:, None] >= at[None, :]
    window = spec.get("sliding_window")
    if kind == "sliding_attention" and window:
        seen = seen & (at[:, None] - at[None, :] < int(window))  # the band t - W < j <= t
    # Scores are recomputed in a backward pass, not kept: memory alone.
    out = jax.checkpoint(masked_softmax_attention)(q, k, v, seen)
    # One sigmoid gate a query head, from the normed input (assumed).
    out = out.transpose(0, 2, 1, 3) * jax.nn.sigmoid(u @ mixer["wg"])[..., None]
    return out.reshape(batch, length, heads * head_dim) @ mixer["wo"]


def dense_mlp(ffn: Dict[str, Any], f: jax.Array) -> jax.Array:
    return (jax.nn.silu(f @ ffn["w1"]) * (f @ ffn["w3"])) @ ffn["w2"]


def _held(spec: Dict[str, Any]) -> Tuple[int, int]:
    return int(spec.get("expert_offset", 0)), int(spec["num_experts"])


def moe(
    ffn: Dict[str, Any], f: jax.Array, spec: Dict[str, Any]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """f [M, D] -> (the held experts' part of the result plus the shared
    expert's [M, D], {"probs" [M, E] the sigmoid scores, "index" [M, k],
    "plain_index" [M, k] the top-k of the scores alone} over all E experts of
    the router)."""
    top_k = int(spec["num_experts_per_tok"])
    offset, held = _held(spec)
    scores = jax.nn.sigmoid((f @ ffn["router"]).astype(jnp.float32))  # sigmoid (assumed)
    # expert_bias: a constant of the tree that only the CHOICE reads (zeros here).
    _, index = jax.lax.top_k(scores + ffn["expert_bias"].astype(jnp.float32), top_k)
    _, plain_index = jax.lax.top_k(scores, top_k)
    weights = jnp.take_along_axis(scores, index, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)  # renormalised
    weights = weights * float(spec["moe_routed_scaling_factor"])  # on the experts' OUTPUTS
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
    if spec.get("shared_expert", True):  # one shared SwiGLU that every token passes, ungated
        out = out + dense_mlp(ffn["shared"], f)
    return out, {"probs": scores, "index": index, "plain_index": plain_index}


def vocabulary(tree: Dict[str, Any], spec: Dict[str, Any]) -> Tuple[jax.Array, jax.Array]:
    """(embedding rows, head columns) held here (`vocab_slice`), else all."""
    first, rows = spec.get("vocab_slice") or (0, tree["embed"].shape[0])
    first, rows = int(first), int(rows)
    return tree["embed"][first:first + rows], tree["lm_head"][:, first:first + rows]


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
    eps, layers = float(spec["rms_norm_eps"]), int(spec["num_hidden_layers"])
    kinds = list(spec["layer_types"])[:layers]
    feed_forwards = list(spec["mlp_layer_types"])[:layers]
    heads = list(spec["num_attention_heads_per_layer"])[:layers]
    with jax.default_matmul_precision(_HIGHEST):
        embed, head = vocabulary(tree, spec)
        x = embed[tokens]
        batch, length, width = x.shape
        probs, index, plain = [], [], []
        for i, kind in enumerate(kinds):
            layer = tree[f"layer_{i}"]
            u = rms_norm(x, layer["operator_norm"], eps)
            x = x + attention(layer["mixer"], u, spec, kind, int(heads[i]))
            f = rms_norm(x, layer["ffn_norm"], eps)
            if feed_forwards[i] == "dense":
                x = x + dense_mlp(layer["ffn"], f)
                continue
            routed, router = moe(layer["ffn"], f.reshape(batch * length, width), spec)
            x = x + routed.reshape(batch, length, width)
            probs.append(router["probs"])
            index.append(router["index"])
            plain.append(router["plain_index"])
        hidden = rms_norm(x, tree["final_norm"], eps)
        logits = hidden @ head  # untied
        value_head = critic_params["params"]  # the value head: this repo's addition for PPO
        values = (hidden @ value_head["kernel"])[..., 0] + value_head["bias"][0]
    return {
        "logits": logits.astype(jnp.float32), "values": values.astype(jnp.float32),
        "router_probs": jnp.stack(probs), "expert_index": jnp.stack(index),
        "plain_index": jnp.stack(plain),
    }


def routed_layers(spec: Dict[str, Any]) -> int:
    return list(spec["mlp_layer_types"])[:int(spec["num_hidden_layers"])].count("sparse")


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
    rows = routed_layers(spec) * tokens
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
