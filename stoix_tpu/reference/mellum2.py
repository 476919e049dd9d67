"""Plain reference of the Mellum2-12B-A2.5B decoder (`model_type` `mellum`) as
a PPO token policy that generates from a PROMPT, whole or one expert-parallel
rank's share of it.

The published layers
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json),
in straightforward `jax.numpy`, float32 at the highest matmul precision, over
the whole sequence [prefix ; response] in ONE forward: attention is an
explicit score matrix under the causal or the BANDED mask, the experts are a
loop over the held experts on ALL tokens with a weight mask; there is no
kernel, no cache, no ring, no prefill, no sort and no grouped matmul. For `h
[N, T, D]`, every layer l, no bias anywhere (32 query heads on 4 key/value
heads of d = `head_dim`):

    h = h + attn_l(RMSNorm(h));   h = h + moe(RMSNorm(h))
    attn_l (`layer_types[l]`), u the normed input:
        q = u Wq [H, d], k = u Wk [KV, d], v = u Wv [KV, d]
        q_h <- RMSNorm_d(q_h; q_norm), k_g <- RMSNorm_d(k_g; k_norm)
        q_h <- R_l(t) q_h, k_g <- R_l(t) k_g          (rotations below)
        a_tj = softmax_j(q_th . k_jg(h) / sqrt(d)) over j <= t (full_attention)
               or over t - W < j <= t (sliding_attention, W = `sliding_window`)
        o_th = sum_j a_tj v_jg(h),  g(h) = h // (H / KV)
        Wo [ o_th ]                                   (no gate)
    R_l, from `rope_parameters[layer_types[l]]`: rotate-half over the WHOLE
        head, f_i = theta^(-2i/d); `rope_type` `yarn` (the full layers): c(beta)
        = d ln(L0 / (2 pi beta)) / (2 ln theta), low = floor(c(beta_fast)), high
        = ceil(c(beta_slow)), ramp_i = clip((i - low) / (high - low), 0, 1),
        inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i), and cos and sin
        times `attention_factor`; the blend does not depend on the length
    moe:  p = softmax_float32(f Wr) over ALL experts;  e = top_k(p);  w = p[e] /
        sum(p[e]) (`norm_topk_prob`);  sum_{j : e_j held} w_j * (silu(f
        Wgate[e_j]) * f Wup[e_j]) Wdown[e_j]; no shared expert, no scaling
        factor, no selection bias
    out:    RMSNorm(h) W_head over the vocabulary slice (untied)

The share: `spec["num_experts"]` experts from `spec["expert_offset"]` on are
held (the router's width is the `router` weight's own) and
`spec["vocab_slice"]` = (first row, rows) of the vocabulary; a parameter tree
that holds more than the share is cut to it here, so the same function runs
the uncut model and any rank's share of it. What the absent experts would add
is left out of the layer's result.

It reads the weights out of the program's parameter tree by name
(`stoix_tpu/networks/lfm2.py` says which) and shares no code with it.

Readings of what the published config does not spell out (`assumed` in
benchmarks/configs/ppo_mellum2_moe_ep8_share.json gives each its reason) and
departures from the published forward, each marked at its line:
  * a per-head RMSNorm of q and of k, one weight vector [d] each, before the
    rotation (no key says so: the Qwen3-MoE lineage's convention, whose keys
    this config carries);
  * rotate-half pairs dims (i, i + d / 2);
  * every feed-forward is routed (`mlp_layer_types` all `sparse`):
    `intermediate_size` is published and unused;
  * the "MTP head" the model card names has no key in the config and is not
    built;
  * no padding and no attention-mask argument: every sequence is full;
  * scores are computed a block of `spec["attention_query_block"]` queries at
    a time where the sequence is longer than that (memory alone: a layer's
    [32, 3584, 3584] scores are 1.6 GB a sequence), and rematerialised in the
    backward pass (`jax.checkpoint`);
  * logits and values are computed on the LAST `response` positions where the
    caller names them (the response after its prompt): memory alone, the head
    is a function of a position;
  * the value head — one Dense [D -> 1] on the final-norm hidden state — is
    this repo's addition for PPO;
  * `load_balancing_loss` is the HF `load_balancing_loss_func` for the
    unpadded case over ALL experts of the router (the published config has no
    coefficient for it; it is logged, times 0).

`ppo_loss` is the learner's loss on one minibatch of whole sequences and
`ppo_loss_and_grads` its `jax.grad`: the sequence is `batch["tokens"]` [N, P +
G] (prefix, then the response's inputs), the loss is on the G response
positions alone. `dtype` is float32; bfloat16 (parameters and activations;
norms, softmaxes and the router still in float32) is the benchmark's
lower-precision reading. A `spec` without `sliding_window` reads every layer
causally: the benchmark's window-ignored reading.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

_HIGHEST = "highest"


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Computed in float32 whatever `x` is, returned in x's dtype."""
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def inverse_frequencies(head_dim: int, stated: Dict[str, Any]) -> Tuple[jax.Array, float]:
    """(inv_freq [head_dim / 2], the factor on cos and sin) of one layer
    kind's `rope_parameters` entry."""
    theta = float(stated["rope_theta"])
    index = jnp.arange(head_dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * index / head_dim)
    if stated.get("rope_type", "default") != "yarn":
        return plain, 1.0
    original = float(stated["original_max_position_embeddings"])
    turns = lambda beta: head_dim * math.log(original / (2.0 * math.pi * beta)) / (
        2.0 * math.log(theta)
    )
    low = max(math.floor(turns(float(stated["beta_fast"]))), 0)
    high = min(math.ceil(turns(float(stated["beta_slow"]))), head_dim - 1)
    ramp = jnp.clip((index - low) / (float(high - low) or 0.001), 0.0, 1.0)
    blended = plain / float(stated["factor"]) * ramp + plain * (1.0 - ramp)
    return blended, float(stated["attention_factor"])


def rotate(x: jax.Array, stated: Dict[str, Any]) -> jax.Array:
    """x [N, H, T, d], positions 0..T-1: the whole head turned, the pair (i, i
    + d / 2) by the angle p * inv_freq_i."""
    head_dim = x.shape[-1]
    inv_freq, factor = inverse_frequencies(head_dim, stated)
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv_freq[None, :]  # [T, d/2]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    half = head_dim // 2
    first, second = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    ).astype(x.dtype)


def masked_softmax_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, at: jax.Array, window: Optional[int]
) -> jax.Array:
    """q [N, H, Q, d] the queries at positions `at` [Q], k, v [N, H, T, d]: the
    explicit score matrix, the softmax over the keys a query sees (j <= t,
    and t - j < `window` where there is one), the weighted values."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    keys = jnp.arange(k.shape[-2])
    seen = at[:, None] >= keys[None, :]
    if window:
        seen = seen & (at[:, None] - keys[None, :] < window)  # the band t - W < j <= t
    scores = jnp.where(seen, scores, jnp.finfo(jnp.float32).min)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)


def attention(mixer: Dict[str, Any], u: jax.Array, spec: Dict[str, Any], kind: str) -> jax.Array:
    batch, length, _ = u.shape
    heads, kv_heads = int(spec["num_attention_heads"]), int(spec["num_key_value_heads"])
    head_dim, eps = int(spec["head_dim"]), float(spec["rms_norm_eps"])
    stated = spec["rope_parameters"][kind]
    split = lambda t, n: t.reshape(batch, length, n, head_dim).transpose(0, 2, 1, 3)
    # A per-head RMSNorm of q and of k, one weight vector each (assumed).
    q = rotate(rms_norm(split(u @ mixer["wq"], heads), mixer["q_norm"], eps), stated)
    k = rotate(rms_norm(split(u @ mixer["wk"], kv_heads), mixer["k_norm"], eps), stated)
    v = split(u @ mixer["wv"], kv_heads)
    # Query head i reads key/value head i // (H / KV).
    k, v = jnp.repeat(k, heads // kv_heads, axis=1), jnp.repeat(v, heads // kv_heads, axis=1)
    window = spec.get("sliding_window") if kind == "sliding_attention" else None
    # No padding and no mask argument: every sequence is full. Scores are
    # recomputed in a backward pass, not kept, and made a block of queries at
    # a time where the sequence is long: memory alone.
    attend = jax.checkpoint(masked_softmax_attention, static_argnums=(4,))
    block = int(spec.get("attention_query_block") or 512)
    if length <= block or length % block:
        out = attend(q, k, v, jnp.arange(length), window)
    else:
        blocks = length // block
        parts = jax.lax.map(
            lambda part: attend(part[0], k, v, part[1], window),
            (
                jnp.moveaxis(q.reshape(batch, heads, blocks, block, head_dim), 2, 0),
                jnp.arange(length).reshape(blocks, block),
            ),
        )  # [blocks, N, H, block, d]
        out = jnp.moveaxis(parts, 0, 2).reshape(batch, heads, length, head_dim)
    return out.transpose(0, 2, 1, 3).reshape(batch, length, heads * head_dim) @ mixer["wo"]  # no gate


def _held(spec: Dict[str, Any]) -> Tuple[int, int]:
    return int(spec.get("expert_offset", 0)), int(spec["num_experts"])


def moe(
    ffn: Dict[str, Any], f: jax.Array, spec: Dict[str, Any]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """f [M, D] -> (the held experts' part of the result [M, D], {"probs" [M,
    E] the softmax over all E experts of the router, "index" [M, k]})."""
    top_k = int(spec["num_experts_per_tok"])
    offset, held = _held(spec)
    probs = jax.nn.softmax((f @ ffn["router"]).astype(jnp.float32), axis=-1)  # softmax over ALL
    weights, index = jax.lax.top_k(probs, top_k)  # no selection bias
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)  # `norm_topk_prob`; no scaling
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
    return out, {"probs": probs, "index": index}  # no shared expert


def vocabulary(tree: Dict[str, Any], spec: Dict[str, Any]) -> Tuple[jax.Array, jax.Array]:
    """(embedding rows, head columns) held here (`vocab_slice`), else all."""
    first, rows = spec.get("vocab_slice") or (0, tree["embed"].shape[0])
    first, rows = int(first), int(rows)
    return tree["embed"][first:first + rows], tree["lm_head"][:, first:first + rows]


def forward(
    actor_params: Dict[str, Any], critic_params: Dict[str, Any], tokens: jax.Array,
    spec: Dict[str, Any], dtype: Any = jnp.float32, response: Optional[int] = None,
) -> Dict[str, jax.Array]:
    """tokens int [N, T] (ids inside the slice; a prompt and the response's
    inputs after it) -> logits [N, R, V] over the slice (un-normalised) and
    values [N, R] of the last R = `response` positions (all T without it),
    and per layer the router's probabilities [L, N*T, E] and the chosen
    experts [L, N*T, k] of EVERY position."""
    tree = jax.tree.map(lambda w: jnp.asarray(w, dtype), actor_params["params"])
    critic_params = jax.tree.map(lambda w: jnp.asarray(w, dtype), critic_params)
    eps, layers = float(spec["rms_norm_eps"]), int(spec["num_hidden_layers"])
    with jax.default_matmul_precision(_HIGHEST):
        embed, head = vocabulary(tree, spec)
        x = embed[tokens]
        batch, length, width = x.shape
        probs, index = [], []
        for i, kind in enumerate(list(spec["layer_types"])[:layers]):
            layer = tree[f"layer_{i}"]
            x = x + attention(layer["mixer"], rms_norm(x, layer["operator_norm"], eps), spec, kind)
            f = rms_norm(x, layer["ffn_norm"], eps)
            routed, router = moe(layer["ffn"], f.reshape(batch * length, width), spec)  # every layer
            x = x + routed.reshape(batch, length, width)
            probs.append(router["probs"])
            index.append(router["index"])
        if response is not None:  # the head on the response alone: memory alone
            x = x[:, length - int(response):]
        hidden = rms_norm(x, tree["final_norm"], eps)
        logits = hidden @ head  # untied
        value_head = critic_params["params"]  # the value head: this repo's addition for PPO
        values = (hidden @ value_head["kernel"])[..., 0] + value_head["bias"][0]
    return {
        "logits": logits.astype(jnp.float32), "values": values.astype(jnp.float32),
        "router_probs": jnp.stack(probs), "expert_index": jnp.stack(index),
    }


def loss_sums(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float], dtype: Any = jnp.float32,
) -> Dict[str, jax.Array]:
    """Sums over `batch` (tokens [N, P + G]: the prefix, then the policy's
    inputs; action, log_prob and value — the rollout's —, advantage, target
    [N, G]) of what the loss is a mean of. Over the G response positions: the
    clipped surrogate, the entropy of the full categorical, the clipped value
    error. Over ALL P + G positions and the layers: the router's
    probabilities [E], the pairs routed to each expert [E], and the
    (position, layer) pairs themselves (`positions`). Sums add over parts of
    a minibatch."""
    out = forward(params[0], params[1], batch["tokens"], spec, dtype, batch["action"].shape[1])
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
    routed = jax.nn.one_hot(out["expert_index"].reshape(-1), experts, dtype=jnp.float32)
    return {
        "surrogate": jnp.sum(surrogate),
        "entropy": jnp.sum(-jnp.sum(jnp.exp(log_probs) * log_probs, axis=-1)),
        "value_error": jnp.sum(value_error),
        "router_prob": jnp.sum(out["router_probs"].reshape(-1, experts), axis=0),
        "routed": jnp.sum(routed, axis=0),
        "positions": jnp.float32(out["expert_index"].shape[0] * out["expert_index"].shape[1]),
    }


def loss_of_sums(
    sums: Dict[str, jax.Array], tokens: int, spec: Dict[str, Any], hyper: Dict[str, float]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The PPO loss of `tokens` RESPONSE tokens from their sums: clip, value,
    entropy, and the HF load-balancing loss E * sum_e (share of the routed
    pairs of all layers that went to e, summed over the slots) * (mean router
    probability of e), both over every position, the prefix's too."""
    rows = sums["positions"]
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
    }
    return total, parts


def ppo_loss(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """`params` = (actor_params, critic_params); `batch` holds tokens [N, P +
    G] (the prefix, then the policy's inputs) and, each [N, G]: action,
    log_prob and value (the rollout's), advantage, target. `hyper`: clip_eps,
    ent_coef, vf_coef, aux_coef."""
    sums = loss_sums(params, batch, spec, hyper)
    return loss_of_sums(sums, batch["action"].size, spec, hyper)


def ppo_loss_and_grads(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float],
) -> Tuple[jax.Array, Dict[str, jax.Array], Any]:
    (total, parts), grads = jax.value_and_grad(ppo_loss, has_aux=True)(params, batch, spec, hyper)
    return total, parts, grads
