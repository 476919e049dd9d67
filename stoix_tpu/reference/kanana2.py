"""Plain reference of the Kanana-2-30B-A3B decoder (`model_type`
`deepseek_v3`) as a PPO token policy, whole or one expert-parallel rank's
share of it.

The published layer
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json;
the family's `modeling_deepseek_v3.py`), in straightforward `jax.numpy`,
float32 at the highest matmul precision, over whole sequences: keys and
values are EXPANDED a head from the latent through W_kvb, attention is an
explicit `[T, T]` masked softmax, the experts are a loop over the held
experts on ALL tokens with a weight mask (a `lax.scan` over the expert axis,
one dense SwiGLU a turn); there is no cache, no absorption of W_kvb into the
query or the output, no sort, no grouped matmul and no kernel. For `h [N, T,
D]`, every layer l, no bias anywhere (H heads, n = qk_nope_head_dim, r =
qk_rope_head_dim, c = kv_lora_rank, v = v_head_dim):

    h = h + MLA_l(RMSNorm_op(h));   h = h + ffn_l(RMSNorm_ffn(h))
    MLA:    q_h = u Wq,h = [q_nope_h (n) ; q_rope_h (r)];  [l ; k_r] = u Wkva;
            l^ = RMSNorm_c(l);  [k_nope_h ; v_h] = l^ Wkvb,h;  RoPE on q_rope_h
            and on k_r (one for all heads), pairs (x_2i, x_2i+1) rotated by
            p theta^(-2i/r);  k_h = [k_nope_h ; k_r];  causal softmax(q_h k_h^T
            / sqrt(n + r)) v_h;  Wo over the heads' v-wide results
    ffn, l < first_k_dense_replace:  (silu(f W_1) * f W_3) W_2
    ffn, the others:  s = sigmoid_float32(f Wr) over ALL experts;  e = top_k(s
            + e_score_correction_bias);  w = s[e] / (sum(s[e]) + 1e-20) *
            routed_scaling_factor;  sum_{j : e_j held} w_j * (silu(f Wgate[e_j])
            * f Wup[e_j]) Wdown[e_j]  +  shared(f), one SwiGLU of width
            n_shared_experts * moe_intermediate_size
    out:    RMSNorm(h) W_head over the vocabulary slice (untied)

The share: `spec["n_routed_experts"]` experts from `spec["expert_offset"]` on
are held (the router's width is the `router` weight's own) and
`spec["vocab_slice"]` = (first row, rows) of the vocabulary; a parameter tree
that holds more than the share is cut to it here, so the same function runs
the uncut model and any rank's share of it. What the absent experts would add
is left out of the layer's result; the shared expert is what every rank
computes alike (`spec["shared_expert"]` false leaves it out: a rank that is
not the one it is counted on).

It reads the weights out of the program's parameter tree by name
(`stoix_tpu/networks/lfm2.py` and `mla.py` say which) and shares no code with
them.

Departures from the published forward, each one marked at its line:
  * no padding and no attention-mask argument: every sequence is full;
  * the rotated pairs stay where they lie (the family's code first permutes
    each head's r rotated dimensions to [evens ; odds], q and k alike, which
    leaves every q . k as it is);
  * `n_group` = `topk_group` = 1: group-limited choice is the plain top-k;
  * the two shared experts are one SwiGLU of twice the width (as the
    family's code builds them);
  * the value head — one Dense [D -> 1] on the final-norm hidden state — is
    this repo's addition for PPO;
  * `e_score_correction_bias` is the tree's `expert_bias`, read as a
    constant: it takes no gradient (only the choice reads it) and no rule
    updates it;
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


def rope_pairs(x: jax.Array, theta: float) -> jax.Array:
    """x [..., T, r], positions 0..T-1: the pair (x_2i, x_2i+1) turned by the
    angle p * theta^(-2i/r). The pairs stay where they lie (the family's code
    permutes them to [evens ; odds] first, q and k alike)."""
    length, dim = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # [T, r/2]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (dim // 2, 2))
    first, second = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         first * jnp.sin(angle) + second * jnp.cos(angle)], axis=-1,
    )
    return turned.reshape(x.shape).astype(x.dtype)


def latent_attention(mixer: Dict[str, Any], u: jax.Array, spec: Dict[str, Any]) -> jax.Array:
    batch, length, _ = u.shape
    heads, rank = int(spec["num_attention_heads"]), int(spec["kv_lora_rank"])
    nope, rot, v_dim = (
        int(spec["qk_nope_head_dim"]), int(spec["qk_rope_head_dim"]), int(spec["v_head_dim"])
    )
    eps, theta = float(spec["rms_norm_eps"]), float(spec["rope_theta"])
    split = lambda t: t.reshape(batch, length, heads, -1).transpose(0, 2, 1, 3)  # [N, H, T, .]
    q = split(u @ mixer["wq"])
    q_nope, q_rope = q[..., :nope], rope_pairs(q[..., nope:], theta)
    down = u @ mixer["wkv_a"]
    latent = rms_norm(down[..., :rank], mixer["kv_norm"], eps)
    k_rope = rope_pairs(down[..., rank:], theta)  # [N, T, r]: one for all heads
    expanded = split(latent @ mixer["wkv_b"])  # keys and values, a head
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    scores = (
        jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope) + jnp.einsum("bhqd,bkd->bhqk", q_rope, k_rope)
    ).astype(jnp.float32) / jnp.sqrt(jnp.float32(nope + rot))
    # No padding and no mask argument: every sequence is full, the mask is causal.
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)
    return out.transpose(0, 2, 1, 3).reshape(batch, length, heads * v_dim) @ mixer["wo"]


def dense_mlp(ffn: Dict[str, Any], f: jax.Array) -> jax.Array:
    return (jax.nn.silu(f @ ffn["w1"]) * (f @ ffn["w3"])) @ ffn["w2"]


def _held(spec: Dict[str, Any]) -> Tuple[int, int]:
    return int(spec.get("expert_offset", 0)), int(spec["n_routed_experts"])


def moe(
    ffn: Dict[str, Any], f: jax.Array, spec: Dict[str, Any]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """f [M, D] -> (the held experts' part of the result plus the shared
    expert's [M, D], {"probs" [M, E] the sigmoid scores, "index" [M, k],
    "plain_index" [M, k] the top-k of the scores alone} over all E experts of
    the router)."""
    top_k = int(spec["num_experts_per_tok"])
    offset, held = _held(spec)
    scores = jax.nn.sigmoid((f @ ffn["router"]).astype(jnp.float32))
    # e_score_correction_bias: a constant of the tree that only the CHOICE
    # reads; n_group = topk_group = 1, so the choice is the plain top-k.
    _, index = jax.lax.top_k(scores + ffn["expert_bias"].astype(jnp.float32), top_k)
    _, plain_index = jax.lax.top_k(scores, top_k)
    weights = jnp.take_along_axis(scores, index, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)  # norm_topk_prob
    weights = weights * float(spec["routed_scaling_factor"])
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
    if spec.get("shared_expert", True):  # the two shared experts: one SwiGLU of twice the width
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
    eps, dense_layers = float(spec["rms_norm_eps"]), int(spec["first_k_dense_replace"])
    with jax.default_matmul_precision(_HIGHEST):
        embed, head = vocabulary(tree, spec)
        x = embed[tokens]
        batch, length, width = x.shape
        probs, index, plain = [], [], []
        for i in range(int(spec["num_hidden_layers"])):
            layer = tree[f"layer_{i}"]
            x = x + latent_attention(layer["mixer"], rms_norm(x, layer["operator_norm"], eps), spec)
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
        logits = hidden @ head  # untied
        value_head = critic_params["params"]  # the value head: this repo's addition for PPO
        values = (hidden @ value_head["kernel"])[..., 0] + value_head["bias"][0]
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
    routed_layers = int(spec["num_hidden_layers"]) - int(spec["first_k_dense_replace"])
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
