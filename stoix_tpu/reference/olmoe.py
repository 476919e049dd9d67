"""Plain reference of the OLMoE decoder block as a PPO token policy.

The published forward (`model_type` `olmoe`,
https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json;
`modeling_olmoe.py` of the `transformers` library), in straightforward float32
`jax.numpy` at the highest matmul precision: the experts are a loop (a
`lax.scan` over the expert axis: one dense SwiGLU a turn, so that it compiles
in seconds at 64 experts) with a one-hot combine, attention is an explicit `[T, T]`
masked softmax, there is no cache, no sort and no kernel. For `x [B, T, D]`:

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    Attn:  q, k, v = x Wq, x Wk, x Wv;  q, k = RMSNorm_D(q), RMSNorm_D(k)   (over the
           whole D-wide projection, BEFORE the split into heads);  RoPE (theta,
           rotate-half) on q and k;  causal softmax(q k^T / sqrt(head_dim)) v;  Wo
    MoE:   p = softmax_float32(x Wg) over ALL experts;  top-k of p, weights NOT
           renormalised;  sum_k p_k * down_k(silu(gate_k(x)) * up_k(x))
    out:   RMSNorm(y) -> lm_head (untied);  RMSNorm(v) = v * rsqrt(mean(v^2) + eps) * w

It reads the weights out of the program's parameter tree by name
(`stoix_tpu/networks/olmoe.py` says which) and shares no code with it.

Departures from the HF forward, each one:
  * no attention mask argument, no padding: every sequence is full length;
  * `clip_qkv` is null in the published config, so no clipping is written;
  * no `attention_dropout` (0.0 in the config) and no KV-cache object;
  * the value head — one Dense [D -> 1] on the final-norm hidden state — is
    this repo's addition for PPO and is not part of the published model;
  * `load_balancing_loss` is the HF `load_balancing_loss_func` for the
    unpadded case: num_experts * sum_e (share of (token, slot) pairs of all
    layers routed to e, summed over the k slots) * (mean router probability
    of e).

`ppo_loss` is the learner's loss on one minibatch of whole sequences and
`ppo_loss_and_grads` its `jax.grad`: PPO clip, clipped value loss, entropy of
the full categorical, the auxiliary loss.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

_HIGHEST = "highest"


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [B, H, T, head_dim], positions 0..T-1."""
    head_dim, length = x.shape[-1], x.shape[-2]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [T, head_dim]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


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
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
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
    spec: Dict[str, Any],
) -> Dict[str, jax.Array]:
    """tokens int [B, T] -> logits [B, T, V] (un-normalised), values [B, T],
    and per layer the router's probabilities [L, B*T, E] and chosen experts
    [L, B*T, k]."""
    tree = actor_params["params"]
    eps = float(spec["rms_norm_eps"])
    with jax.default_matmul_precision(_HIGHEST):
        x = jnp.asarray(tree["embed"], jnp.float32)[tokens]
        batch, length, width = x.shape
        probs, index = [], []
        for i in range(int(spec["num_hidden_layers"])):
            layer = jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), tree[f"layer_{i}"])
            x = x + attention(layer, rms_norm(x, layer["input_norm"], eps), spec)
            normed = rms_norm(x, layer["post_attn_norm"], eps)
            routed, router = moe(layer, normed.reshape(batch * length, width), spec)
            x = x + routed.reshape(batch, length, width)
            probs.append(router["probs"])
            index.append(router["index"])
        hidden = rms_norm(x, jnp.asarray(tree["final_norm"], jnp.float32), eps)
        logits = hidden @ jnp.asarray(tree["lm_head"], jnp.float32)
        head = critic_params["params"]
        values = (hidden @ jnp.asarray(head["kernel"], jnp.float32))[..., 0] + jnp.asarray(
            head["bias"], jnp.float32
        )[0]
    return {
        "logits": logits, "values": values,
        "router_probs": jnp.stack(probs), "expert_index": jnp.stack(index),
    }


def load_balancing_loss(
    router_probs: jax.Array, expert_index: jax.Array, num_experts: int
) -> jax.Array:
    """router_probs [L, N, E], expert_index [L, N, k]."""
    probs = router_probs.reshape(-1, num_experts)
    index = expert_index.reshape(probs.shape[0], -1)
    routed_share = jnp.mean(jax.nn.one_hot(index, num_experts, dtype=jnp.float32), axis=0)  # [k, E]
    return num_experts * jnp.sum(routed_share * jnp.mean(probs, axis=0)[None, :])


def ppo_loss(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """`params` = (actor_params, critic_params); `batch` holds, each [B, T]:
    tokens (the policy's inputs), action, log_prob and value (the rollout's),
    advantage, target. `hyper`: clip_eps, ent_coef, vf_coef, aux_coef."""
    out = forward(params[0], params[1], batch["tokens"], spec)
    log_probs = jax.nn.log_softmax(out["logits"], axis=-1)
    log_prob = jnp.take_along_axis(log_probs, batch["action"][..., None], axis=-1)[..., 0]
    ratio = jnp.exp(log_prob - batch["log_prob"])
    eps = hyper["clip_eps"]
    surrogate = jnp.minimum(
        ratio * batch["advantage"], jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * batch["advantage"]
    )
    actor_loss = -jnp.mean(surrogate)
    entropy = jnp.mean(-jnp.sum(jnp.exp(log_probs) * log_probs, axis=-1))
    clipped = batch["value"] + jnp.clip(out["values"] - batch["value"], -eps, eps)
    value_loss = jnp.mean(
        jnp.maximum((out["values"] - batch["target"]) ** 2, (clipped - batch["target"]) ** 2)
    )
    aux = load_balancing_loss(out["router_probs"], out["expert_index"], int(spec["num_experts"]))
    total = (
        actor_loss - hyper["ent_coef"] * entropy + hyper["vf_coef"] * value_loss
        + hyper["aux_coef"] * aux
    )
    parts = {
        "actor_loss": actor_loss, "entropy": entropy, "value_loss": value_loss, "aux_loss": aux,
    }
    return total, parts


def ppo_loss_and_grads(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float],
) -> Tuple[jax.Array, Dict[str, jax.Array], Any]:
    (total, parts), grads = jax.value_and_grad(ppo_loss, has_aux=True)(params, batch, spec, hyper)
    return total, parts, grads
