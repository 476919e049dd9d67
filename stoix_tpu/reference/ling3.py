"""Plain reference of the Ling-3.0-flash decoder (`model_type`
`bailing_hybrid`) as a PPO token policy, whole or one expert-parallel rank's
share of it.

The published layers
(https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json; the
delta layer is Kimi Delta Attention, Kimi Linear, arXiv:2510.26692 section 3,
whose public implementation is `fla`'s `KimiDeltaAttention`), in
straightforward `jax.numpy`, float32 at the highest matmul precision, over
whole sequences: the delta rule is the recurrence POSITION BY POSITION (a
`lax.scan` over the sequence that carries the matrix state), the
convolutions are shifted copies added up, latent attention EXPANDS keys and
values a head and is an explicit `[T, T]` masked softmax, the experts are a
loop over the held experts on ALL tokens with a weight mask; there is no
chunk, no cache, no tail, no absorption, no sort, no grouped matmul and no
kernel. For `h [N, T, D]`, every layer l, no bias anywhere (H heads of d; n =
qk_nope_head_dim, r = qk_rope_head_dim, c = kv_lora_rank, v = v_head_dim):

    h = h + mixer_l(RMSNorm_op(h));   h = h + ffn_l(RMSNorm_ffn(h))
    mixer_l is latent attention where (l + 1) % layer_group_size == 0, delta
    attention otherwise
    delta:  q = SiLU(conv(u Wq)), k = SiLU(conv(u Wk)), v = SiLU(conv(u Wv)),
            conv depthwise, causal, short_conv_kernel_size taps;  q_h <- q_h /
            |q_h| / sqrt(d), k_h <- k_h / |k_h|;  g_t = kda_lower_bound *
            sigmoid(exp(A_log_h) (u Wf + dt_bias)) a channel;  beta_t =
            sigmoid(u Wbeta) a head;  S_t = (I - beta_t k_t k_t^T) Diag(exp(
            g_t)) S_{t-1} + beta_t k_t v_t^T, S_0 = 0;  o_t = S_t^T q_t;  Wo [
            RMSNorm_{H d}(o_t) * sigmoid(u Wg)_h ]
    latent: q_h = u Wq,h = [q_nope_h (n) ; q_rope_h (r)];  [l ; k_r] = u Wkva;
            l^ = RMSNorm_c(l);  [k_nope_h ; v_h] = l^ Wkvb,h;  RoPE on q_rope_h
            and on k_r (one for all heads), pairs (x_2i, x_2i+1) rotated by
            p theta^(-2i/r);  k_h = [k_nope_h ; k_r];  o_h = causal softmax(q_h
            k_h^T / sqrt(n + r)) v_h;  Wo [ o_h * sigmoid(u Wg)_h ]
    ffn, l < first_k_dense_replace:  (silu(f W_1) * f W_3) W_2
    ffn, the others:  s = sigmoid_float32(f Wr) over ALL experts;  the experts
            lie in n_group groups in order, a group's score is the sum of its
            two largest s + expert_bias, the topk_group best groups are kept;
            e = top_k(s + expert_bias) inside them;  w = s[e] / (sum(s[e]) +
            1e-20) * routed_scaling_factor;  sum_{j : e_j held} w_j * (silu(f
            Wgate[e_j]) * f Wup[e_j]) Wdown[e_j]  +  shared(f), one SwiGLU of
            width moe_shared_expert_intermediate_size
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
(`stoix_tpu/networks/lfm2.py`, `kda.py` and `mla.py` say which) and shares no
code with them.

Departures from the published forward, each one marked at its line:
  * no padding and no attention-mask argument: every sequence is full;
  * the L2 norm of a head's q and k divides by sqrt(sum of squares + 1e-6)
    (the public implementation's; the description has no epsilon);
  * the output norm of a delta layer is ONE RMSNorm over all H d outputs
    (`group_norm_size` 1), the output gate one number a head on both mixers
    (`gated_attention_proj_granularity_type` `head_wise`);
  * `W_f` is one full [D, H d] matrix (`no_kda_lora`), keys and values have
    the queries' 32 heads (`num_kv_heads_for_linear_attn` 0);
  * in a latent layer `use_qk_norm` is the latent's own RMSNorm and nothing
    on the uncompressed query; the rotated pairs stay where they lie;
  * no clamp of the SwiGLUs: `expert_swiglu_limit_list` and
    `share_expert_swiglu_limit_list` are 0 for every layer of the first
    period (the clamps are in the last 8 of the 42 layers);
  * no multi-token-prediction module (`num_nextn_predict_layers` 1 at
    `mtp_loss_scaling_factor` 0 adds no loss term, and a rollout samples one
    token a step);
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
(parameters and activations; norms, softmaxes, the log-decay and the delta
rule's state still in float32) is the benchmark's lower-precision reading.
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
    angle p * theta^(-2i/r). The pairs stay where they lie."""
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


def causal_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """x [N, T, C], taps [K, C]: y_t = sum_j taps_j * x_{t-K+1+j}, x before
    the sequence 0; shifted copies added up."""
    length, kernel = x.shape[1], taps.shape[0]
    mixed = jnp.zeros_like(x)
    for j in range(kernel):
        back = kernel - 1 - j  # tap j reads the position `back` before
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :length]
        mixed = mixed + taps[j] * shifted
    return mixed


def delta_rule(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array
) -> jax.Array:
    """q, k, v, g [N, T, H, d], beta [N, T, H] -> o [N, T, H, d]: the
    recurrence position by position, its matrix state [N, H, d, d] float32
    from zeros. The positions run in stretches whose inner states a backward
    pass recomputes instead of keeping (`jax.checkpoint`: memory alone — a
    state a position a layer is 2 MiB a sequence at the published widths)."""
    batch, length, heads, d = q.shape
    stretch = next(n for n in (16, 8, 4, 2, 1) if length % n == 0)
    # position first, in stretches: [T / stretch, stretch, N, ...]
    f32 = lambda x: jnp.swapaxes(x, 0, 1).astype(jnp.float32).reshape(
        (length // stretch, stretch) + x.shape[:1] + x.shape[2:]
    )

    def position(state: jax.Array, at: Tuple[jax.Array, ...]) -> Tuple[jax.Array, jax.Array]:
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None] * state  # Diag(exp(g_t)) S_{t-1}
        held = jnp.einsum("nhkv,nhk->nhv", state, k_t)  # what the state answers to k_t
        state = state + beta_t[..., None, None] * k_t[..., None] * (v_t - held)[..., None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, q_t)  # o_t = S_t^T q_t

    positions = jax.checkpoint(lambda state, at: jax.lax.scan(position, state, at))
    _, out = jax.lax.scan(
        positions, jnp.zeros((batch, heads, d, d), jnp.float32),
        (f32(q), f32(k), f32(v), f32(g), f32(beta)),
    )
    return jnp.swapaxes(out.reshape((length,) + out.shape[2:]), 0, 1).astype(v.dtype)


def delta_attention(mixer: Dict[str, Any], u: jax.Array, spec: Dict[str, Any]) -> jax.Array:
    batch, length, _ = u.shape
    heads, d = int(spec["num_attention_heads"]), int(spec["head_dim"])
    split = lambda t: t.reshape(batch, length, heads, d)
    # L2 norm a head; the epsilon is the public implementation's.
    unit = lambda t: (
        t.astype(jnp.float32)
        * jax.lax.rsqrt(jnp.sum(jnp.square(t.astype(jnp.float32)), axis=-1, keepdims=True) + 1e-6)
    ).astype(t.dtype)
    project = lambda w, taps: split(jax.nn.silu(causal_conv(u @ mixer[w], mixer[taps])))
    q = unit(project("wq", "q_conv")) / jnp.sqrt(jnp.asarray(d, u.dtype))
    k, v = unit(project("wk", "k_conv")), project("wv", "v_conv")
    # W_f is one full matrix (no_kda_lora); the log-decay in float32, in (lower_bound, 0).
    rate = jnp.exp(mixer["a_log"].astype(jnp.float32))[:, None] * split(
        (u @ mixer["wf"] + mixer["dt_bias"]).astype(jnp.float32)
    )
    g = float(spec["kda_lower_bound"]) * jax.nn.sigmoid(rate)
    beta = jax.nn.sigmoid(u @ mixer["wbeta"])  # [N, T, H]
    out = delta_rule(q, k, v, g, beta)
    # ONE norm over all H d outputs (group_norm_size 1), then one gate a head.
    normed = split(rms_norm(
        out.reshape(batch, length, heads * d), mixer["out_norm"], float(spec["rms_norm_eps"])
    ))
    gated = normed * jax.nn.sigmoid(u @ mixer["wg"])[..., None]
    return gated.reshape(batch, length, heads * d) @ mixer["wo"]


def latent_attention(mixer: Dict[str, Any], u: jax.Array, spec: Dict[str, Any]) -> jax.Array:
    batch, length, _ = u.shape
    heads, rank = int(spec["num_attention_heads"]), int(spec["kv_lora_rank"])
    nope, rot, v_dim = (
        int(spec["qk_nope_head_dim"]), int(spec["qk_rope_head_dim"]), int(spec["v_head_dim"])
    )
    eps, theta = float(spec["rms_norm_eps"]), float(spec["rope_theta"])
    split = lambda t: t.reshape(batch, length, heads, -1).transpose(0, 2, 1, 3)  # [N, H, T, .]
    q = split(u @ mixer["wq"])  # nothing normalises the uncompressed query
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
    out = out.transpose(0, 2, 1, 3) * jax.nn.sigmoid(u @ mixer["wg"])[..., None]  # head-wise gate
    return out.reshape(batch, length, heads * v_dim) @ mixer["wo"]


def dense_mlp(ffn: Dict[str, Any], f: jax.Array) -> jax.Array:
    # No clamp of the SwiGLU: the published limits are 0 for every layer kept.
    return (jax.nn.silu(f @ ffn["w1"]) * (f @ ffn["w3"])) @ ffn["w2"]


def _held(spec: Dict[str, Any]) -> Tuple[int, int]:
    return int(spec.get("expert_offset", 0)), int(spec["num_experts"])


def group_limited(choice: jax.Array, groups: int, top_groups: int) -> jax.Array:
    """choice [M, E] -> the same with -inf outside each token's `top_groups`
    best of `groups` groups of E / groups experts in order; a group's score
    is the sum of its two largest entries."""
    grouped = choice.reshape(choice.shape[0], groups, -1)
    group_score = jnp.sum(jnp.sort(grouped, axis=-1)[..., -2:], axis=-1)  # [M, groups]
    rank = jnp.argsort(jnp.argsort(-group_score, axis=-1), axis=-1)  # 0 for the best group
    return jnp.where((rank < top_groups)[..., None], grouped, -jnp.inf).reshape(choice.shape)


def moe(
    ffn: Dict[str, Any], f: jax.Array, spec: Dict[str, Any]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """f [M, D] -> (the held experts' part of the result plus the shared
    expert's [M, D], {"probs" [M, E] the sigmoid scores, "index" [M, k] the
    group-limited choice, "ungrouped_index" [M, k] the plain top-k of score +
    bias, "plain_index" [M, k] the top-k of the scores alone} over all E
    experts of the router)."""
    top_k = int(spec["num_experts_per_tok"])
    offset, held = _held(spec)
    scores = jax.nn.sigmoid((f @ ffn["router"]).astype(jnp.float32))
    # e_score_correction_bias: a constant of the tree that only the CHOICE reads.
    choice = scores + ffn["expert_bias"].astype(jnp.float32)
    _, index = jax.lax.top_k(
        group_limited(choice, int(spec["n_group"]), int(spec["topk_group"])), top_k
    )
    _, ungrouped_index = jax.lax.top_k(choice, top_k)
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
    if spec.get("shared_expert", True):  # one shared SwiGLU that every token passes
        out = out + dense_mlp(ffn["shared"], f)
    return out, {
        "probs": scores, "index": index, "ungrouped_index": ungrouped_index,
        "plain_index": plain_index,
    }


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
    scores [L, N*T, E], the chosen experts [L, N*T, k], the top-k of score +
    bias without the group limit and the top-k of the scores alone."""
    tree = jax.tree.map(lambda w: jnp.asarray(w, dtype), actor_params["params"])
    critic_params = jax.tree.map(lambda w: jnp.asarray(w, dtype), critic_params)
    eps, dense_layers = float(spec["rms_norm_eps"]), int(spec["first_k_dense_replace"])
    period = int(spec["layer_group_size"])
    with jax.default_matmul_precision(_HIGHEST):
        embed, head = vocabulary(tree, spec)
        x = embed[tokens]
        batch, length, width = x.shape
        probs, index, ungrouped, plain = [], [], [], []
        for i in range(int(spec["num_hidden_layers"])):
            layer = tree[f"layer_{i}"]
            mixer = latent_attention if (i + 1) % period == 0 else delta_attention
            x = x + mixer(layer["mixer"], rms_norm(x, layer["operator_norm"], eps), spec)
            f = rms_norm(x, layer["ffn_norm"], eps)
            if i < dense_layers:
                x = x + dense_mlp(layer["ffn"], f)
                continue
            routed, router = moe(layer["ffn"], f.reshape(batch * length, width), spec)
            x = x + routed.reshape(batch, length, width)
            probs.append(router["probs"])
            index.append(router["index"])
            ungrouped.append(router["ungrouped_index"])
            plain.append(router["plain_index"])
        hidden = rms_norm(x, tree["final_norm"], eps)
        logits = hidden @ head  # untied
        value_head = critic_params["params"]  # the value head: this repo's addition for PPO
        values = (hidden @ value_head["kernel"])[..., 0] + value_head["bias"][0]
    return {
        "logits": logits.astype(jnp.float32), "values": values.astype(jnp.float32),
        "router_probs": jnp.stack(probs), "expert_index": jnp.stack(index),
        "ungrouped_index": jnp.stack(ungrouped), "plain_index": jnp.stack(plain),
    }


def loss_sums(
    params: Tuple[Any, Any], batch: Dict[str, jax.Array], spec: Dict[str, Any],
    hyper: Dict[str, float], dtype: Any = jnp.float32,
) -> Dict[str, jax.Array]:
    """Sums over the tokens of `batch` (leaves [N, T]: tokens — the policy's
    inputs —, action, log_prob and value — the rollout's —, advantage, target)
    of what the loss is a mean of: the clipped surrogate, the entropy of the
    full categorical, the clipped value error; over tokens and routed layers
    the router's scores [E], the pairs routed to each expert [E], the tokens
    whose chosen set is not the top-k of the scores alone and those whose
    chosen set is not the plain top-k of score + bias. Sums add over parts of
    a minibatch."""
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
    differs = lambda other: jnp.sum(
        jnp.any(member(out["expert_index"]) != member(out[other]), axis=-1)
    ).astype(jnp.float32)
    routed = jax.nn.one_hot(out["expert_index"].reshape(-1), experts, dtype=jnp.float32)
    return {
        "surrogate": jnp.sum(surrogate),
        "entropy": jnp.sum(-jnp.sum(jnp.exp(log_probs) * log_probs, axis=-1)),
        "value_error": jnp.sum(value_error),
        "router_prob": jnp.sum(out["router_probs"].reshape(-1, experts), axis=0),
        "routed": jnp.sum(routed, axis=0),
        "bias_changed": differs("plain_index"),
        "group_changed": differs("ungrouped_index"),
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
        "group_limited_changed_share": sums["group_changed"] / rows,
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
