"""The plain reference of `ppo_ling3_flash_ep64_share` and what `correct`
holds such a run to. The forward and the loss below are the benchmark's own
copy of stoix_tpu/reference/ling3.py (kept equal by tests/test_ling3_ppo.py):
the published Ling-3.0-flash (`bailing_hybrid`) layers in straightforward
float32 `jax.numpy` at the highest matmul precision over whole sequences —
the delta rule as the recurrence POSITION BY POSITION (a `lax.scan` that
carries the matrix state), the convolutions as shifted copies added up,
latent attention with keys and values EXPANDED a head and an explicit [T, T]
masked softmax, the router's group-limited choice over all 512 experts, the
experts as a loop over the held experts on all tokens, the shared expert
beside them; no chunk, no cache, no tail, no absorption, no sort, no kernel —
reading the weights out of the program's parameter tree by name and sharing
no code with the program. The departures from the published forward are
listed in that file's header.

The system is `ff_lm_ppo` and the share is a held one with a selection bias,
as `ppo_lfm2_moe_ep4_share`'s and `ppo_kanana2_moe_ep8_share`'s: what a
window is, what the rollout stores, how the update is replayed, `expert_bias`
to the bit and the held share's counters are those configurations'. This file
loads a PRIVATE copy of references/ppo_lfm2.py (`loader.load_reference`: a
module object of its own, which loads its own private copy of
references/ppo_olmoe.py) and gives it this configuration's `forward`,
`loss_sums`, `loss_of_sums`, `expected_shapes` and `stated_mismatches`;
`check_before` is that copy's, and `check_after` is that copy's with one
more comparison. What differs is here: the forward (the decode it is compared
with goes through five matrix states with their convolution tails and one
latent cache, at all 64 slots; the teacher-forced pass it is compared with is
the CHUNKED form of the recurrence), the loss's sums, one more counter
(`group_limited_changed_share`: what the group limit re-routed, the logged
share against the reference's own count), and what the configuration file
states: the `bailing_hybrid` keys, a delta layer's thirteen leaves, a latent
layer's six, the shared expert's three, the untied head, the carry's kinds.
`check_after` runs on the chip, outside the timed window, on WHAT WAS TIMED.
Logits are compared, never sampled tokens. The two entry points it runs as
programs of their own are compiled with the XLA options the run's learner was
compiled with (`_JaxWith`), and every run prints where the stored record
parted from that decode (`health.reference.parted`).

Tolerances are in the configuration file (`reference.*_tol`) with their
reasons; the readings they were set from are in PERF.md section 6 (PR 40).
Every run also makes the second reading — the same reference with bfloat16
parameters and activations against itself in float32, which has to come out
as not correct — and prints it (`health.reference.lower_precision`).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import loader
from benchmarks.harness import reference as compare

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


# --------------------------------------------------------------------------- #
# What `correct` holds a run to
# --------------------------------------------------------------------------- #


def layer_kinds(config: Dict[str, Any]) -> List[str]:
    """The mixer of each layer kept: latent attention closes every period."""
    period = int(config["layer_group_size"])
    return [
        "latent_attention" if (i + 1) % period == 0 else "delta_attention"
        for i in range(int(config["num_hidden_layers"]))
    ]


def expected_shapes(config: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """The parameter tree the configuration file states, leaf by leaf."""
    d, f, fm = int(config["hidden_size"]), int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    heads, hd, taps = int(config["num_attention_heads"]), int(config["head_dim"]), int(config["short_conv_kernel_size"])
    rank, nope, rot, v_dim = (int(config[k]) for k in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    held, e = int(config["num_experts"]), int(config["router_experts"])
    shared = int(config["num_shared_experts"]) * int(config["moe_shared_expert_intermediate_size"])
    width = heads * hd
    mixers = {
        "delta_attention": {
            **{name: (d, width) for name in ("wq", "wk", "wv", "wf")},
            **{name: (taps, width) for name in ("q_conv", "k_conv", "v_conv")},
            "dt_bias": (width,), "a_log": (heads,), "wbeta": (d, heads), "wg": (d, heads),
            "out_norm": (width,), "wo": (width, d),
        },
        "latent_attention": {
            "wq": (d, heads * (nope + rot)), "wkv_a": (d, rank + rot), "kv_norm": (rank,),
            "wkv_b": (rank, heads * (nope + v_dim)), "wo": (heads * v_dim, d), "wg": (d, heads),
        },
    }
    dense = {"w1": (d, f), "w3": (d, f), "w2": (f, d)}
    routed = {
        "router": (d, e), "expert_bias": (e,), "gate": (held, d, fm), "up": (held, d, fm),
        "down": (held, fm, d), "shared/w1": (d, shared), "shared/w3": (d, shared), "shared/w2": (shared, d),
    }
    vocab = int(config["vocab_size"])
    want = {"embed": (vocab, d), "final_norm": (d,), "lm_head": (d, vocab)}  # untied
    for i, kind in enumerate(layer_kinds(config)):
        ffn = dense if i < int(config["first_k_dense_replace"]) else routed
        want.update({f"layer_{i}/operator_norm": (d,), f"layer_{i}/ffn_norm": (d,)})
        want.update({f"layer_{i}/mixer/{name}": shape for name, shape in mixers[kind].items()})
        want.update({f"layer_{i}/ffn/{name}": shape for name, shape in ffn.items()})
    return want


def expected_carry(config: Dict[str, Any], batch: int) -> List[Tuple[int, ...]]:
    """The decode carry's leaves the configuration file states, in layer
    order: a matrix a head and three convolutions' tails a delta layer, rows
    kv_lora_rank + qk_rope_head_dim wide a latent layer."""
    heads, hd, taps = int(config["num_attention_heads"]), int(config["head_dim"]), int(config["short_conv_kernel_size"])
    rows = (batch, int(config["rollout_length"]), int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"]))
    delta = [(batch, heads, hd, hd), (batch, taps - 1, 3 * heads * hd)]
    return [shape for kind in layer_kinds(config) for shape in (delta if kind == "delta_attention" else [rows])]


def stated_mismatches(config: Dict[str, Any], nets: Dict[str, Any], params: Any, shapes: Dict[str, Any], tokens: jax.Array) -> List[str]:
    """What the run contradicts of what the configuration file states."""
    out = _lf.shape_mismatch(config, params.actor_params["params"])
    leaf_dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    if leaf_dtypes != [config["parameter_dtype"]]:
        out.append(f"parameters are {leaf_dtypes}, stated {config['parameter_dtype']}")

    d, e, held = int(config["hidden_size"]), int(config["router_experts"]), int(config["num_experts"])
    cache = jax.eval_shape(lambda: nets["init_cache"](tokens.shape[0]))
    carry = [tuple(x.shape) for x in jax.tree.leaves(cache) if x.ndim >= 3]
    if carry != expected_carry(config, tokens.shape[0]):
        out.append(f"the decode carry holds {carry}, stated {expected_carry(config, tokens.shape[0])}")
    if any(str(x.dtype) != config["parameter_dtype"] for x in jax.tree.leaves(cache) if x.ndim >= 3):
        out.append(f"the decode carry is not {config['parameter_dtype']}")
    programs = {
        "forward": _lm.matmuls_of(nets["forward"], params.actor_params, tokens),
        "step": _lm.matmuls_of(nets["step"], params.actor_params, cache, tokens[:, 0]),
    }
    # The expansion W_kvb as ONE product over every position is the update's;
    # a decode step that made it would have expanded its cache.
    expansion = (int(config["kv_lora_rank"]), int(config["num_attention_heads"]) * (
        int(config["qk_nope_head_dim"]) + int(config["v_head_dim"])))
    if any(m["rhs"] == expansion for m in programs["step"]):
        out.append(f"step: the decode multiplies by the whole expansion {expansion}: it is not absorbed")
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
    for key in ("rollout_length", "epochs", "num_minibatches"):
        if int(shapes.get(key, -1)) != int(config[key]):
            out.append(f"{key} resolved to {shapes.get(key)}, stated {config[key]}")
    return out


_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The held share's window, record, replay and counters: a private copy of
# the LFM2 configuration's file (and, inside it, of the OLMoE one's), given
# this configuration's forward, loss and stated tree.
_lf = loader.load_reference("ppo_lfm2", _ROOT)
_lm = _lf._lm
_lf.forward, _lf.expected_shapes, _lf.stated_mismatches = forward, expected_shapes, stated_mismatches
_lm.loss_sums, _lm.loss_of_sums = loss_sums, loss_of_sums

check_before = _lf.check_before  # the learner's GAE against a float64 loop

_make_replay = _lm.make_replay


def _one_at_a_time(*args: Any) -> Tuple[Any, Any, Any]:
    """references/ppo_olmoe.py's replay programs, with each part's gradient
    WAITED FOR before the next is enqueued: beside parameters, the gradient
    sum and Adam's two moments (10.65 GiB here) one `add_gradient` holds 2.9
    GiB of its own (compiled for a described v5e), and two enqueued at once
    would ask for more than the chip has."""
    sums, add_gradient, step = _make_replay(*args)
    return sums, lambda *operands: jax.block_until_ready(add_gradient(*operands)), step


_lm.make_replay = _one_at_a_time


class _JaxWith:
    """`jax` as the private copy of references/ppo_olmoe.py sees it while it
    builds its two stand-in programs: `jit` with the XLA options the run's
    learner was compiled with (drivers/anakin_ling3.py hands them over;
    configs/network/ling3_flash_moe.yaml says why the learner has one). The
    timed rollout is part of that learner, and the standalone decode stands in
    for its expert sets where the stored numbers are its own to `_SAME`: with
    the same options the two compilations agree to 1e-5 nats on every token
    (0 of 4,096 parted in five runs), with XLA's defaults for the stand-in they
    round to bfloat16 at other points (half the tokens 0.0017 nats apart) and
    part at 34 scattered router near-ties of 4,096 (PERF.md section 6, PR 40)."""

    def __init__(self, options: Dict[str, Any]) -> None:
        self._options = options

    def __getattr__(self, name: str) -> Any:
        return getattr(jax, name)

    def jit(self, fn: Any, **kwargs: Any) -> Any:
        return jax.jit(fn, compiler_options=dict(self._options), **kwargs)


def _parted(gap: Dict[str, jax.Array], rows: List[int]) -> Dict[str, Any]:
    """Where the timed rollout's stored numbers are not the standalone decode
    program's own to `_SAME` (`rollout_differs_from_decode` counts them): a
    sampled sequence -> [tokens parted, first position, last position, the
    largest log-prob gap], so that a run over its limit says whether one
    near-tie parted a sequence's tail or the tokens lie scattered."""
    log_prob, value = np.asarray(gap["log_prob"]), np.asarray(gap["value"])
    parted = (log_prob > _lm._SAME) | (value > _lm._SAME)
    return {
        str(row): [int(np.sum(at)), int(np.argmax(at)), int(len(at) - 1 - np.argmax(at[::-1])), float(np.max(lp))]
        for row, at, lp in zip(rows, parted, log_prob) if np.any(at)
    }


def check_after(ctx: Any) -> Dict[str, Tuple[float, float]]:
    """references/ppo_lfm2.py's comparison of the timed window, its two
    stand-in programs compiled as the learner was (`_JaxWith`), and beside
    its counters the share of tokens the group limit re-routed: what the
    timed update logged against the reference's own count over its replay."""
    seen: Dict[str, Any] = {}
    window, replay, gap_of = _lm.timed_window, _lm.replay_update, _lm._compilation_gap
    outputs_of, options = _lm.program_outputs, (ctx.networks or {}).get("compiler_options")

    def timed_window(nets: Dict[str, Any]) -> Any:
        before, after = window(nets)
        seen["train"] = after["train"]
        return before, after

    def replay_update(*args: Any) -> Any:
        replayed, logged = replay(*args)
        seen.setdefault("logged", logged)  # the float32 replay comes first
        return replayed, logged

    def compilation_gap(*args: Any) -> Any:
        seen["gap"] = gap_of(*args)  # the last call is the stored record's
        return seen["gap"]

    def program_outputs(*args: Any) -> Any:
        _lm.jax = _JaxWith(options) if options else jax
        try:
            return outputs_of(*args)
        finally:
            _lm.jax = jax

    _lm.timed_window, _lm.replay_update, _lm._compilation_gap = timed_window, replay_update, compilation_gap
    _lm.program_outputs = program_outputs
    try:
        errors = _lf.check_after(ctx)
    finally:
        _lm.timed_window, _lm.replay_update, _lm._compilation_gap = window, replay, gap_of
        _lm.program_outputs = outputs_of
    if "gap" in seen and "reference" in ctx.health:
        ctx.health["reference"]["parted"] = _parted(seen["gap"], ctx.health["reference"]["rows"])
        ctx.health["reference"]["stand_in_compiler_options"] = dict(options or {})
    name = "group_limited_changed_share"
    if "logged" in seen and name in seen["train"]:
        got = float(np.mean(seen["train"][name]))
        errors[f"update_{name}"] = (
            compare.max_scaled_error(got, seen["logged"][name]),
            float(ctx.cell.config["reference"]["group_changed_tol"]),
        )
        ctx.health["reference"]["counters"][name] = got
    elif errors:
        ctx.problems.append(f"the timed update logged no {name}")
    return errors
