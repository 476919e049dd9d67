"""The plain reference of `ppo_kanana2_moe_ep8_share` and what `correct` holds
such a run to. The forward and the loss below are the benchmark's own copy of
stoix_tpu/reference/kanana2.py (kept equal by tests/test_kanana2_ppo.py): the
published Kanana-2 (`deepseek_v3`) layers in straightforward float32
`jax.numpy` at the highest matmul precision over whole sequences — keys and
values EXPANDED a head from the latent, attention as an explicit [T, T] masked
softmax, the experts as a loop over the held experts on all tokens, the shared
expert beside them; no cache, no absorption, no sort, no kernel — reading the
weights out of the program's parameter tree by name and sharing no code with
the program. The departures from the published forward are listed in that
file's header.

The system is `ff_lm_ppo` and the share is a held one with a selection bias,
as `ppo_lfm2_moe_ep4_share`'s: what a window is, what the rollout stores, how
the update is replayed, `expert_bias` to the bit and the held share's counters
are that configuration's. This file loads a PRIVATE copy of
references/ppo_lfm2.py (`loader.load_reference`: a module object of its own,
which loads its own private copy of references/ppo_olmoe.py) and gives it
this configuration's `forward`, `loss_sums`, `loss_of_sums`,
`expected_shapes` and `stated_mismatches`; `check_before` and `check_after`
are that copy's. What differs is here: the forward (the decode it is compared
with goes through the latent cache, absorbed, at all 128 slots), the loss's
sums, and what the configuration file states: the `deepseek_v3` keys, the
latent layer's five leaves, the shared expert's three, the untied head.
`check_after` runs on the chip, outside the timed window, on WHAT WAS TIMED.
Logits are compared, never sampled tokens.

Tolerances are in the configuration file (`reference.*_tol`) with their
reasons; the readings they were set from are in PERF.md section 6 (PR 38).
Every run also makes the second reading — the same reference with bfloat16
parameters and activations against itself in float32, which has to come out
as not correct — and prints it (`health.reference.lower_precision`).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness import loader

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


# --------------------------------------------------------------------------- #
# What `correct` holds a run to
# --------------------------------------------------------------------------- #


def expected_shapes(config: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """The parameter tree the configuration file states, leaf by leaf."""
    d, f, fm = int(config["hidden_size"]), int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    heads, rank = int(config["num_attention_heads"]), int(config["kv_lora_rank"])
    nope, rot, v_dim = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"]), int(config["v_head_dim"])
    held, e, shared = int(config["n_routed_experts"]), int(config["router_experts"]), int(config["n_shared_experts"]) * fm
    mixer = {
        "wq": (d, heads * (nope + rot)), "wkv_a": (d, rank + rot), "kv_norm": (rank,),
        "wkv_b": (rank, heads * (nope + v_dim)), "wo": (heads * v_dim, d),
    }
    dense = {"w1": (d, f), "w3": (d, f), "w2": (f, d)}
    routed = {
        "router": (d, e), "expert_bias": (e,), "gate": (held, d, fm), "up": (held, d, fm),
        "down": (held, fm, d), "shared/w1": (d, shared), "shared/w3": (d, shared), "shared/w2": (shared, d),
    }
    vocab = int(config["vocab_size"])
    want = {"embed": (vocab, d), "final_norm": (d,), "lm_head": (d, vocab)}  # untied
    for i in range(int(config["num_hidden_layers"])):
        ffn = dense if i < int(config["first_k_dense_replace"]) else routed
        want.update({f"layer_{i}/operator_norm": (d,), f"layer_{i}/ffn_norm": (d,)})
        want.update({f"layer_{i}/mixer/{name}": shape for name, shape in mixer.items()})
        want.update({f"layer_{i}/ffn/{name}": shape for name, shape in ffn.items()})
    return want


def stated_mismatches(config: Dict[str, Any], nets: Dict[str, Any], params: Any, shapes: Dict[str, Any], tokens: jax.Array) -> List[str]:
    """What the run contradicts of what the configuration file states."""
    out = _lf.shape_mismatch(config, params.actor_params["params"])
    leaf_dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    if leaf_dtypes != [config["parameter_dtype"]]:
        out.append(f"parameters are {leaf_dtypes}, stated {config['parameter_dtype']}")

    d, e, held = int(config["hidden_size"]), int(config["router_experts"]), int(config["n_routed_experts"])
    width = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
    cache = jax.eval_shape(lambda: nets["init_cache"](tokens.shape[0]))
    rows = [tuple(x.shape[1:]) for x in jax.tree.leaves(cache) if x.ndim == 3]
    layers = int(config["num_hidden_layers"])
    if rows != [(int(config["rollout_length"]), width)] * layers:
        out.append(f"the decode carry holds rows {rows}, stated {layers} latent caches of rows {width} wide")
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
check_after = _lf.check_after
