"""The plain reference of `ppo_laguna_xs2_ep32_share` and what `correct` holds
such a run to. The forward and the loss below are the benchmark's own copy of
stoix_tpu/reference/laguna.py (kept equal by tests/test_laguna_ppo.py): the
published Laguna-XS.2 (`laguna`) layers in straightforward float32
`jax.numpy` at the highest matmul precision over whole sequences — attention
as an explicit [T, T] score matrix under the causal or the BANDED mask, two
rotations (plain over the whole head | YaRN over its first half) from the
configuration's own numbers, one sigmoid gate a query head, the router's
choice over all 256 experts, the experts as a loop over the held experts on
all tokens, the shared expert beside them; no kernel, no cache, no ring, no
sort — reading the weights out of the program's parameter tree by name and
sharing no code with the program. The readings of what the published config
leaves open and the departures from it are listed in that file's header.

The system is `ff_lm_ppo` and the share is a held one, as
`ppo_lfm2_moe_ep4_share`'s, `ppo_kanana2_moe_ep8_share`'s and
`ppo_ling3_flash_ep64_share`'s: what a window is, what the rollout stores, how
the update is replayed, `expert_bias` to the bit and the held share's counters
are those configurations'. This file loads a PRIVATE copy of
references/ppo_lfm2.py (`loader.load_reference`: a module object of its own,
which loads its own private copy of references/ppo_olmoe.py) and gives it this
configuration's `forward`, `loss_sums`, `loss_of_sums`, `expected_shapes` and
`stated_mismatches`; `check_before` is that copy's, and `check_after` is that
copy's with one more reading. What differs is here: the forward (the decode
it is compared with goes through three RINGS of 512 rows and two growing
caches at all 32 slots and ALL 1,024 positions, so past the wrap; the
teacher-forced pass it is compared with is the banded and the causal flash
kernel pair), and what the configuration file states: the `laguna` keys, a
layer's own head count in `wq`, `wo` and `wg`, the shared expert's three
leaves, the untied head, the carry's two kinds of rows.
`check_after` runs on the chip, outside the timed window, on WHAT WAS TIMED.
Logits are compared, never sampled tokens.

Tolerances are in the configuration file (`reference.*_tol`) with their
reasons; the readings they were set from are in PERF.md section 6 (PR 44).
Every run also makes two further readings and prints them with the limits
that refuse each (`health.reference.lower_precision`,
`health.reference.window_ignored`, `health.reference.refused_by`): the same
reference with bfloat16 parameters and activations, and the same reference in
float32 with the window IGNORED (every layer causal), each against itself as
stated. Both have to come out as not correct; a window-ignored reading that
no limit refuses makes the run itself not correct (the comparison could then
not tell a window layer from a full one).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import loader

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


# --------------------------------------------------------------------------- #
# What `correct` holds a run to
# --------------------------------------------------------------------------- #


def _kept(config: Dict[str, Any], key: str) -> List[Any]:
    return list(config[key])[:int(config["num_hidden_layers"])]


def expected_shapes(config: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """The parameter tree the configuration file states, leaf by leaf: a
    layer's `wq`, `wo` and `wg` at ITS number of query heads."""
    d, f, fm = int(config["hidden_size"]), int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    kv_heads, hd = int(config["num_key_value_heads"]), int(config["head_dim"])
    held, e = int(config["num_experts"]), int(config["router_experts"])
    shared = int(config["shared_expert_intermediate_size"])
    dense = {"w1": (d, f), "w3": (d, f), "w2": (f, d)}
    routed = {
        "router": (d, e), "expert_bias": (e,), "gate": (held, d, fm), "up": (held, d, fm),
        "down": (held, fm, d), "shared/w1": (d, shared), "shared/w3": (d, shared), "shared/w2": (shared, d),
    }
    vocab = int(config["vocab_size"])
    want = {"embed": (vocab, d), "final_norm": (d,), "lm_head": (d, vocab)}  # untied
    layers = zip(_kept(config, "num_attention_heads_per_layer"), _kept(config, "mlp_layer_types"))
    for i, (heads, feed_forward) in enumerate(layers):
        mixer = {
            "wq": (d, int(heads) * hd), "wk": (d, kv_heads * hd), "wv": (d, kv_heads * hd),
            "wo": (int(heads) * hd, d), "q_norm": (hd,), "k_norm": (hd,),
        }
        if config.get("gating"):
            mixer["wg"] = (d, int(heads))
        ffn = dense if feed_forward == "dense" else routed
        want.update({f"layer_{i}/operator_norm": (d,), f"layer_{i}/ffn_norm": (d,)})
        want.update({f"layer_{i}/mixer/{name}": shape for name, shape in mixer.items()})
        want.update({f"layer_{i}/ffn/{name}": shape for name, shape in ffn.items()})
    return want


def expected_carry(config: Dict[str, Any], batch: int) -> List[Tuple[int, ...]]:
    """The decode carry's leaves the configuration file states, in layer
    order: keys and values of `rollout_length` rows a full layer, of
    `sliding_window` rows (the ring) a window layer."""
    row = (batch, int(config["num_key_value_heads"]), int(config["head_dim"]))
    rows = {"full_attention": int(config["rollout_length"]), "sliding_attention": int(config["sliding_window"])}
    return [(rows[kind],) + row for kind in _kept(config, "layer_types") for _ in ("k", "v")]


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

_update_errors = _lf.update_errors


def update_errors(
    before: Any, got: Any, want: Any, step_sizes: Tuple[float, float]
) -> Tuple[Dict[str, float], Dict[str, List[float]]]:
    """references/ppo_lfm2.py's leaf-by-leaf parameter change, with a leaf of
    ONE number (the critic's bias) read as |change_got - change_want| over
    one Adam step's size, `step_sizes` = the (actor's, critic's) learning
    rate, which is about what a step moves a number whatever its gradient's
    size. Its change over 8 steps of alternating sign is a cancellation's
    remainder (8e-7 to 4e-5 where a step is 1e-4), so the ratio to that
    remainder read 0.009 to 2.0 on nine sound runs of this cell (PERF.md
    section 6, PR 44) and no limit between a reading and 1 holds it; against a
    step the same nine runs read 0.002 to 0.042, a step missed or taken twice
    reads 1, and the leaf stays under the worst leaf's limit. `leaves` keeps
    what ppo_lfm2.py printed for it (its change's size, the ratio to it)."""
    update, leaves = _update_errors(before, got, want)
    worst = 0.0
    for side, trees in enumerate(zip(before, got, want)):
        named = [jax.tree_util.tree_leaves_with_path(tree) for tree in trees]
        for (path, was), (_, now), (_, wanted) in zip(*named):
            name = ("critic/" if side else "actor/") + "/".join(
                str(k.key) for k in path if str(k.key) != "params"
            )
            if name not in leaves:  # `expert_bias`: held to the bit elsewhere
                continue
            error = leaves[name][1]
            if np.size(was) == 1:
                apart = float(np.abs(np.asarray(now, np.float64) - np.asarray(wanted, np.float64)).sum())
                error = apart / step_sizes[side] if step_sizes[side] > 0.0 else float("inf")
            worst = max(worst, error if np.isfinite(error) else float("inf"))
    update["worst_leaf"] = worst
    return update, leaves


_make_replay = _lm.make_replay


def _one_at_a_time(*args: Any) -> Tuple[Any, Any, Any]:
    """references/ppo_olmoe.py's replay programs, with each part's gradient
    WAITED FOR before the next is enqueued (as references/ppo_ling3.py): beside
    parameters, the gradient sum and Adam's two moments (5.8 GiB here) one
    `add_gradient` holds a sequence's activations of its own, and two enqueued
    at once would hold them twice."""
    sums, add_gradient, step = _make_replay(*args)
    return sums, lambda *operands: jax.block_until_ready(add_gradient(*operands)), step


_lm.make_replay = _one_at_a_time


class _JaxWith:
    """`jax` as the private copy of references/ppo_olmoe.py sees it while it
    builds its two stand-in programs: `jit` with the XLA options the run's
    learner was compiled with, where its network's yaml names any
    (drivers/anakin_laguna.py hands them over; references/ppo_ling3.py says
    why a stand-in has to be compiled as the learner was)."""

    def __init__(self, options: Dict[str, Any]) -> None:
        self._options = options

    def __getattr__(self, name: str) -> Any:
        return getattr(jax, name)

    def jit(self, fn: Any, **kwargs: Any) -> Any:
        return jax.jit(fn, compiler_options=dict(self._options), **kwargs)


# The limits that tell one reading of the forward from another: the errors of
# `compare_outputs` and of the stored record, each beside its key of
# `reference`.
_LIMITS = {
    "logits_max": "max_tol", "values_max": "max_tol", "logits_rms": "logits_rms_tol",
    "values_rms": "values_rms_tol", "expert_set_disagreement": "expert_set_tol",
    "record_log_prob_rms": "log_prob_rms_tol", "record_log_prob_max": "log_prob_max_tol",
    "record_values_max": "max_tol", "record_values_rms": "values_rms_tol",
}


def refused_by(reading: Dict[str, float], ref: Dict[str, Any]) -> List[str]:
    """The limits of the configuration file that a second reading's errors
    pass: what makes that reading not correct."""
    return sorted(
        name for name, limit in _LIMITS.items()
        if name in reading and not (float(reading[name]) <= float(ref[limit]))
    )


def check_after(ctx: Any) -> Dict[str, Tuple[float, float]]:
    """references/ppo_lfm2.py's comparison of the timed window, its two
    stand-in programs compiled as the learner was (`_JaxWith`), and beside
    its bfloat16 reading one more: the reference with the window ignored,
    against the reference as stated, on the same sampled sequences."""
    seen: Dict[str, Any] = {}
    window, outputs_of = _lm.timed_window, _lm.program_outputs
    options = (ctx.networks or {}).get("compiler_options")

    def timed_window(nets: Dict[str, Any]) -> Any:
        before, after = window(nets)
        seen["params"] = before["params"]
        return before, after

    def program_outputs(nets: Dict[str, Any], params: Any, tokens: jax.Array, rows: Any) -> Any:
        seen["tokens"] = tokens[jnp.asarray(rows)]
        _lm.jax = _JaxWith(options) if options else jax
        try:
            return outputs_of(nets, params, tokens, rows)
        finally:
            _lm.jax = jax

    hyper = (ctx.networks or {}).get("hyper") or {}
    _lm.timed_window, _lm.program_outputs = timed_window, program_outputs
    _lf.update_errors = functools.partial(
        update_errors, step_sizes=(hyper.get("actor_lr", 0.0), hyper.get("critic_lr", 0.0))
    )
    try:
        errors = _lf.check_after(ctx)
    finally:
        _lm.timed_window, _lm.program_outputs = window, outputs_of
        _lf.update_errors = _update_errors
    if "tokens" not in seen or "reference" not in ctx.health:
        return errors
    config = ctx.cell.config
    ref, health = config["reference"], ctx.health["reference"]
    params = jax.device_put(seen["params"])
    run = lambda spec: jax.jit(functools.partial(forward, spec=spec))(*params, seen["tokens"])
    want, causal = run(config), run({**config, "sliding_window": None})
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    top_k = int(config["num_experts_per_tok"])
    health["window_ignored"] = _lf.compare_outputs({**causal, "pairs_per_token": top_k}, want, config)
    health["refused_by"] = {
        "lower_precision": refused_by(health["lower_precision"], ref),
        "window_ignored": refused_by(health["window_ignored"], ref),
    }
    if not health["refused_by"]["window_ignored"]:
        ctx.problems.append(
            "the reference with its window ignored passes every limit: the comparison cannot "
            "tell a window layer from a full one"
        )
    return errors
