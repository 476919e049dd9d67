"""The plain reference of `ppo_mellum2_moe_ep8_share` and what `correct` holds
such a run to. The forward and the loss below are the benchmark's own copy of
stoix_tpu/reference/mellum2.py (kept equal by tests/test_mellum2_ppo.py): the
published Mellum2-12B-A2.5B (`mellum`) layers in straightforward float32
`jax.numpy` at the highest matmul precision over the whole sequence [prefix ;
response] in ONE forward — attention as an explicit score matrix under the
causal or the BANDED mask (a block of queries at a time: memory alone), the
whole-head rotation plain on the window layers and under YaRN on the full
ones from the configuration's own numbers, the softmax router's choice over
all 64 experts, the experts as a loop over the held experts on all tokens; no
kernel, no cache, no ring, no prefill, no sort — reading the weights out of
the program's parameter tree by name and sharing no code with the program.
The readings of what the published config leaves open and the departures from
it are listed in that file's header.

The system is `ff_lm_ppo` WITH A PROMPT: the rollout prefills a seeded prefix
of `prompt_length` tokens into three rings and one growing cache by one
teacher-forced pass and decodes `rollout_length` tokens from there; the update
is teacher-forced over [prefix ; response] with the loss on the response. What
a window is, the token task's verifier, GAE as a float64 loop, the clip and
Adam, the stored record's comparison and the replay's three programs are
`ppo_olmoe_1layer_tokens`': this file loads a PRIVATE copy of
references/ppo_olmoe.py (`loader.load_reference`: a module object of its own)
and gives it this configuration's `loss_sums`. What differs is here:
  the sequence: the prompt the window's rollout was given rides out with its
      record (`sequence_prompt`), and every comparison is made at the response
      positions P .. P + G - 1 of the reference's forward over [prefix ;
      response] — the stored log-probs and values of the TIMED rollout, which
      decoded through the PREFILLED rings and cache; the teacher-forced entry
      point with the head on the response; the prefill and the decode as one
      program of their own (prefill at every slot, then G steps at every slot);
  the expert sets over ALL 64 experts of the router, at the response
      positions for both entry points and at the prefix positions for the
      teacher-forced pass and for the prefill;
  the replay: [prefix ; response] through `jax.grad` of the reference loss a
      sequence at a time, the router's sums over every position, the loss
      over the response's; its OLD VALUES (the clipped value loss's `value`,
      GAE's targets and advantages) are the reference's own forward's at the
      window's first parameters, not the rollout's stored ones
      (`reference.old_values_why`: the configuration file says why — the
      two precisions' values differ by an offset that a sequence's 512
      positions share, which a replay fed the stored values turns into a
      coherent gradient the program does not have); the stored values are
      held to the reference's by the record's comparison;
  the counters of a held share: the pairs a token that landed on the held
      experts in the timed prefill, rollout and update, the held experts'
      load, nothing dropped in any of the three;
  what the configuration file states: the `mellum` keys, one head count, no
      `expert_bias` leaf and no shared expert, the untied head, the carry's
      two kinds of rows (P + G of a full layer, `sliding_window` of a ring).
`check_after` runs on the chip, outside the timed window, on WHAT WAS TIMED.
Logits are compared, never sampled tokens.

Tolerances are in the configuration file (`reference.*_tol`) with their
reasons; the readings they were set from are in PERF.md section 6 (PR 47).
Every run also makes three further readings and prints them with the limits
that refuse them (`health.reference.lower_precision`, `.window_ignored`,
`.prefix_dropped`, `.refused_by`): the same reference with bfloat16
parameters and activations, the same reference in float32 with the window
IGNORED (every layer causal), and the same reference on the response ALONE
(the prefix dropped: a decode from empty), each against itself as stated. All
three have to come out as not correct: a reading of the three that no limit
refuses makes the run itself not correct (the comparison could then not tell
float32 from the precision below it, a window layer from a full one, or a
prefilled state from an empty one).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import loader
from benchmarks.harness import reference as compare

_HIGHEST = "highest"
# Sequences a reference forward takes at once where it is asked for many (memory
# alone: its scores are made `attention_query_block` queries at a time, [4, 32,
# 512, 3584] = 940 MB a block at the cell's sizes).
_FORWARD_SEQUENCES = 4


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


# --------------------------------------------------------------------------- #
# What `correct` holds a run to
# --------------------------------------------------------------------------- #

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The token task's verifier, float64 GAE, clip and Adam, the timed window, the
# record's comparison and the replay's three programs: a private copy of the
# OLMoE configuration's file, given this configuration's loss.
_lm = loader.load_reference("ppo_olmoe", _ROOT)
_lm.loss_sums = loss_sums

check_before = _lm.check_before  # the learner's GAE against a float64 loop


def _kept(config: Dict[str, Any], key: str) -> List[Any]:
    return list(config[key])[:int(config["num_hidden_layers"])]


def expected_shapes(config: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """The parameter tree the configuration file states, leaf by leaf: every
    layer routed, no `expert_bias`, no shared expert, no gate."""
    d, fm = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    heads, kv_heads, hd = int(config["num_attention_heads"]), int(config["num_key_value_heads"]), int(config["head_dim"])
    held, e, vocab = int(config["num_experts"]), int(config["router_experts"]), int(config["vocab_size"])
    layer = {
        "operator_norm": (d,), "ffn_norm": (d,),
        "mixer/wq": (d, heads * hd), "mixer/wk": (d, kv_heads * hd), "mixer/wv": (d, kv_heads * hd),
        "mixer/wo": (heads * hd, d), "mixer/q_norm": (hd,), "mixer/k_norm": (hd,),
        "ffn/router": (d, e), "ffn/gate": (held, d, fm), "ffn/up": (held, d, fm), "ffn/down": (held, fm, d),
    }
    want = {"embed": (vocab, d), "final_norm": (d,), "lm_head": (d, vocab)}  # untied
    for i, feed_forward in enumerate(_kept(config, "mlp_layer_types")):
        if feed_forward != "sparse":
            raise ValueError(f"mlp_layer_types[{i}] is {feed_forward!r}: every layer of this model is routed")
        want.update({f"layer_{i}/{name}": shape for name, shape in layer.items()})
    return want


def shape_mismatch(config: Dict[str, Any], tree: Dict[str, Any]) -> List[str]:
    """Where the parameter tree is not the one the configuration file states."""
    want = expected_shapes(config)
    got = {
        "/".join(str(k.key) for k in path): tuple(leaf.shape)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }
    if got == want:
        return []
    wrong = sorted(k for k in set(got) | set(want) if got.get(k) != want.get(k))
    return [f"parameter shapes differ from the stated layers and widths at {wrong[:6]}: "
            f"{[got.get(k) for k in wrong[:6]]} vs {[want.get(k) for k in wrong[:6]]}"]


def expected_carry(config: Dict[str, Any], batch: int) -> List[Tuple[int, ...]]:
    """The decode carry's leaves the configuration file states, in layer
    order: keys and values of `prompt_length` + `rollout_length` rows a full
    layer, of `sliding_window` rows (the ring) a window layer."""
    row = (batch, int(config["num_key_value_heads"]), int(config["head_dim"]))
    rows = {
        "full_attention": int(config["prompt_length"]) + int(config["rollout_length"]),
        "sliding_attention": int(config["sliding_window"]),
    }
    return [(rows[kind],) + row for kind in _kept(config, "layer_types") for _ in ("k", "v")]


def stated_mismatches(config: Dict[str, Any], nets: Dict[str, Any], params: Any, shapes: Dict[str, Any], tokens: jax.Array) -> List[str]:
    """What the run contradicts of what the configuration file states;
    `tokens` [rows, P + G]."""
    out = shape_mismatch(config, params.actor_params["params"])
    leaf_dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    if leaf_dtypes != [config["parameter_dtype"]]:
        out.append(f"parameters are {leaf_dtypes}, stated {config['parameter_dtype']}")

    d, e, held = int(config["hidden_size"]), int(config["router_experts"]), int(config["num_experts"])
    prompt = int(config["prompt_length"])
    cache = jax.eval_shape(lambda: nets["init_cache"](tokens.shape[0]))
    carry = [tuple(x.shape) for x in jax.tree.leaves(cache) if x.ndim >= 3]
    if carry != expected_carry(config, tokens.shape[0]):
        out.append(f"the decode carry holds {carry}, stated {expected_carry(config, tokens.shape[0])}")
    if any(str(x.dtype) != config["parameter_dtype"] for x in jax.tree.leaves(cache) if x.ndim >= 3):
        out.append(f"the decode carry is not {config['parameter_dtype']}")
    programs = {
        "forward": _lm.matmuls_of(nets["forward"], params.actor_params, tokens),
        "prefill": _lm.matmuls_of(nets["prefill"], params.actor_params, cache, tokens[:, :prompt]),
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
    for key in ("rollout_length", "prompt_length", "epochs", "num_minibatches"):
        if int(shapes.get(key, -1)) != int(config[key]):
            out.append(f"{key} resolved to {shapes.get(key)}, stated {config[key]}")
    return out


def program_outputs(
    nets: Dict[str, Any], params: Any, tokens: jax.Array, prompt: int, rows: np.ndarray
) -> Dict[str, Dict[str, jax.Array]]:
    """The program's entry points as programs of their own, on `tokens` [E, P
    + G] (the prompts a rollout was given, then the inputs it generated):
    teacher-forced on the sequences `rows` with the head on the response; and
    the prefill of every sequence's prompt followed by G cached steps at
    EVERY slot, as the rollout runs them (teacher-forced INPUTS, so that all
    are compared with one reference forward), of which the sampled rows'
    outputs are kept. Both give logits and values [rows, G, ..] and the
    expert sets of the response's positions (`expert_index`) and of the
    prefix's (`prefix_expert_index`), each [L, rows * positions, k] in the
    teacher-forced token order."""
    rows = jnp.asarray(rows)
    response = tokens.shape[1] - prompt

    def teacher_forced(params, tokens):
        logits, hidden, stats = nets["forward"](params.actor_params, tokens, response)
        sets = functools.partial(_positions, stats["expert_index"], tokens.shape[0])
        return {
            "logits": logits, "values": nets["value"](params.critic_params, hidden),
            "expert_index": sets(prompt, prompt + response), "prefix_expert_index": sets(0, prompt),
            "pairs_per_token": jnp.sum(stats["expert_count"]) / (stats["expert_count"].shape[0] * tokens.size),
        }

    def decoded(params, tokens):
        cache, stats = nets["prefill"](
            params.actor_params, nets["init_cache"](tokens.shape[0]), tokens[:, :prompt]
        )
        layers, top_k = stats["expert_index"].shape[0], stats["expert_index"].shape[-1]
        prefilled = stats["expert_index"].reshape(layers, tokens.shape[0], prompt, top_k)[:, rows]

        def one(cache, token):
            logits, hidden, cache, stats = nets["step"](params.actor_params, cache, token)
            out = {
                "logits": logits[rows], "values": nets["value"](params.critic_params, hidden)[rows],
                "expert_index": stats["expert_index"][:, rows],
                "pairs": jnp.sum(stats["expert_count"]),
            }
            return cache, out

        _, out = jax.lax.scan(one, cache, tokens[:, prompt:].T)
        return {
            "logits": jnp.swapaxes(out["logits"], 0, 1),  # [rows, G, V]
            "values": jnp.swapaxes(out["values"], 0, 1),
            # [G, L, rows, k] -> [L, rows*G, k], the teacher-forced token order
            "expert_index": jnp.transpose(out["expert_index"], (1, 2, 0, 3)).reshape(layers, -1, top_k),
            "pairs_per_token": jnp.sum(out["pairs"]) / (layers * tokens.shape[0] * response),
            "prefix_expert_index": prefilled.reshape(layers, -1, top_k),  # the prefill's
            "prefill_pairs_per_token": jnp.sum(stats["expert_count"]) / (layers * tokens.shape[0] * prompt),
        }

    return {
        "tf": jax.jit(teacher_forced)(params, tokens[rows]),
        "decode": jax.jit(decoded)(params, tokens),
    }


def _positions(index: jax.Array, sequences: int, first: int, last: int) -> jax.Array:
    """Expert sets [L, sequences * T, k] in token order -> those of the
    positions first .. last - 1 of every sequence, [L, sequences * (last -
    first), k]."""
    layers, _, top_k = index.shape
    return index.reshape(layers, sequences, -1, top_k)[:, :, first:last].reshape(layers, -1, top_k)


def compare_outputs(got: Dict[str, jax.Array], want: Dict[str, jax.Array], config: Dict[str, Any]) -> Dict[str, float]:
    """Errors of one entry point against the reference forward, both at the
    response's positions; the expert sets are compared over ALL the router's
    experts, not the held ones."""
    return _lm.compare_outputs(got, want, {**config, "num_experts": int(config["router_experts"])})


def _disagreement(got: jax.Array, want: jax.Array, config: Dict[str, Any]) -> float:
    return float(1.0 - _lm._set_agreement(got, want, int(config["router_experts"]))[0])


# The limits that tell one reading of the forward from another: the errors of
# `compare_outputs` and of the stored record, each beside its key of
# `reference`.
_LIMITS = {
    "logits_max": "max_tol", "values_max": "max_tol", "logits_rms": "logits_rms_tol",
    "values_rms": "values_rms_tol", "expert_set_disagreement": "expert_set_tol",
    "record_log_prob_rms": "log_prob_rms_tol", "record_log_prob_max": "log_prob_max_tol",
    "record_values_max": "max_tol", "record_values_rms": "values_rms_tol",
    "all_expert_set_disagreement": "all_expert_set_tol",
}


def refused_by(reading: Dict[str, float], ref: Dict[str, Any]) -> List[str]:
    """The limits of the configuration file that a further reading's errors
    pass: what makes that reading not correct."""
    return sorted(
        name for name, limit in _LIMITS.items()
        if name in reading and not math.isnan(float(reading[name]))
        and not (float(reading[name]) <= float(ref[limit]))
    )


def sampled_errors(
    ctx: Any, before: Dict[str, Any], rollout: Dict[str, np.ndarray], rows: np.ndarray
) -> Tuple[Dict[str, Tuple[float, float]], Dict[str, Dict[str, float]], Dict[str, float]]:
    """On the sequences `rows` of the window's rollout and the parameters it
    started from: the entry points as programs (teacher-forced with the head
    on the response; the prefill, then the decode through the prefilled rings
    and cache at EVERY slot), and what the timed rollout stored, against the
    reference forward over [prefix ; response] -> (errors with their
    tolerances, the three further readings' errors, quantiles of what the two
    compilations of the decode differ by)."""
    config, nets = ctx.cell.config, ctx.networks
    ref, top_k = config["reference"], int(config["num_experts_per_tok"])
    prompt = rollout["prompt"].shape[0]
    response = rollout["tokens"].shape[0]
    tokens = jnp.asarray(np.concatenate([rollout["prompt"], rollout["tokens"]], axis=0).T)  # [E, P + G]
    params = jax.device_put(type(nets["state"].params)(*before["params"]))
    ctx.problems.extend(stated_mismatches(config, nets, params, ctx.shapes, tokens[rows]))
    outputs = program_outputs(nets, params, tokens, prompt, rows)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    reference_params = jax.device_put(before["params"])

    def run(spec: Dict[str, Any], dtype: Any = jnp.float32, first: int = 0) -> Dict[str, jax.Array]:
        """The reference on the sampled sequences from position `first` on,
        logits and values of the response, expert sets by position; a few
        sequences at a time."""
        one = jax.jit(functools.partial(forward, spec=spec, dtype=dtype, response=response))
        parts = []
        for start in range(0, len(rows), _FORWARD_SEQUENCES):
            some = rows[start:start + _FORWARD_SEQUENCES]
            out = one(*reference_params, tokens[some][:, first:])
            sets = functools.partial(_positions, out["expert_index"], len(some))
            parts.append({
                "logits": out["logits"], "values": out["values"],
                "expert_index": sets(prompt - first, prompt - first + response),
                "prefix_expert_index": sets(0, prompt - first),
            })
        # (expert sets are [L, sequences * positions, k]: sequences along axis 1)
        return {
            name: jnp.concatenate([part[name] for part in parts], axis=1 if "index" in name else 0)
            for name in parts[0]
        }

    def all_sets(outputs: Dict[str, jax.Array]) -> jax.Array:
        """A reading's expert sets at the prefix's and the response's
        positions together, [L, rows * (P + G), k] (prefix first: the two
        sides of a comparison in the same order)."""
        return jnp.concatenate([outputs["prefix_expert_index"], outputs["expert_index"]], axis=1)

    want, low = run(config), run(config, jnp.bfloat16)
    causal, empty = run({**config, "sliding_window": None}), run(config, first=prompt)
    for leaf in jax.tree.leaves(reference_params):
        leaf.delete()
    tolerances = {
        "logits_max": float(ref["max_tol"]), "values_max": float(ref["max_tol"]),
        "logits_rms": float(ref["logits_rms_tol"]), "values_rms": float(ref["values_rms_tol"]),
        "expert_set_disagreement": float(ref["expert_set_tol"]),
        "dropped_pairs": float(ref["dropped_tol"]),
        "log_prob_rms": float(ref["log_prob_rms_tol"]), "log_prob_max": float(ref["log_prob_max_tol"]),
        "differs_from_decode": float(ref["rollout_decode_tol"]),
    }
    errors = {
        f"{entry}_{name}": (error, tolerances[name])
        for entry, got in outputs.items()
        for name, error in compare_outputs(got, want, config).items()
    }
    # The expert sets at EVERY position, the prefix's 3,072 and the response's
    # 512 a sequence (seven times the pairs, so a third of the spread from
    # seed to seed): the teacher-forced pass's, and the prefill's with the
    # decode's; `<entry>_expert_set_disagreement` above is the response's
    # alone, where logits and values are compared. Nothing dropped by the prefill.
    for entry, got in outputs.items():
        errors[f"{entry}_all_expert_set_disagreement"] = (
            _disagreement(all_sets(got), all_sets(want), config), float(ref["all_expert_set_tol"])
        )
    errors["prefill_dropped_pairs"] = (
        abs(float(outputs["decode"]["prefill_pairs_per_token"]) - top_k), tolerances["dropped_pairs"]
    )
    agreeing = lambda got: _lm._set_agreement(
        got["expert_index"], want["expert_index"], int(config["router_experts"])
    )[1].reshape(want["values"].shape)
    actions = jnp.asarray(rollout["action"].T[rows])
    record = {"log_prob": rollout["log_prob"].T[rows], "value": rollout["value"].T[rows]}
    stored = _lm.compare_record(record, outputs["decode"], want, actions, agreeing(outputs["decode"]))
    errors.update({f"rollout_{name}": (error, tolerances[name]) for name, error in stored.items()})

    def further(reading: Dict[str, jax.Array]) -> Dict[str, float]:
        """A further reading of the reference against the reference as
        stated: its outputs, and its own record of the stored actions. Logits
        and values are compared on the tokens whose expert sets agree in every
        layer; a reading so far off that no token's do (`agreeing_tokens` 0)
        has none to compare, reads NaN there and is refused by its expert
        sets."""
        its_record = {"log_prob": _lm._log_prob_of(reading["logits"], actions), "value": reading["values"]}
        out = {
            **compare_outputs({**reading, "pairs_per_token": top_k}, want, config),
            **{
                f"record_{k}": v
                for k, v in _lm.compare_record(its_record, reading, want, actions, agreeing(reading)).items()
            },
            "agreeing_tokens": float(jnp.mean(agreeing(reading))),
        }
        if reading["prefix_expert_index"].shape[1]:  # (the reading with the prefix dropped has none)
            out["all_expert_set_disagreement"] = _disagreement(all_sets(reading), all_sets(want), config)
        return out

    readings = {"lower_precision": further(low), "window_ignored": further(causal), "prefix_dropped": further(empty)}
    gap = {
        f"{name}_{label}": float(jnp.quantile(x, q))
        for name, x in _lm._compilation_gap(record, outputs["decode"], actions).items()
        for label, q in (("p50", 0.5), ("p99", 0.99), ("p999", 0.999), ("max", 1.0))
    }
    return errors, readings, gap


def reference_values(
    before: Dict[str, Any], rollout: Dict[str, np.ndarray], spec: Dict[str, Any],
    dtype: Any = jnp.float32,
) -> np.ndarray:
    """The reference's OWN values [G, E] of the response's positions at the
    parameters the window started from: its one forward over [prefix ;
    response], a few sequences at a time."""
    tokens = np.concatenate([rollout["prompt"], rollout["tokens"]], axis=0).T  # [E, P + G]
    response = rollout["tokens"].shape[0]
    params = jax.device_put(before["params"])
    one = jax.jit(lambda actor, critic, some: forward(actor, critic, some, spec, dtype, response)["values"])
    values = [
        np.asarray(one(*params, jnp.asarray(tokens[i:i + _FORWARD_SEQUENCES])))
        for i in range(0, len(tokens), _FORWARD_SEQUENCES)
    ]
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    return np.concatenate(values).T


def replay_update(
    before: Dict[str, Any], rollout: Dict[str, np.ndarray], spec: Dict[str, Any],
    hyper: Dict[str, Any], ref: Dict[str, Any], shards: int, dtype: Any = jnp.float32,
) -> Tuple[Any, Dict[str, float]]:
    """references/ppo_olmoe.py's replay on sequences that start with a prompt:
    the reference's own update from the state the timed window started from
    and the rollout it stored (`rollout` leaves [G, E]: tokens, action,
    log_prob, value, reward; `prompt` [P, E]) -> (parameters afterwards on the
    host, the loss parts as the learner logs them: means over shards and
    minibatches, and `old_values` [G, E] as the replay took them). A
    minibatch's sequence is [prefix ; tokens], its loss the response's; the
    old values are `reference_values` at `dtype`. Shard s holds the sequences [s*E/S, (s+1)*E/S), standardises
    its own advantages, shuffles with its own key; gradients are means over
    shards; each part's gradient is waited for before the next is enqueued
    (beside parameters, the gradient sum and Adam's two moments one part
    holds a sequence's activations, and two enqueued at once would hold them
    twice)."""
    length, envs = rollout["action"].shape
    per_shard = envs // shards
    minibatches, epochs = int(hyper["num_minibatches"]), int(hyper["epochs"])
    size = per_shard // minibatches  # sequences of one shard in a minibatch
    part = min(int(ref["replay_part_sequences"]), size)
    while size % part:
        part -= 1
    # The reference's own, not the rollout's stored ones (`reference.old_values_why`).
    old_values = reference_values(before, rollout, spec, dtype)
    data: List[Dict[str, np.ndarray]] = []
    for s in range(shards):
        cols = slice(s * per_shard, (s + 1) * per_shard)
        advantage, target = _lm.gae(
            rollout["reward"][:, cols], old_values[:, cols], hyper["gamma"],
            hyper["gae_lambda"], hyper["standardize_advantages"],
        )
        data.append({
            "tokens": np.concatenate([rollout["prompt"][:, cols], rollout["tokens"][:, cols]], axis=0).T,
            "action": rollout["action"][:, cols].T,
            "log_prob": rollout["log_prob"][:, cols].T, "value": old_values[:, cols].T,
            "advantage": advantage.T, "target": target.T,
        })
    keys = [_lm.shuffle_keys(jnp.asarray(before["key"][s]), length, epochs) for s in range(shards)]

    sums, add_gradient, step = _lm.make_replay(spec, hyper, ref["adam"], dtype)
    params = jax.device_put(before["params"])
    moments = jax.device_put(before["moments"])
    tokens = size * length  # the RESPONSE tokens of one shard's minibatch
    logged: List[Dict[str, float]] = []
    norms: List[List[float]] = []
    for epoch in range(epochs):
        orders = [np.asarray(jax.random.permutation(keys[s][epoch], per_shard)) for s in range(shards)]
        for m in range(minibatches):
            picked = [orders[s][m * size:(m + 1) * size] for s in range(shards)]
            # [shards * parts a shard, sequences a part, positions]
            parts = {
                name: jnp.asarray(np.concatenate([
                    data[s][name][picked[s]].reshape(size // part, part, -1) for s in range(shards)
                ]))
                for name in data[0]
            }
            part_sums = sums(params, parts)
            of_shard = lambda s: jax.tree.map(
                lambda x: jnp.sum(x[s * (size // part):(s + 1) * (size // part)], axis=0), part_sums
            )
            loss = lambda shard_sums: loss_of_sums(shard_sums, tokens, spec, hyper)
            shard_sums = [of_shard(s) for s in range(shards)]
            logged.append({
                k: float(np.mean([float(loss(x)[1][k]) for x in shard_sums]))
                for k in loss(shard_sums[0])[1]
            })
            # d(mean over shards of the loss) / d(each part's sums)
            weights = [jax.grad(lambda x: loss(x)[0] / shards)(x) for x in shard_sums]
            grads = jax.tree.map(jnp.zeros_like, params)
            for index in range(shards * (size // part)):
                one = jax.tree.map(lambda x: x[index], parts)
                grads = jax.block_until_ready(
                    add_gradient(grads, params, one, weights[index // (size // part)])
                )
            # (what the clip sees: the gradient's global norm, the trunk's and the value head's)
            norms.append([float(jnp.sqrt(sum(jnp.vdot(g, g) for g in jax.tree.leaves(side)))) for side in grads])
            params, moments = step(params, moments, grads)
    after = jax.device_get(params)
    for leaf in jax.tree.leaves((params, moments)):
        leaf.delete()
    means = {k: float(np.mean([rec[k] for rec in logged])) for k in logged[0]}
    return after, {**means, "gradient_norms": norms, "old_values": old_values}


def update_errors(
    before: Any, got: Any, want: Any, step_sizes: Tuple[float, float]
) -> Tuple[Dict[str, float], Dict[str, List[float]]]:
    """references/ppo_olmoe.py's leaf-by-leaf parameter change, with a leaf of
    ONE number (the critic's bias) read as |change_got - change_want| over
    one Adam step's size, `step_sizes` = the (actor's, critic's) learning
    rate, which is about what a step moves a number whatever its gradient's
    size (references/ppo_laguna.py says why: its change over 8 steps of
    alternating sign is a cancellation's remainder, and no limit holds the
    ratio to that; against a step a sound run reads under 0.05 and a step
    missed or taken twice reads 1). `leaves` keeps what ppo_olmoe.py printed
    for it (its change's size, the ratio to it)."""
    update, leaves = _lm.update_errors(before, got, want)
    worst = 0.0
    for side, trees in enumerate(zip(before, got, want)):
        named = [jax.tree_util.tree_leaves_with_path(tree) for tree in trees]
        for (path, was), (_, now), (_, wanted) in zip(*named):
            name = ("critic/" if side else "actor/") + "/".join(
                str(k.key) for k in path if str(k.key) != "params"
            )
            error = leaves[name][1]
            if np.size(was) == 1:
                apart = float(np.abs(np.asarray(now, np.float64) - np.asarray(wanted, np.float64)).sum())
                error = apart / step_sizes[side] if step_sizes[side] > 0.0 else float("inf")
            worst = max(worst, error if np.isfinite(error) else float("inf"))
    update["worst_leaf"] = worst
    return update, leaves


_LOSS_PARTS = ("total_loss", "actor_loss", "value_loss", "entropy", "aux_loss")


def _by_sequence(difference: np.ndarray) -> Dict[str, List[float]]:
    """[G, E] -> each sequence's mean and RMS over its G positions."""
    return {
        "mean": np.mean(difference, axis=0).tolist(),
        "rms": np.sqrt(np.mean(np.square(difference), axis=0)).tolist(),
    }


def check_after(ctx: Any) -> Dict[str, Tuple[float, float]]:
    config, nets = ctx.cell.config, ctx.networks
    if not nets or nets.get("state") is None or nets.get("learn") is None:
        ctx.problems.append("the run's timed learner and final state were not observed")
        return {}
    ref, hyper, shards = config["reference"], nets["hyper"], int(nets["shards"])
    if int(ctx.shapes.get("updates_per_tick", 1)) != 1 or hyper["decay_learning_rates"]:
        ctx.problems.append("the reference replays one update a window at a constant learning rate")
        return {}
    before, after = _lm.timed_window(nets)
    mismatch = shape_mismatch(config, before["params"][0]["params"])
    if mismatch:  # the reference reads the stated tree by name: there is none to read
        ctx.problems.extend(mismatch)
        return {}
    if "sequence_prompt" not in after["episode"]:
        ctx.problems.append("the window's record holds no prompt: the rollout started from none")
        return {}
    rollout = _lm.stored_rollout(before, after, int(hyper["env_modulus"]))
    rollout["prompt"] = np.asarray(after["episode"]["sequence_prompt"])[0]  # [P, E]
    prompt, response = rollout["prompt"].shape[0], rollout["action"].shape[0]
    train = {k: float(np.mean(v)) for k, v in after["train"].items()}
    top_k, steps = int(config["num_experts_per_tok"]), int(hyper["epochs"]) * int(hyper["num_minibatches"])
    vocab = int(config["vocab_size"])
    errors: Dict[str, Tuple[float, float]] = {
        "rollout_returns": (
            compare.max_scaled_error(rollout["logged_return"], rollout["reward"][-1]),
            float(ref["returns_tol"]),
        ),
        # every sequence its own seeded prompt of ids of the slice
        "rollout_prompts_alike": (
            float(len(rollout["prompt"].T) - len({row.tobytes() for row in rollout["prompt"].T})), 0.0
        ),
        "rollout_prompt_outside_slice": (
            float(np.sum((rollout["prompt"] < 0) | (rollout["prompt"] >= vocab))), 0.0
        ),
        "prefill_dropped_pairs_timed": (
            abs(train["prefill_routed_pairs_per_token"] - top_k), float(ref["dropped_tol"])
        ),
        "rollout_dropped_pairs": (
            abs(train["rollout_routed_pairs_per_token"] - top_k), float(ref["dropped_tol"])
        ),
        "update_dropped_pairs": (abs(train["routed_pairs_per_token"] - top_k), float(ref["dropped_tol"])),
        "update_dropped_pairs_counted": (abs(train["dropped_pairs"]), float(ref["dropped_tol"])),
        "update_adam_steps": (
            float(max(
                abs(int(got) - int(m["count"]) - steps)
                for got, m in zip(after["count"], before["moments"])
            )), 0.0,
        ),
    }
    envs = rollout["action"].shape[1]
    rows = np.sort(np.random.default_rng(ctx.seed).choice(envs, int(ref["sample_sequences"]), replace=False))
    sampled, readings, gap = sampled_errors(ctx, before, rollout, rows)
    errors.update(sampled)

    # The update: the window's parameter change, logged loss parts and
    # counters against the reference's replay.
    replayed, logged = replay_update(before, rollout, config, hyper, ref, shards)
    update, leaves = update_errors(
        before["params"], after["params"], replayed, (hyper["actor_lr"], hyper["critic_lr"])
    )
    errors["update_params_worst_leaf"] = (update["worst_leaf"], float(ref["update_worst_leaf_tol"]))
    errors["update_params_all_leaves"] = (update["all_leaves"], float(ref["update_all_leaves_tol"]))
    for name in _LOSS_PARTS:
        limit = "value_loss_tol" if name in ("value_loss", "total_loss") else "loss_tol"
        errors[f"update_{name}"] = (compare.max_scaled_error(train[name], logged[name]), float(ref[limit]))
    for name, limit in (
        ("expert_load_max_over_mean", "expert_load_tol"), ("held_pairs_per_token", "held_pairs_tol"),
    ):
        errors[f"update_{name}"] = (compare.max_scaled_error(train[name], logged[name]), float(ref[limit]))
    # The rollout and the prefill keep no expert sets: their held pairs a
    # token, weighed by the positions each saw, are held to the update's,
    # which passes the same P + G tokens through the same router
    # teacher-forced (on parameters 0 to 8 Adam steps on).
    seen = (
        prompt * train["prefill_held_pairs_per_token"] + response * train["rollout_held_pairs_per_token"]
    ) / (prompt + response)
    errors["rollout_held_pairs_per_token"] = (
        compare.max_scaled_error(seen, logged["held_pairs_per_token"]), float(ref["rollout_held_pairs_tol"])
    )
    if ref.get("lower_precision_update"):
        low_replayed, low_logged = replay_update(before, rollout, config, hyper, ref, shards, jnp.bfloat16)
        low_update, low_leaves = _lm.update_errors(before["params"], low_replayed, replayed)
        second = readings["lower_precision"]
        second.update({f"update_params_{k}": v for k, v in low_update.items()})
        second.update({f"update_{k}": compare.max_scaled_error(low_logged[k], logged[k]) for k in _LOSS_PARTS})
        second["update_leaves"] = low_leaves
    ctx.health["reference"] = {
        "update_leaves": leaves, **readings, "stored_minus_decoded": gap, "rows": rows.tolist(),
        "reference_gradient_norms": logged["gradient_norms"],
        # the stored values less the replay's old ones, a sequence at a time: what the two
        # precisions differ by is an offset that a sequence's positions share
        "stored_minus_replayed_values": _by_sequence(rollout["value"] - logged["old_values"]),
        "refused_by": {name: refused_by(reading, ref) for name, reading in readings.items()},
        "counters": {k: train[k] for k in (
            "held_pairs_per_token", "rollout_held_pairs_per_token", "prefill_held_pairs_per_token",
            "expert_load_max_over_mean", "dropped_pairs",
        )},
    }
    for name, told in (("lower_precision", "float32 from bfloat16"),
                       ("window_ignored", "a window layer from a full one"),
                       ("prefix_dropped", "a prefilled state from an empty one")):
        if not ctx.health["reference"]["refused_by"][name]:
            ctx.problems.append(
                f"the reference's {name.replace('_', ' ')} reading passes every limit: the comparison "
                f"cannot tell {told}"
            )
    return errors
