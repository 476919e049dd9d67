"""The plain reference of `ppo_lfm2_moe_ep4_share` and what `correct` holds
such a run to. The forward and the loss below are the benchmark's own copy of
stoix_tpu/reference/lfm2.py (kept equal by tests/test_lfm2_ppo.py): the
published LFM2 layers in straightforward float32 `jax.numpy` at the highest
matmul precision over whole sequences — the short convolution as shifted
copies added up, attention as an explicit [T, T] masked softmax, the experts
as a loop over the held experts on all tokens, no cache, no tail, no sort, no
kernel — reading the weights out of the program's parameter tree by name and
sharing no code with the program. The departures from the published forward
are listed in that file's header.

The system is `ff_lm_ppo`, the one `ppo_olmoe_1layer_tokens` runs, so what a
window is, what the rollout stores and how the update is replayed are that
configuration's: this file loads a PRIVATE copy of references/ppo_olmoe.py
(`loader.load_reference`: a module object of its own) for the token task's
verifier, GAE as a float64 loop, the clip and Adam, the timed window, the
stored record's comparison and the replay of the update, and gives that copy
this configuration's `loss_sums` and `loss_of_sums` to replay with. What
differs is here: the forward, the loss's sums, what the configuration file
states, and what is compared beside:
  the expert sets over ALL 32 experts of the router, not the 8 held;
  `expert_bias`, in every routed layer, unchanged TO THE BIT by the window's
      Adam steps (it takes no gradient), and kept out of the leaf-by-leaf
      change (a leaf that does not move has no relative error);
  the counters of a held share: the pairs a token that landed on the held
      experts in the timed rollout and in the timed update, the held experts'
      load, the tokens the selection bias re-routed, nothing dropped.
`check_after` runs on the chip, outside the timed window, on WHAT WAS TIMED:
it calls the timed learner once more on the run's final state and holds what
that window produced to the reference replayed from the state it started from
(references/ppo_olmoe.py's header says how). Logits are compared, never
sampled tokens.

Tolerances are in the configuration file (`reference.*_tol`) with their
reasons; the readings they were set from are in PERF.md section 6 (PR 33).
Every run also makes the second reading — the same reference with bfloat16
parameters and activations against itself in float32, which has to come out
as not correct — and prints it (`health.reference.lower_precision`).
"""

from __future__ import annotations

import functools
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


# --------------------------------------------------------------------------- #
# What `correct` holds a run to
# --------------------------------------------------------------------------- #

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The token-policy system's window, record and replay: a private copy, given
# this configuration's loss to replay with.
_lm = loader.load_reference("ppo_olmoe", _ROOT)
_lm.loss_sums, _lm.loss_of_sums = loss_sums, loss_of_sums

check_before = _lm.check_before  # the learner's GAE against a float64 loop

_LOSS_PARTS = ("total_loss", "actor_loss", "value_loss", "entropy", "aux_loss")


def _router_width(config: Dict[str, Any]) -> int:
    return int(config.get("router_experts") or config["num_experts"])


def expected_shapes(config: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """The parameter tree the configuration file states, leaf by leaf."""
    d, f, fm = int(config["hidden_size"]), int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    heads, kv_heads, hd = int(config["num_attention_heads"]), int(config["num_key_value_heads"]), int(config["head_dim"])
    held, e, taps = int(config["num_experts"]), _router_width(config), int(config["conv_L_cache"])
    mixers = {
        "conv": {"in_proj": (d, 3 * d), "conv": (taps, d), "out_proj": (d, d)},
        "full_attention": {
            "wq": (d, heads * hd), "wk": (d, kv_heads * hd), "wv": (d, kv_heads * hd),
            "wo": (heads * hd, d), "q_norm": (hd,), "k_norm": (hd,),
        },
    }
    dense = {"w1": (d, f), "w3": (d, f), "w2": (f, d)}
    routed = {
        "router": (d, e), "expert_bias": (e,), "gate": (held, d, fm), "up": (held, d, fm),
        "down": (held, fm, d),
    }
    # No `lm_head`: the head is the embedding's transpose.
    want = {"embed": (int(config["vocab_size"]), d), "final_norm": (d,)}
    for i, kind in enumerate(list(config["layer_types"])[:int(config["num_hidden_layers"])]):
        ffn = dense if i < int(config["num_dense_layers"]) else routed
        want.update({f"layer_{i}/operator_norm": (d,), f"layer_{i}/ffn_norm": (d,)})
        want.update({f"layer_{i}/mixer/{name}": shape for name, shape in mixers[kind].items()})
        want.update({f"layer_{i}/ffn/{name}": shape for name, shape in ffn.items()})
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


def stated_mismatches(config: Dict[str, Any], nets: Dict[str, Any], params: Any, shapes: Dict[str, Any], tokens: jax.Array) -> List[str]:
    """What the run contradicts of what the configuration file states."""
    out = shape_mismatch(config, params.actor_params["params"])
    leaf_dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    if leaf_dtypes != [config["parameter_dtype"]]:
        out.append(f"parameters are {leaf_dtypes}, stated {config['parameter_dtype']}")

    d, e, held = int(config["hidden_size"]), _router_width(config), int(config["num_experts"])
    cache = jax.eval_shape(lambda: nets["init_cache"](tokens.shape[0]))
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


def compare_outputs(got: Dict[str, jax.Array], want: Dict[str, jax.Array], config: Dict[str, Any]) -> Dict[str, float]:
    """Errors of one entry point against the reference forward; the expert
    sets are compared over ALL the router's experts, not the held ones."""
    return _lm.compare_outputs(got, want, {**config, "num_experts": _router_width(config)})


def sampled_errors(
    ctx: Any, before: Dict[str, Any], rollout: Dict[str, np.ndarray], rows: np.ndarray
) -> Tuple[Dict[str, Tuple[float, float]], Dict[str, float], Dict[str, float]]:
    """On the sequences `rows` of the window's rollout and the parameters it
    started from: both entry points as programs (the decode through the
    hybrid carry at EVERY slot), and what the timed rollout stored, against
    the reference forward -> (errors with their tolerances, the bfloat16
    reference's errors: the second reading, quantiles of what the two
    compilations of the decode differ by)."""
    config, nets = ctx.cell.config, ctx.networks
    ref, top_k = config["reference"], int(config["num_experts_per_tok"])
    tokens = jnp.asarray(rollout["tokens"].T)  # [E, T]
    params = jax.device_put(type(nets["state"].params)(*before["params"]))
    ctx.problems.extend(stated_mismatches(config, nets, params, ctx.shapes, tokens[rows]))
    outputs = _lm.program_outputs(nets, params, tokens, rows)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    reference_params = jax.device_put(before["params"])
    run = lambda dtype: jax.jit(functools.partial(forward, spec=config, dtype=dtype))(
        *reference_params, tokens[rows]
    )
    want, low = run(jnp.float32), run(jnp.bfloat16)
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
    agreeing = lambda got: _lm._set_agreement(
        got["expert_index"], want["expert_index"], _router_width(config)
    )[1].reshape(want["values"].shape)
    actions = jnp.asarray(rollout["action"].T[rows])
    record = {"log_prob": rollout["log_prob"].T[rows], "value": rollout["value"].T[rows]}
    stored = _lm.compare_record(record, outputs["decode"], want, actions, agreeing(outputs["decode"]))
    errors.update({f"rollout_{name}": (error, tolerances[name]) for name, error in stored.items()})
    # The second reading of the record: the bfloat16 reference's own.
    low_record = {"log_prob": _lm._log_prob_of(low["logits"], actions), "value": low["values"]}
    second = {
        **compare_outputs({**low, "pairs_per_token": top_k}, want, config),
        **{
            f"record_{k}": v
            for k, v in _lm.compare_record(low_record, low, want, actions, agreeing(low)).items()
        },
    }
    gap = {
        f"{name}_{label}": float(jnp.quantile(x, q))
        for name, x in _lm._compilation_gap(record, outputs["decode"], actions).items()
        for label, q in (("p50", 0.5), ("p99", 0.99), ("p999", 0.999), ("max", 1.0))
    }
    return errors, second, gap


def _without_bias(params: Tuple[Any, Any]) -> Tuple[Tuple[Any, Any], Dict[str, np.ndarray]]:
    """(the parameters without their `expert_bias` leaves, those leaves by layer)."""
    tree = params[0]["params"]
    biases = {name: layer["ffn"]["expert_bias"] for name, layer in tree.items()
              if isinstance(layer, dict) and "expert_bias" in layer.get("ffn", {})}
    strip = lambda layer: {**layer, "ffn": {k: v for k, v in layer["ffn"].items() if k != "expert_bias"}}
    rest = {name: strip(layer) if name in biases else layer for name, layer in tree.items()}
    return ({"params": rest}, params[1]), biases


def update_errors(before: Any, got: Any, want: Any) -> Tuple[Dict[str, float], Dict[str, List[float]]]:
    """references/ppo_olmoe.py's leaf-by-leaf parameter change, over every
    leaf but `expert_bias`; of those, how many the window changed at all
    (`expert_bias_changed`: bit for bit, in program or reference)."""
    (before, bias_before), (got, bias_got), (want, bias_want) = (
        _without_bias(before), _without_bias(got), _without_bias(want)
    )
    same = lambda a, b: np.array_equal(
        np.asarray(a, np.float32).view(np.uint32), np.asarray(b, np.float32).view(np.uint32)
    )
    changed = sum(
        not (same(bias_got[name], bias_before[name]) and same(bias_want[name], bias_before[name]))
        for name in bias_before
    )
    update, leaves = _lm.update_errors(before, got, want)
    return {**update, "expert_bias_changed": float(changed), "expert_bias_leaves": len(bias_before)}, leaves


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
    rollout = _lm.stored_rollout(before, after, int(hyper["env_modulus"]))
    train = {k: float(np.mean(v)) for k, v in after["train"].items()}
    top_k, steps = int(config["num_experts_per_tok"]), int(hyper["epochs"]) * int(hyper["num_minibatches"])
    errors: Dict[str, Tuple[float, float]] = {
        "rollout_returns": (
            compare.max_scaled_error(rollout["logged_return"], rollout["reward"][-1]),
            float(ref["returns_tol"]),
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
    sampled, second, gap = sampled_errors(ctx, before, rollout, rows)
    errors.update(sampled)

    # The update: the window's parameter change, logged loss parts and
    # counters against the reference's replay.
    replayed, logged = _lm.replay_update(before, rollout, config, hyper, ref, shards)
    update, leaves = update_errors(before["params"], after["params"], replayed)
    if not update["expert_bias_leaves"]:
        ctx.problems.append("no expert_bias leaf in the run's parameters")
    errors["update_expert_bias_changed"] = (update["expert_bias_changed"], 0.0)
    errors["update_params_worst_leaf"] = (update["worst_leaf"], float(ref["update_worst_leaf_tol"]))
    errors["update_params_all_leaves"] = (update["all_leaves"], float(ref["update_all_leaves_tol"]))
    for name in _LOSS_PARTS:
        errors[f"update_{name}"] = (compare.max_scaled_error(train[name], logged[name]), float(ref["loss_tol"]))
    for name, limit in (
        ("expert_load_max_over_mean", "expert_load_tol"), ("held_pairs_per_token", "held_pairs_tol"),
        ("router_bias_changed_share", "bias_changed_tol"),
    ):
        errors[f"update_{name}"] = (compare.max_scaled_error(train[name], logged[name]), float(ref[limit]))
    # The rollout keeps no expert sets: its held pairs a token are held to the
    # update's, which passes the same tokens through the same router
    # teacher-forced (on parameters 0 to 8 Adam steps on).
    errors["rollout_held_pairs_per_token"] = (
        compare.max_scaled_error(train["rollout_held_pairs_per_token"], logged["held_pairs_per_token"]),
        float(ref["rollout_held_pairs_tol"]),
    )
    if ref.get("lower_precision_update"):
        low_replayed, low_logged = _lm.replay_update(before, rollout, config, hyper, ref, shards, jnp.bfloat16)
        low_update, low_leaves = update_errors(before["params"], low_replayed, replayed)
        second.update({f"update_params_{k}": v for k, v in low_update.items()})
        second.update({f"update_{k}": compare.max_scaled_error(low_logged[k], logged[k]) for k in _LOSS_PARTS})
        second["update_leaves"] = low_leaves
    ctx.health["reference"] = {
        "update_leaves": leaves, "lower_precision": second, "stored_minus_decoded": gap,
        "rows": rows.tolist(),
        "counters": {k: train[k] for k in (
            "held_pairs_per_token", "rollout_held_pairs_per_token", "expert_load_max_over_mean",
            "router_bias_changed_share", "dropped_pairs",
        )},
    }
    return errors
