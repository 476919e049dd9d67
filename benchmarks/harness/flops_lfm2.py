"""Operations and bytes the hybrid token policy's layers need, from shapes
alone: the LFM2 mixture-of-experts stack, one expert-parallel rank's share
(`ppo_lfm2_moe_ep4_share`). A sibling of flops_lm.py and flops_sdar.py, which
stay as they are and whose per-matmul rules are used here: counted as the
LEAST the work needs, so that no roofline share can pass 100%, and of the
WORK, not of what implements it.

  * a conv mixer is its two projections ([D, 3D] in, [D, D] out) and the
    gates and the K-tap convolution between them: 2 + 2K operations a
    channel a token, and as bytes the least a fused pass moves — forward the
    projection's three parts in and the gated result out, backward those
    again with the result's gradient in and the three parts' gradients out.
    A decode step reads each mixer's weights once — as the bfloat16 operands
    of the one MXU pass the stated precision asks for (2 bytes a weight: XLA
    hoists that copy of the loop-invariant float32 weights out of the
    rollout's scan, and a count at 4 bytes read 124% on the chip, PERF.md §6,
    PR 33) — and reads and writes its float32 tail;
  * attention projections are grouped-query (wq and wo [D, heads * hd], wk
    and wv [D, kv_heads * hd]); scores count the lower triangle;
  * the dense feed-forwards are three [D, F] matmuls a token;
  * experts count the rows that land on the HELD experts (the pairs a token
    a layer the run itself logged, else top-k * held / experts under uniform
    routing) and the held experts' weights, not top-k a token;
  * the head is the embedding's transpose over the vocabulary slice;
  * norms, RoPE, softmaxes, the sort and the gathers of the dispatch, the
    value head and the optimiser are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.harness.flops_lm import (
    _F32,
    _dense_bytes,
    _train,
    attention_scores_bytes,
    attention_scores_flops,
    expert_cost,
)

Cost = Dict[str, float]
_BF16 = 2


def _times(cost: Cost, n: float) -> Cost:
    return {key: n * value for key, value in cost.items()}


def _add(*costs: Cost) -> Cost:
    return {key: sum(cost[key] for cost in costs) for key in costs[0]}


def held_rows(tokens: float, model: Dict[str, Any], pairs_per_token: Optional[float]) -> float:
    if pairs_per_token is None:  # uniform routing
        pairs_per_token = model["experts_per_token"] * model["experts_held"] / model["num_experts"]
    return tokens * pairs_per_token


def conv_mixer_update_cost(tokens: float, steps: int, model: Dict[str, Any]) -> Cost:
    """ONE conv mixer over `tokens` tokens in `steps` SGD steps, forward and
    backward."""
    d, taps = model["hidden_size"], model["conv_kernel"]
    projections = lambda n_out: {
        "flops": _train(2.0 * tokens * d * n_out),
        "bytes": steps * _dense_bytes(tokens / steps, d, n_out, 2),
    }
    between = {
        "flops": _train((2.0 + 2.0 * taps) * tokens * d),
        "bytes": _F32 * tokens * d * (4 + 7),
    }
    return _add(projections(3 * d), projections(d), between)


def conv_mixer_decode_step_cost(sequences: float, model: Dict[str, Any]) -> Cost:
    """ONE conv mixer, one decode step of `sequences` sequences: the two
    projections' weights read once as bfloat16 operands, the taps, the tail
    (read and written), a row in and a row out in float32."""
    d, taps = model["hidden_size"], model["conv_kernel"]
    tails = 2 * sequences * (taps - 1) * d
    return {
        "flops": 2.0 * sequences * 4 * d * d + (2.0 + 2.0 * taps) * sequences * d,
        "bytes": _BF16 * 4 * d * d + _F32 * (taps * d + tails + 2 * sequences * d),
    }


def update_cost(
    sequences: int, length: int, epochs: int, num_minibatches: int, model: Dict[str, Any],
    held_pairs_per_token: Optional[float] = None,
) -> Dict[str, Any]:
    """One PPO update on one chip: every epoch passes every token once
    through the stack, forward and backward, in `num_minibatches` SGD steps."""
    d, v, e = model["hidden_size"], model["vocab_size"], model["num_experts"]
    q_width = model["num_heads"] * model["head_dim"]
    kv_width = model["num_kv_heads"] * model["head_dim"]
    kinds = model["layer_types"]
    convs, attentions = kinds.count("conv"), kinds.count("full_attention")
    dense_layers = model["num_dense_layers"]
    routed_layers = len(kinds) - dense_layers
    tokens = float(sequences) * length * epochs
    steps = epochs * num_minibatches

    def dense(n_in: int, n_out: int) -> Cost:
        return {
            "flops": _train(2.0 * tokens * n_in * n_out),
            "bytes": steps * _dense_bytes(tokens / steps, n_in, n_out, 2),
        }

    heads_only = {"num_heads": model["num_heads"], "head_dim": model["head_dim"]}
    rows = held_rows(tokens, model, held_pairs_per_token)
    parts = {
        "conv_mixers": _times(conv_mixer_update_cost(tokens, steps, model), convs),
        "qkvo": _times(_add(_times(dense(d, q_width), 2), _times(dense(d, kv_width), 2)), attentions),
        "scores": {
            "flops": attentions * _train(attention_scores_flops(sequences * epochs, length, heads_only)),
            "bytes": attentions * 3 * attention_scores_bytes(sequences * epochs, length, heads_only),
        },
        "dense_mlps": _times(dense(d, model["dense_width"]), 3 * dense_layers),
        "router": _times(dense(d, e), routed_layers),
        "experts": _times(
            expert_cost(rows / steps, model, True, model["experts_held"]), routed_layers * steps
        ),
        "head": dense(d, v),
    }
    return {
        "samples": int(tokens),
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": sum(p["bytes"] for p in parts.values()),
        "parts": parts,
    }


def lfm2_ppo_shapes(
    config: Any, envs_per_chip: int, updates_per_tick: int,
    held_pairs: Optional[Dict[str, Optional[float]]] = None,
) -> Dict[str, Any]:
    """What the composed config resolved to, `update_cost` for the readers
    every cell shares (`update_roofline_share`), and the per-kernel costs the
    layers' roofline readers divide by their scoped time. `held_pairs`: the
    run's own mean pairs a token a layer on the held experts, `update` and
    `rollout`, where it logged them."""
    net = config.network.actor_network
    held_pairs = held_pairs or {}
    model = {
        "hidden_size": int(net.hidden_size), "layer_types": [str(k) for k in net.layer_types],
        "num_dense_layers": int(net.num_dense_layers), "dense_width": int(net.dense_width),
        "conv_kernel": int(net.conv_kernel), "num_heads": int(net.num_heads),
        "num_kv_heads": int(net.num_kv_heads), "head_dim": int(net.head_dim),
        "num_experts": int(net.num_experts), "experts_held": int(net.experts_held),
        "experts_per_token": int(net.experts_per_token), "expert_width": int(net.expert_width),
        "vocab_size": int(config.env.kwargs.vocab_size),
    }
    length, epochs = int(config.system.rollout_length), int(config.system.epochs)
    minibatches = int(config.system.num_minibatches)
    convs = model["layer_types"].count("conv")
    attentions = model["layer_types"].count("full_attention")
    routed_layers = len(model["layer_types"]) - model["num_dense_layers"]
    shapes = {
        "envs_per_chip": int(envs_per_chip), "rollout_length": length, "epochs": epochs,
        "num_minibatches": minibatches, "updates_per_tick": int(updates_per_tick), "model": model,
    }
    cost = update_cost(
        envs_per_chip, length, epochs, minibatches, model, held_pairs.get("update")
    )
    shapes["update_cost"] = cost
    shapes["experts_update_cost"] = cost["parts"]["experts"]
    shapes["conv_mixer_update_cost"] = cost["parts"]["conv_mixers"]
    # One decode step of the rollout: every sequence one token.
    shapes["conv_mixer_decode_step_cost"] = _times(
        conv_mixer_decode_step_cost(envs_per_chip, model), convs
    )
    shapes["experts_decode_step_cost"] = _times(
        expert_cost(
            held_rows(float(envs_per_chip), model, held_pairs.get("rollout")), model, False,
            model["experts_held"],
        ),
        routed_layers,
    )
    heads_only = {"num_heads": model["num_heads"], "head_dim": model["head_dim"]}
    shapes["attention_forward_cost"] = {
        "flops": attentions * attention_scores_flops(envs_per_chip * epochs, length, heads_only),
        "bytes": attentions * attention_scores_bytes(envs_per_chip * epochs, length, heads_only),
    }
    return shapes
