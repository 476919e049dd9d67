"""Operations and bytes a latent-attention token policy's layers need, from
shapes alone: the Kanana-2 (`deepseek_v3`) stack, one expert-parallel rank's
share (`ppo_kanana2_moe_ep8_share`). A sibling of flops_lm.py and
flops_lfm2.py, which stay as they are and whose per-matmul rules are used
here: counted as the LEAST the work needs, so that no roofline share can pass
100%, and of the WORK, not of what implements it.

  * latent attention's projections are W_q [D, H (n + r)], W_kva [D, c + r],
    W_kvb [c, H (n + v)] and W_o [H v, D] (H heads; n, r the query's and key's
    un-rotated and rotated widths, v the values', c the latent's);
  * the update's scores count the lower triangle, q k^T at n + r and p v at
    v; their bytes are q, k, v read and the output written once;
  * a decode step attends in the latent space: W_uk into the query (2 B H n
    c), every head against every live row's whole width (2 B H (c + r) a
    row), the weights times the row's latent (2 B H c a row), W_uv (2 B H c
    v). Its bytes are the live rows read once in float32 — (T + 1) / 2 of
    them a sequence, the mean over a rollout of T steps from an empty cache —
    W_kvb once as the bfloat16 operands of one MXU pass (XLA hoists that copy
    of a loop-invariant weight out of the rollout's scan: counted at 4 bytes
    the LFM2 cell's share read 124%, PERF.md §6, PR 33), and the queries in
    and the result out;
  * the shared expert is three [D, shared_width] matmuls a token, the dense
    layer three [D, dense_width];
  * experts count the rows that land on the HELD experts (the pairs a token a
    layer the run itself logged, else top-k * held / experts under uniform
    routing) and the held experts' weights;
  * the head is a [D, V] matrix of its own over the vocabulary slice;
  * norms, rotations, softmaxes, the sort and the gathers of the dispatch,
    the embedding's lookup, the value head and the optimiser are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.harness.flops_lfm2 import _add, _times, held_rows
from benchmarks.harness.flops_lm import _F32, _dense_bytes, _train, expert_cost

Cost = Dict[str, float]
_BF16 = 2


def _triangle(length: int) -> float:
    return length * (length + 1) / 2.0


def scores_forward_cost(sequences: float, length: int, model: Dict[str, Any]) -> Cost:
    """Causal q k^T (n + r wide) and p v (v wide) of ONE layer's forward
    pass, and a fused kernel's traffic: q, k, v in, the result out."""
    heads, qk, v = model["num_heads"], model["qk_nope_head_dim"] + model["qk_rope_head_dim"], model["v_head_dim"]
    return {
        "flops": sequences * 2.0 * _triangle(length) * heads * (qk + v),
        "bytes": _F32 * sequences * length * heads * (2 * qk + 2 * v),
    }


def latent_attend_decode_step_cost(sequences: float, length: int, model: Dict[str, Any]) -> Cost:
    """ONE layer, one decode step of `sequences` sequences, the mean over a
    rollout of `length` steps from an empty cache."""
    heads, rank, rot = model["num_heads"], model["kv_lora_rank"], model["qk_rope_head_dim"]
    nope, v = model["qk_nope_head_dim"], model["v_head_dim"]
    live = (length + 1) / 2.0
    per_head = 2.0 * nope * rank + 2.0 * (2 * rank + rot) * live + 2.0 * rank * v
    return {
        "flops": sequences * heads * per_head,
        "bytes": _F32 * sequences * (live * (rank + rot) + heads * (nope + rot + v))
        + _BF16 * rank * heads * (nope + v),
    }


def update_cost(
    sequences: int, length: int, epochs: int, num_minibatches: int, model: Dict[str, Any],
    held_pairs_per_token: Optional[float] = None,
) -> Dict[str, Any]:
    """One PPO update on one chip: every epoch passes every token once
    through the stack, forward and backward, in `num_minibatches` SGD steps."""
    d, heads, rank = model["hidden_size"], model["num_heads"], model["kv_lora_rank"]
    nope, rot, v = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    layers, dense_layers = model["num_layers"], model["num_dense_layers"]
    routed_layers = layers - dense_layers
    tokens = float(sequences) * length * epochs
    steps = epochs * num_minibatches

    def dense(n_in: int, n_out: int) -> Cost:
        return {
            "flops": _train(2.0 * tokens * n_in * n_out),
            "bytes": steps * _dense_bytes(tokens / steps, n_in, n_out, 2),
        }

    scores = scores_forward_cost(sequences * epochs, length, model)
    rows = held_rows(tokens, model, held_pairs_per_token)
    parts = {
        "latent_projections": _times(
            _add(dense(d, heads * (nope + rot)), dense(d, rank + rot), dense(heads * v, d)), layers
        ),
        "latent_expansion": _times(dense(rank, heads * (nope + v)), layers),
        "scores": {"flops": layers * _train(scores["flops"]), "bytes": layers * 3 * scores["bytes"]},
        "dense_mlps": _times(dense(d, model["dense_width"]), 3 * dense_layers),
        "shared_experts": _times(dense(d, model["shared_width"]), 3 * routed_layers),
        "router": _times(dense(d, model["num_experts"]), routed_layers),
        "experts": _times(
            expert_cost(rows / steps, model, True, model["experts_held"]), routed_layers * steps
        ),
        "head": dense(d, model["vocab_size"]),
    }
    return {
        "samples": int(tokens),
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": sum(p["bytes"] for p in parts.values()),
        "parts": parts,
    }


def mla_ppo_shapes(
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
        "hidden_size": int(net.hidden_size), "num_layers": len(net.layer_types),
        "num_dense_layers": int(net.num_dense_layers), "dense_width": int(net.dense_width),
        "num_heads": int(net.num_heads), "kv_lora_rank": int(net.kv_lora_rank),
        "qk_nope_head_dim": int(net.qk_nope_head_dim), "qk_rope_head_dim": int(net.qk_rope_head_dim),
        "v_head_dim": int(net.v_head_dim), "num_experts": int(net.num_experts),
        "experts_held": int(net.experts_held), "experts_per_token": int(net.experts_per_token),
        "expert_width": int(net.expert_width),
        "shared_width": int(net.n_shared_experts) * int(net.expert_width),
        "vocab_size": int(config.env.kwargs.vocab_size),
    }
    length, epochs = int(config.system.rollout_length), int(config.system.epochs)
    minibatches = int(config.system.num_minibatches)
    layers = model["num_layers"]
    routed_layers = layers - model["num_dense_layers"]
    shapes = {
        "envs_per_chip": int(envs_per_chip), "rollout_length": length, "epochs": epochs,
        "num_minibatches": minibatches, "updates_per_tick": int(updates_per_tick), "model": model,
    }
    cost = update_cost(
        envs_per_chip, length, epochs, minibatches, model, held_pairs.get("update")
    )
    shapes["update_cost"] = cost
    shapes["experts_update_cost"] = cost["parts"]["experts"]
    shapes["latent_attend_update_cost"] = _add(
        cost["parts"]["latent_expansion"], cost["parts"]["scores"]
    )
    # One decode step of the rollout: every sequence one token.
    shapes["latent_attend_decode_step_cost"] = _times(
        latent_attend_decode_step_cost(envs_per_chip, length, model), layers
    )
    shapes["experts_decode_step_cost"] = _times(
        expert_cost(
            held_rows(float(envs_per_chip), model, held_pairs.get("rollout")), model, False,
            model["experts_held"],
        ),
        routed_layers,
    )
    shapes["attention_forward_cost"] = _times(
        scores_forward_cost(envs_per_chip * epochs, length, model), layers
    )
    return shapes
