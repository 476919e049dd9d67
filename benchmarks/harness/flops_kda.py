"""Operations and bytes a delta-rule hybrid token policy's layers need, from
shapes alone: the Ling-3.0-flash (`bailing_hybrid`) stack, one
expert-parallel rank's share (`ppo_ling3_flash_ep64_share`). A sibling of
flops_lm.py, flops_lfm2.py and flops_mla.py, which stay as they are and whose
rules are used here: counted as the LEAST the work needs, so that no roofline
share can pass 100%, and of the WORK, not of what implements it — the delta
rule's count knows no chunk.

  * a delta mixer's projections are W_q, W_k, W_v, W_f [D, H d], W_o [H d, D]
    and W_beta, W_g [D, H] (H heads of d); its three K-tap convolutions, the
    SiLUs, norms and gates between them are elementwise and not counted;
  * the delta rule itself, a token a head, forward: the decay of the d x d
    state (d^2), the read under k (2 d^2), the rank-one write (2 d^2), the
    read under q (2 d^2) = 7 d^2 operations, whatever order or blocking
    computes them; a training pass is three forwards' worth, as every matmul
    here. Over whole sequences its bytes are its operands and results moved
    once a pass: forward q, k, v, g and beta in and o out; backward those
    again with o's gradient in and the five gradients out. The state is not
    counted there: a fused pass keeps it on the chip;
  * a decode step of the rule reads and writes every state once in float32
    (2 B H d^2 x 4 bytes: all there is to do is move it) with q, k, v, g,
    beta in and o out; HBM binds;
  * a latent-attention layer's projections, expansion, scores and decode
    step are flops_mla.py's, with the head-wise gate W_g [D, H] beside them;
  * the shared expert is three [D, shared_width] matmuls a token, the dense
    layer three [D, dense_width]; experts count the rows that land on the
    HELD experts (the pairs a token a layer the run itself logged, else top-k
    * held / experts under uniform routing) and the weights of the held
    experts that a call's rows reach: all of them in an update (512 rows a
    minibatch on 8), but a decode step's 8 rows reach 5.25 of 8 on average
    (`held_experts_reached`), and the grouped matmul reads no other's;
  * the head is a [D, V] matrix of its own over the vocabulary slice;
  * norms, rotations, softmaxes, convolutions, gates, the sort and the
    gathers of the dispatch, the embedding's lookup, the value head and the
    optimiser are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.harness import flops_mla
from benchmarks.harness.flops_lfm2 import _add, _times, held_rows
from benchmarks.harness.flops_lm import _F32, _dense_bytes, _train, expert_cost

Cost = Dict[str, float]


def _rule_flops(tokens: float, model: Dict[str, Any]) -> float:
    """The recurrence's forward operations over `tokens` tokens of ONE layer."""
    return 7.0 * tokens * model["num_heads"] * model["head_dim"] ** 2


def delta_rule_update_cost(tokens: float, model: Dict[str, Any]) -> Cost:
    """ONE layer's recurrence over `tokens` tokens, forward and backward."""
    heads, d = model["num_heads"], model["head_dim"]
    operands = 4 * d + 1  # q, k, v, g and beta a token a head
    forward, backward = operands + d, operands + d + operands
    return {
        "flops": _train(_rule_flops(tokens, model)),
        "bytes": _F32 * tokens * heads * (forward + backward),
    }


def delta_rule_decode_step_cost(sequences: float, model: Dict[str, Any]) -> Cost:
    """ONE layer, one decode step of `sequences` sequences: every state read
    and written once."""
    heads, d = model["num_heads"], model["head_dim"]
    return {
        "flops": _rule_flops(sequences, model),
        "bytes": _F32 * sequences * heads * (2 * d * d + 5 * d + 1),
    }


def held_experts_reached(rows: float, held: int) -> float:
    """Of `held` experts, those that `rows` (token, slot) pairs spread evenly
    over them reach, on average: held * (1 - (1 - 1 / held)^rows)."""
    return held * (1.0 - (1.0 - 1.0 / held) ** rows)


def update_cost(
    sequences: int, length: int, epochs: int, num_minibatches: int, model: Dict[str, Any],
    held_pairs_per_token: Optional[float] = None,
) -> Dict[str, Any]:
    """One PPO update on one chip: every epoch passes every token once
    through the stack, forward and backward, in `num_minibatches` SGD steps."""
    d, heads = model["hidden_size"], model["num_heads"]
    width = heads * model["head_dim"]
    kinds = model["layer_types"]
    deltas, latents = kinds.count("delta_attention"), kinds.count("latent_attention")
    dense_layers = model["num_dense_layers"]
    routed_layers = len(kinds) - dense_layers
    tokens = float(sequences) * length * epochs
    steps = epochs * num_minibatches

    def dense(n_in: int, n_out: int) -> Cost:
        return {
            "flops": _train(2.0 * tokens * n_in * n_out),
            "bytes": steps * _dense_bytes(tokens / steps, n_in, n_out, 2),
        }

    # flops_mla.py's own count of so many latent layers, of which its
    # latent-attention parts are taken.
    latent = flops_mla.update_cost(
        sequences, length, epochs, num_minibatches, {**model, "num_layers": latents}
    )["parts"]
    rows = held_rows(tokens, model, held_pairs_per_token)
    parts = {
        "delta_projections": _times(
            _add(_times(dense(d, width), 5), _times(dense(d, heads), 2)), deltas
        ),
        "delta_rule": _times(delta_rule_update_cost(tokens, model), deltas),
        "latent_projections": _add(latent["latent_projections"], _times(dense(d, heads), latents)),
        "latent_expansion": latent["latent_expansion"],
        "scores": latent["scores"],
        "dense_mlps": _times(dense(d, model["dense_width"]), 3 * dense_layers),
        "shared_experts": _times(dense(d, model["shared_width"]), 3 * routed_layers),
        "router": _times(dense(d, model["num_experts"]), routed_layers),
        "experts": _times(
            expert_cost(
                rows / steps, model, True, held_experts_reached(rows / steps, model["experts_held"])
            ),
            routed_layers * steps,
        ),
        "head": dense(d, model["vocab_size"]),
    }
    return {
        "samples": int(tokens),
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": sum(p["bytes"] for p in parts.values()),
        "parts": parts,
    }


def kda_ppo_shapes(
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
        "num_heads": int(net.num_heads), "head_dim": int(net.head_dim),
        "conv_kernel": int(net.conv_kernel), "kv_lora_rank": int(net.kv_lora_rank),
        "qk_nope_head_dim": int(net.qk_nope_head_dim), "qk_rope_head_dim": int(net.qk_rope_head_dim),
        "v_head_dim": int(net.v_head_dim), "num_experts": int(net.num_experts),
        "experts_held": int(net.experts_held), "experts_per_token": int(net.experts_per_token),
        "expert_width": int(net.expert_width),
        "shared_width": int(net.n_shared_experts) * int(net.expert_width),
        "vocab_size": int(config.env.kwargs.vocab_size),
    }
    length, epochs = int(config.system.rollout_length), int(config.system.epochs)
    minibatches = int(config.system.num_minibatches)
    deltas = model["layer_types"].count("delta_attention")
    latents = model["layer_types"].count("latent_attention")
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
    shapes["delta_rule_update_cost"] = cost["parts"]["delta_rule"]
    shapes["latent_attend_update_cost"] = _add(
        cost["parts"]["latent_expansion"], cost["parts"]["scores"]
    )
    # One decode step of the rollout: every sequence one token.
    shapes["delta_rule_decode_step_cost"] = _times(
        delta_rule_decode_step_cost(envs_per_chip, model), deltas
    )
    shapes["latent_attend_decode_step_cost"] = _times(
        flops_mla.latent_attend_decode_step_cost(envs_per_chip, length, model), latents
    )
    decode_rows = held_rows(float(envs_per_chip), model, held_pairs.get("rollout"))
    shapes["experts_decode_step_cost"] = _times(
        expert_cost(
            decode_rows, model, False, held_experts_reached(decode_rows, model["experts_held"])
        ),
        routed_layers,
    )
    shapes["attention_forward_cost"] = _times(
        flops_mla.scores_forward_cost(envs_per_chip * epochs, length, model), latents
    )
    return shapes
