"""Operations and bytes the block-diffusion token policy's matmuls need, from
shapes alone: the SDAR mixture-of-experts layer, one expert-parallel rank's
share (`ppo_sdar_moe_ep8_share`). A sibling of flops_lm.py, which stays as it
is and whose per-matmul rules are used here: counted as the LEAST the work
needs, so that no roofline share can pass 100%.

  * projections are grouped-query: wq and wo are [D, heads * hd], wk and wv
    [D, kv_heads * hd];
  * attention scores count the ALLOWED (query, key) pairs of the block mask
    only — in the update's `[clean ; S noisy copies]` a clean query of block b
    sees the b + 1 clean blocks up to its own, a noisy one the b clean blocks
    before it and its own block in its own copy — whatever computes them;
  * experts count the rows that land on the HELD experts (the pairs a token a
    layer the run itself logged, else top-k * held / experts under uniform
    routing), not top-k a token;
  * the head is over the vocabulary slice and, in the update, over the
    positions a pass committed: every response token once, not once a copy;
  * elementwise work, the sort and the gathers of the dispatch, the value
    head and the optimiser are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.harness.flops_lm import _F32, _dense_bytes, _train, expert_cost


def positions_a_sequence(model: Dict[str, int]) -> int:
    """`[clean ; S noisy copies]`: the prompt block once, the response 1 + S times."""
    return model["block_length"] + (1 + model["passes"]) * model["response_length"]


def allowed_pairs(model: Dict[str, int]) -> int:
    """(query, key) pairs the block mask allows in one sequence of the update."""
    size, blocks = model["block_length"], model["response_length"] // model["block_length"]
    clean = size * size * (blocks + 1) * (blocks + 2) // 2  # block b of 0..blocks sees b + 1 blocks
    noisy = size * size * sum(b + 1 for b in range(1, blocks + 1))  # b clean blocks and its own
    return clean + model["passes"] * noisy


def block_attention_cost(sequences: float, model: Dict[str, int], train: bool) -> Dict[str, float]:
    """q k^T and p v over the allowed pairs of `sequences` sequences, all
    layers; q, k, v read and the output written once a pass."""
    q_width = model["num_heads"] * model["head_dim"]
    kv_width = model["num_kv_heads"] * model["head_dim"]
    forward = sequences * 2 * 2.0 * allowed_pairs(model) * q_width
    moved = _F32 * sequences * positions_a_sequence(model) * (2 * q_width + 2 * kv_width)
    layers = model["num_layers"]
    return {
        "flops": layers * (_train(forward) if train else forward),
        "bytes": layers * (3 if train else 1) * moved,
    }


def held_rows(tokens: float, model: Dict[str, int], pairs_per_token: Optional[float]) -> float:
    if pairs_per_token is None:  # uniform routing
        pairs_per_token = model["experts_per_token"] * model["experts_held"] / model["num_experts"]
    return tokens * pairs_per_token


def update_cost(
    sequences: int, epochs: int, num_minibatches: int, model: Dict[str, int],
    held_pairs_per_token: Optional[float] = None,
) -> Dict[str, Any]:
    """One PPO update on one chip: every epoch passes every sequence's
    `[clean ; noisy copies]` once through the trunk, forward and backward, in
    `num_minibatches` SGD steps."""
    d, v, e = model["hidden_size"], model["vocab_size"], model["num_experts"]
    q_width, kv_width = model["num_heads"] * model["head_dim"], model["num_kv_heads"] * model["head_dim"]
    layers = model["num_layers"]
    steps = epochs * num_minibatches
    tokens = float(sequences) * positions_a_sequence(model) * epochs
    head_tokens = float(sequences) * model["response_length"] * epochs

    def dense(rows: float, n_in: int, n_out: int) -> Dict[str, float]:
        return {
            "flops": _train(2.0 * rows * n_in * n_out),
            "bytes": steps * _dense_bytes(rows / steps, n_in, n_out, 2),
        }

    times = lambda cost, n: {key: n * value for key, value in cost.items()}
    add = lambda a, b: {key: a[key] + b[key] for key in a}
    rows = held_rows(tokens, model, held_pairs_per_token)
    parts = {
        "qkvo": times(add(times(dense(tokens, d, q_width), 2), times(dense(tokens, d, kv_width), 2)), layers),
        "scores": block_attention_cost(float(sequences) * epochs, model, True),
        "router": times(dense(tokens, d, e), layers),
        "experts": times(
            expert_cost(rows / steps, model, True, model["experts_held"]), layers * steps
        ),
        "head": dense(head_tokens, d, v),
    }
    return {
        "samples": int(tokens),
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": sum(p["bytes"] for p in parts.values()),
        "parts": parts,
    }


def sdar_ppo_shapes(
    config: Any, envs_per_chip: int, updates_per_tick: int,
    held_pairs: Optional[Dict[str, float]] = None,
) -> Dict[str, Any]:
    """What the composed config resolved to, `update_cost` for the readers
    every cell shares (`update_roofline_share`), and the per-kernel costs the
    block's roofline readers divide by their scoped time. `held_pairs`: the
    run's own mean pairs a token a layer on the held experts, `update` and
    `rollout`, where it logged them."""
    net, kwargs = config.network.actor_network, config.env.kwargs
    held_pairs = held_pairs or {}
    model = {
        "hidden_size": int(net.hidden_size), "num_heads": int(net.num_heads),
        "num_kv_heads": int(net.num_kv_heads), "head_dim": int(net.head_dim),
        "num_experts": int(net.num_experts), "experts_held": int(net.experts_held),
        "experts_per_token": int(net.experts_per_token), "expert_width": int(net.expert_width),
        "num_layers": int(net.get("num_layers", 1)), "vocab_size": int(kwargs.vocab_size),
        "block_length": int(kwargs.block_length), "passes": int(kwargs.passes),
        "response_length": int(kwargs.length),
    }
    epochs, minibatches = int(config.system.epochs), int(config.system.num_minibatches)
    steps = int(config.system.rollout_length)
    blocks = model["response_length"] // model["block_length"]
    model_passes = 1 + blocks * (model["passes"] + 1)
    shapes = {
        "envs_per_chip": int(envs_per_chip), "rollout_length": steps, "epochs": epochs,
        "num_minibatches": minibatches, "updates_per_tick": int(updates_per_tick), "model": model,
        "model_passes": model_passes,
    }
    cost = update_cost(envs_per_chip, epochs, minibatches, model, held_pairs.get("update"))
    shapes["update_cost"] = cost
    shapes["experts_update_cost"] = cost["parts"]["experts"]
    shapes["block_attention_update_cost"] = cost["parts"]["scores"]
    # One env step of the rollout (what `moe_experts_decode_roofline_share`
    # multiplies by `rollout_length`): its share of the rollout's model
    # passes, each B positions a sequence through the held experts' weights.
    a_pass = expert_cost(
        held_rows(float(envs_per_chip) * model["block_length"], model, held_pairs.get("rollout")),
        model, False, model["experts_held"],
    )
    shapes["experts_decode_step_cost"] = {
        key: model["num_layers"] * value * model_passes / steps for key, value in a_pass.items()
    }
    return shapes
