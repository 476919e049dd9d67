"""Operations and bytes the token policy's matmuls need, from shapes alone:
the OLMoE block between embedding and head (`ppo_olmoe_1layer_tokens`).
Counted as the LEAST the work needs, so that no roofline share can pass
100%: causal attention scores count the lower triangle only, experts count
the ACTIVE top-k a token, elementwise work (norms, RoPE, softmax, SwiGLU's
product, the losses, the optimizer) and the sort/gather of the dispatch are
not counted, and every tensor is moved once a use.

A matmul [rows, in] x [in, out] costs 2*rows*in*out FLOPs forward and twice
that backward (gradients with respect to weights and inputs).
"""

from __future__ import annotations

from typing import Any, Dict

_F32 = 4


def _train(flops_forward: float) -> float:
    return 3.0 * flops_forward


def _dense_bytes(rows: float, n_in: int, n_out: int, passes: int) -> float:
    """Activations in and out once a pass (forward, backward), weights once a
    pass and their gradient once."""
    return _F32 * (passes * rows * (n_in + n_out) + (passes + (passes > 1)) * n_in * n_out)


def expert_cost(rows: float, model: Dict[str, int], train: bool, experts_touched: float) -> Dict[str, float]:
    """The three grouped matmuls (gate, up, down) of `rows` routed (token,
    slot) pairs over `experts_touched` experts' weights."""
    d, f = model["hidden_size"], model["expert_width"]
    forward = 3 * 2.0 * rows * d * f
    passes = 2 if train else 1
    weights = 3 * d * f * experts_touched * (passes + (passes > 1))
    activations = passes * rows * (2 * (d + f) + (f + d))
    return {
        "flops": _train(forward) if train else forward,
        "bytes": _F32 * (weights + activations),
    }


def experts_touched(tokens_in_call: float, model: Dict[str, int]) -> float:
    """Expected number of distinct experts that `tokens_in_call` tokens reach
    under uniform routing: E * (1 - (1 - k/E)^tokens). 64 of 64 from about
    60 tokens on."""
    e, k = model["num_experts"], model["experts_per_token"]
    return e * (1.0 - (1.0 - k / e) ** tokens_in_call)


def attention_scores_flops(sequences: float, length: int, model: Dict[str, int]) -> float:
    """Causal q k^T and p v of one forward pass: the lower triangle of both,
    2 * 2 * (T*(T+1)/2) * heads * head_dim a sequence."""
    width = model["num_heads"] * model["head_dim"]
    return sequences * 2 * 2.0 * (length * (length + 1) / 2.0) * width


def attention_scores_bytes(sequences: float, length: int, model: Dict[str, int]) -> float:
    """q, k, v read and the output written once (a fused kernel's traffic)."""
    return _F32 * sequences * length * 4 * model["num_heads"] * model["head_dim"]


def update_cost(sequences: int, length: int, epochs: int, num_minibatches: int, model: Dict[str, int]) -> Dict[str, Any]:
    """One PPO update on one chip: every epoch passes every token once through
    the block, forward and backward, in `num_minibatches` SGD steps."""
    d, v, e, k = model["hidden_size"], model["vocab_size"], model["num_experts"], model["experts_per_token"]
    proj = model["num_heads"] * model["head_dim"]
    layers = model["num_layers"]
    tokens = float(sequences) * length * epochs
    steps = epochs * num_minibatches
    rows_a_step = tokens / steps
    dense = lambda n_in, n_out: {
        "flops": _train(2.0 * tokens * n_in * n_out),
        "bytes": steps * _dense_bytes(rows_a_step, n_in, n_out, 2),
    }
    parts = {
        "qkvo": {key: layers * 4 * value for key, value in dense(d, proj).items()},
        "scores": {
            "flops": layers * _train(attention_scores_flops(sequences * epochs, length, model)),
            "bytes": layers * 3 * attention_scores_bytes(sequences * epochs, length, model),
        },
        "router": {key: layers * value for key, value in dense(d, e).items()},
        "experts": {
            key: layers * steps * value
            for key, value in expert_cost(
                rows_a_step * k, model, True, experts_touched(rows_a_step, model)
            ).items()
        },
        "head": dense(d, v),
    }
    return {
        "samples": int(tokens),
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": sum(p["bytes"] for p in parts.values()),
        "parts": parts,
    }


def lm_ppo_shapes(config: Any, envs_per_chip: int, updates_per_tick: int) -> Dict[str, Any]:
    """What the composed config resolved to, `update_cost` for the readers
    that every cell shares (`update_roofline_share`), and the per-kernel
    costs the block's own roofline readers divide by their scoped time."""
    net = config.network.actor_network
    model = {
        "hidden_size": int(net.hidden_size), "num_heads": int(net.num_heads),
        "head_dim": int(net.head_dim), "num_experts": int(net.num_experts),
        "experts_per_token": int(net.experts_per_token), "expert_width": int(net.expert_width),
        "num_layers": int(net.get("num_layers", 1)), "vocab_size": int(config.system.action_dim),
    }
    length, epochs = int(config.system.rollout_length), int(config.system.epochs)
    minibatches = int(config.system.num_minibatches)
    shapes = {
        "envs_per_chip": int(envs_per_chip), "rollout_length": length, "epochs": epochs,
        "num_minibatches": minibatches, "updates_per_tick": int(updates_per_tick), "model": model,
    }
    cost = update_cost(envs_per_chip, length, epochs, minibatches, model)
    shapes["update_cost"] = cost
    shapes["experts_update_cost"] = cost["parts"]["experts"]
    # One decode step of the rollout: every sequence one token.
    step = expert_cost(
        float(envs_per_chip) * model["experts_per_token"], model, False,
        experts_touched(envs_per_chip, model),
    )
    shapes["experts_decode_step_cost"] = {k: model["num_layers"] * v for k, v in step.items()}
    shapes["attention_forward_cost"] = {
        "flops": model["num_layers"] * attention_scores_flops(envs_per_chip * epochs, length, model),
        "bytes": model["num_layers"] * attention_scores_bytes(envs_per_chip * epochs, length, model),
    }
    return shapes
