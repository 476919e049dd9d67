"""Operations and bytes a window-and-full attention token policy's layers
need when every sequence starts from a PROMPT, from shapes alone: the
Mellum2-12B-A2.5B (`mellum`) stack, one expert-parallel rank's share
(`ppo_mellum2_moe_ep8_share`). A sibling of flops_swa.py, which stays as it
is (its decode means run over positions 0 .. G - 1 of a rollout from an empty
cache) and whose per-kernel rules are used here: counted as the LEAST the
work needs, so that no roofline share can pass 100%, and of the WORK, not of
what implements it.

A sequence is P prefix tokens (`env.kwargs.prompt_length`), prefilled in one
teacher-forced pass, then G generated ones (`system.rollout_length`) whose
inputs sit at positions P .. P + G - 1:

  * a decode step at position t reads t + 1 rows of a full layer's growing
    cache and min(t + 1, W) rows of a window layer's ring; both as means over
    the positions P .. P + G - 1 (a cost is linear in the rows);
  * the update passes every one of the P + G positions through the stack,
    forward and backward — projections, the router, the held experts' rows,
    the band's and the triangle's pairs over P + G — and the head over the G
    response positions alone;
  * the prefill is one FORWARD pass over the P prefix positions, with no
    head: projections, the router, the held experts' rows, the band's and
    the triangle's pairs over P, and the state it leaves written once (a full
    layer's P rows, a window layer's min(P, W), keys and values in float32);
  * the prefill runs under its own scope BESIDE `rollout`, not under it, so
    what reads `rollout/.../<scope>` (`decode_share`, the decode's roofline
    shares) reads decode steps alone and is handed a decode step's cost;
  * a decode step's expert weights are counted as what has to come from HBM
    (flops_swa.py's `from_hbm_share`: what the chip's vector memory cannot
    hold of the held experts' float32 weights, all layers together);
  * there is no dense layer, no shared expert and no gate; norms, rotations,
    softmaxes, the sort and the gathers of the dispatch, the embedding's
    lookup, the value head and the optimiser are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.harness.flops_lfm2 import _add, _times, held_rows
from benchmarks.harness.flops_lm import _F32, _dense_bytes, _train, expert_cost
from benchmarks.harness.flops_swa import (
    FULL, WINDOW, attend_decode_step_cost, attend_forward_cost, attend_update_cost,
    from_hbm_share, held_experts_reached, layer_pairs,
)

Cost = Dict[str, float]


def mean_live_rows(kind: str, prompt: int, length: int, model: Dict[str, Any]) -> float:
    """Rows of its state a decode step of a layer of `kind` reads, a mean
    over the input positions prompt .. prompt + length - 1."""
    rows = lambda t: min(t + 1, model["sliding_window"]) if kind == WINDOW else t + 1
    return sum(rows(t) for t in range(prompt, prompt + length)) / float(length)


def _layers(model: Dict[str, Any], kind: str) -> int:
    return model["layer_types"].count(kind)


def _projection_widths(model: Dict[str, Any]):
    q_width = model["num_heads"] * model["head_dim"]
    kv_width = model["num_kv_heads"] * model["head_dim"]
    return q_width, kv_width


def update_cost(
    sequences: int, prompt: int, length: int, epochs: int, num_minibatches: int,
    model: Dict[str, Any], held_pairs_per_token: Optional[float] = None,
) -> Dict[str, Any]:
    """One PPO update on one chip: every epoch passes every position of
    [prefix ; response] once through the stack, forward and backward, and
    the response's positions through the head, in `num_minibatches` SGD
    steps."""
    d, layers = model["hidden_size"], len(model["layer_types"])
    q_width, kv_width = _projection_widths(model)
    whole = prompt + length
    positions = float(sequences) * whole * epochs
    steps = epochs * num_minibatches

    def dense(n_in: int, n_out: int, rows: float = positions) -> Cost:
        return {
            "flops": _train(2.0 * rows * n_in * n_out),
            "bytes": steps * _dense_bytes(rows / steps, n_in, n_out, 2),
        }

    scores = lambda kind: _times(
        attend_update_cost(
            sequences * epochs, whole, model["num_heads"], layer_pairs(kind, whole, model), model
        ),
        _layers(model, kind),
    )
    rows = held_rows(positions, model, held_pairs_per_token)
    parts = {
        "projections": _times(_add(_times(dense(d, q_width), 2), _times(dense(d, kv_width), 2)), layers),
        "full_scores": scores(FULL),
        "window_scores": scores(WINDOW),
        "router": _times(dense(d, model["num_experts"]), layers),
        "experts": _times(
            expert_cost(rows / steps, model, True, held_experts_reached(positions / steps, model)),
            layers * steps,
        ),
        "head": dense(d, model["vocab_size"], float(sequences) * length * epochs),
    }
    return {
        "samples": int(sequences * length * epochs),
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": sum(p["bytes"] for p in parts.values()),
        "parts": parts,
    }


def prefill_cost(
    sequences: int, prompt: int, model: Dict[str, Any], held_pairs_per_token: Optional[float] = None
) -> Dict[str, Any]:
    """One prefill on one chip: the forward pass of `sequences` prefixes of
    `prompt` positions, no head, and every layer's state written once."""
    d, layers = model["hidden_size"], len(model["layer_types"])
    q_width, kv_width = _projection_widths(model)
    positions = float(sequences) * prompt
    dense = lambda n_in, n_out: {
        "flops": 2.0 * positions * n_in * n_out, "bytes": _dense_bytes(positions, n_in, n_out, 1),
    }
    scores = lambda kind: _times(
        attend_forward_cost(sequences, prompt, model["num_heads"], layer_pairs(kind, prompt, model), model),
        _layers(model, kind),
    )
    kept = lambda kind: min(prompt, model["sliding_window"]) if kind == WINDOW else prompt
    rows = held_rows(positions, model, held_pairs_per_token)
    parts = {
        "projections": _times(_add(_times(dense(d, q_width), 2), _times(dense(d, kv_width), 2)), layers),
        "full_scores": scores(FULL),
        "window_scores": scores(WINDOW),
        "router": _times(dense(d, model["num_experts"]), layers),
        "experts": _times(
            expert_cost(rows, model, False, held_experts_reached(positions, model)), layers
        ),
        "state": {
            "flops": 0.0,
            "bytes": _F32 * sequences * 2.0 * kv_width * sum(kept(kind) for kind in model["layer_types"]),
        },
    }
    return {
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": sum(p["bytes"] for p in parts.values()),
        "parts": parts,
    }


def mellum2_ppo_shapes(
    config: Any, envs_per_chip: int, updates_per_tick: int,
    held_pairs: Optional[Dict[str, Optional[float]]] = None,
) -> Dict[str, Any]:
    """What the composed config resolved to, `update_cost` for the readers
    every cell shares (`update_roofline_share`), and the per-kernel costs the
    layers' roofline readers divide by their scoped time. `held_pairs`: the
    run's own mean pairs a token a layer on the held experts, `update`,
    `rollout` and `prefill`, where it logged them."""
    net = config.network.actor_network
    held_pairs = held_pairs or {}
    kinds = [str(k) for k in net.layer_types]
    model = {
        "hidden_size": int(net.hidden_size), "layer_types": kinds,
        "num_heads": int(net.num_heads), "num_heads_per_layer": [int(net.num_heads)] * len(kinds),
        "num_kv_heads": int(net.num_kv_heads), "head_dim": int(net.head_dim),
        "sliding_window": int(net.sliding_window),
        "num_experts": int(net.num_experts), "experts_held": int(net.experts_held),
        "experts_per_token": int(net.experts_per_token), "expert_width": int(net.expert_width),
        "vocab_size": int(config.env.kwargs.vocab_size),
    }
    prompt, length = int(config.env.kwargs.prompt_length), int(config.system.rollout_length)
    epochs, minibatches = int(config.system.epochs), int(config.system.num_minibatches)
    heads, layers = model["num_heads"], len(kinds)
    shapes = {
        "envs_per_chip": int(envs_per_chip), "rollout_length": length, "prompt_length": prompt,
        "epochs": epochs, "num_minibatches": minibatches, "updates_per_tick": int(updates_per_tick),
        "model": model,
    }
    cost = update_cost(
        envs_per_chip, prompt, length, epochs, minibatches, model, held_pairs.get("update")
    )
    shapes["update_cost"] = cost
    shapes["experts_update_cost"] = cost["parts"]["experts"]
    shapes["window_attend_update_cost"] = cost["parts"]["window_scores"]
    # The full layers' causal forward over P + G, which `attention_roofline_share` reads.
    shapes["attention_forward_cost"] = _times(
        attend_forward_cost(
            envs_per_chip * epochs, prompt + length, heads, layer_pairs(FULL, prompt + length, model), model
        ),
        _layers(model, FULL),
    )
    prefill = prefill_cost(envs_per_chip, prompt, model, held_pairs.get("prefill"))
    shapes["prefill_cost"] = prefill
    # One decode step of the rollout: every sequence one token, the live rows
    # a mean over the positions P .. P + G - 1.
    decode = lambda kind: _times(
        attend_decode_step_cost(envs_per_chip, mean_live_rows(kind, prompt, length, model), heads, model),
        _layers(model, kind),
    )
    shapes["full_attend_decode_step_cost"] = decode(FULL)
    shapes["window_attend_decode_step_cost"] = decode(WINDOW)
    decode_rows = held_rows(float(envs_per_chip), model, held_pairs.get("rollout"))
    reached = held_experts_reached(float(envs_per_chip), model)
    shapes["experts_decode_step_cost"] = _times(
        expert_cost(decode_rows, model, False, reached * from_hbm_share(model, layers)), layers
    )
    return shapes
