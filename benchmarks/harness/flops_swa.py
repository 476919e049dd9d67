"""Operations and bytes a window-and-full attention token policy's layers
need, from shapes alone: the Laguna-XS.2 (`laguna`) stack, one
expert-parallel rank's share (`ppo_laguna_xs2_ep32_share`). A sibling of
flops_lm.py, flops_lfm2.py, flops_mla.py and flops_kda.py, which stay as they
are and whose rules are used here: counted as the LEAST the work needs, so
that no roofline share can pass 100%, and of the WORK, not of what implements
it — a band's count knows no tile.

  * an attention layer's projections are W_q and W_o [D, H_l d] at ITS number
    of query heads H_l (`num_heads_per_layer`), W_k and W_v [D, KV d] and the
    gate W_g [D, H_l]; the per-head norms, the rotations and the gate's
    sigmoid are elementwise and not counted;
  * scores count the (query, key) pairs the mask allows, q k^T and p v each 2
    d operations a pair a query head: a full layer the triangle T (T + 1) / 2,
    a window layer the band sum_t min(t + 1, W) (393,472 of 524,800 at T =
    1,024, W = 512); a training pass is three forwards' worth; as bytes a
    fused pass's traffic: forward q and the result at H_l heads and k and v at
    KV heads once, backward those, the result's cotangent and the three
    gradients once;
  * a decode step of a window layer reads the ring's live rows, min(t + 1, W)
    a sequence, keys and values of KV heads in float32 (8 KiB a row here),
    once, with q in and the result out; of a full layer the t + 1 rows of the
    growing cache. Both are means over the rollout's positions (a cost is
    linear in the rows). HBM binds: 2 d operations a row a query head a
    product against 2 KV d 4 bytes a row;
  * the dense layer is three [D, dense_width] matmuls a token, the shared
    expert three [D, shared_width]; experts count the rows that land on the
    HELD experts (the pairs a token a layer the run itself logged, else top-k
    * held / experts under uniform routing) and the float32 weights of the
    held experts a call's tokens reach (`held_experts_reached`), read once a
    pass (flops_lm.py's `expert_cost`, as every cell counts them). A DECODE
    step's count is of what has to come from HBM: the rollout is one loop
    over the same weights, and what the chip's vector memory holds of them
    stays there from step to step (`from_hbm_share`). The compiled learner
    keeps three of the twelve [8, 2048, 512] float32 operands of the decode's
    grouped matmuls in that memory space, and a count that read every one
    from HBM every step read 107% on the chip (PERF.md section 6, PR 44);
  * the head is a [D, V] matrix of its own over the vocabulary slice;
  * norms, rotations, softmaxes, gates, the sort and the gathers of the
    dispatch, the embedding's lookup, the value head and the optimiser are
    not counted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks.harness.flops_lfm2 import _add, _times, held_rows
from benchmarks.harness.flops_lm import _F32, _dense_bytes, _train, expert_cost, experts_touched

Cost = Dict[str, float]
WINDOW, FULL = "sliding_attention", "full_attention"
# A v5e core's vector memory. XLA gives loop-invariant operands a place in it
# for the length of a loop (memory space 1 in the compiled text).
_VMEM_BYTES = 128 * 2**20


def band_pairs(length: int, window: int) -> float:
    """(query, key) pairs 0 <= t - j < window of a sequence of `length`."""
    ramp = min(length, window)
    return ramp * (ramp + 1) / 2.0 + (length - ramp) * float(window)


def triangle_pairs(length: int) -> float:
    return length * (length + 1) / 2.0


def layer_pairs(kind: str, length: int, model: Dict[str, Any]) -> float:
    return band_pairs(length, model["sliding_window"]) if kind == WINDOW else triangle_pairs(length)


def attend_forward_cost(sequences: float, length: int, heads: int, pairs: float, model: Dict[str, Any]) -> Cost:
    """ONE layer's q k^T and p v over `pairs` pairs a query head a sequence,
    forward: q and the result at `heads` heads, k and v at the key/value
    heads, each moved once."""
    d, kv = model["head_dim"], model["num_kv_heads"]
    return {
        "flops": sequences * 2 * 2.0 * pairs * heads * d,
        "bytes": _F32 * sequences * length * d * (2 * heads + 2 * kv),
    }


def attend_update_cost(sequences: float, length: int, heads: int, pairs: float, model: Dict[str, Any]) -> Cost:
    """The same forward and backward: three forwards' operations; the
    backward pass reads q, k, v, the result and its cotangent and writes the
    three gradients."""
    forward = attend_forward_cost(sequences, length, heads, pairs, model)
    d, kv = model["head_dim"], model["num_kv_heads"]
    backward = _F32 * sequences * length * d * (4 * heads + 4 * kv)
    return {"flops": _train(forward["flops"]), "bytes": forward["bytes"] + backward}


def mean_live_rows(kind: str, length: int, model: Dict[str, Any]) -> float:
    """Rows of its cache a decode step of a layer of `kind` reads, a mean
    over the positions 0 .. length - 1 of a rollout from an empty cache."""
    return layer_pairs(kind, length, model) / length


def attend_decode_step_cost(sequences: float, rows: float, heads: int, model: Dict[str, Any]) -> Cost:
    """ONE layer, one decode step of `sequences` sequences against `rows`
    live rows each: keys and values read once in float32, q in, the result
    out."""
    d, kv = model["head_dim"], model["num_kv_heads"]
    return {
        "flops": sequences * 2 * 2.0 * rows * heads * d,
        "bytes": _F32 * sequences * (rows * 2 * kv * d + 2 * heads * d),
    }


def held_experts_reached(tokens: float, model: Dict[str, Any]) -> float:
    """Of the held experts, those the `tokens` tokens of one call reach on
    average under uniform routing: a token's top-k are k DIFFERENT experts of
    all, so each misses a given one with probability 1 - k / experts
    (flops_lm.py's `experts_touched`, the held share of it): 5.10 of 8 at 32
    tokens, where 8 pairs thrown one by one would reach 5.25."""
    return experts_touched(tokens, model) * model["experts_held"] / model["num_experts"]


def from_hbm_share(model: Dict[str, Any], routed_layers: int) -> float:
    """The share of the held experts' float32 weights, all routed layers
    together, that the chip's vector memory cannot hold: what a step of a
    loop over them has to read from HBM, however the rest is placed."""
    held = _F32 * routed_layers * 3.0 * model["hidden_size"] * model["expert_width"] * model["experts_held"]
    return max(0.0, 1.0 - _VMEM_BYTES / held)


def _heads_of(model: Dict[str, Any], kind: str) -> List[int]:
    """The query-head counts of the layers of `kind`, in layer order."""
    return [h for k, h in zip(model["layer_types"], model["num_heads_per_layer"]) if k == kind]


def _total(costs: List[Cost]) -> Cost:
    return _add(*costs) if costs else {"flops": 0.0, "bytes": 0.0}


def update_cost(
    sequences: int, length: int, epochs: int, num_minibatches: int, model: Dict[str, Any],
    held_pairs_per_token: Optional[float] = None,
) -> Dict[str, Any]:
    """One PPO update on one chip: every epoch passes every token once
    through the stack, forward and backward, in `num_minibatches` SGD steps."""
    d, kv_width = model["hidden_size"], model["num_kv_heads"] * model["head_dim"]
    dense_layers = model["num_dense_layers"]
    routed_layers = len(model["layer_types"]) - dense_layers
    tokens = float(sequences) * length * epochs
    steps = epochs * num_minibatches

    def dense(n_in: int, n_out: int) -> Cost:
        return {
            "flops": _train(2.0 * tokens * n_in * n_out),
            "bytes": steps * _dense_bytes(tokens / steps, n_in, n_out, 2),
        }

    def projections(heads: int) -> Cost:
        width = heads * model["head_dim"]
        return _add(_times(dense(d, width), 2), _times(dense(d, kv_width), 2), dense(d, heads))

    def scores(kind: str) -> Cost:
        return _total([
            attend_update_cost(sequences * epochs, length, heads, layer_pairs(kind, length, model), model)
            for heads in _heads_of(model, kind)
        ])

    rows = held_rows(tokens, model, held_pairs_per_token)
    parts = {
        "projections": _add(*[projections(heads) for heads in model["num_heads_per_layer"]]),
        "full_scores": scores(FULL),
        "window_scores": scores(WINDOW),
        "dense_mlps": _times(dense(d, model["dense_width"]), 3 * dense_layers),
        "shared_experts": _times(dense(d, model["shared_width"]), 3 * routed_layers),
        "router": _times(dense(d, model["num_experts"]), routed_layers),
        "experts": _times(
            expert_cost(
                rows / steps, model, True, held_experts_reached(tokens / steps, model)
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


def swa_ppo_shapes(
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
    kinds = [str(k) for k in net.layer_types]
    per_layer = net.get("num_heads_per_layer")
    model = {
        "hidden_size": int(net.hidden_size), "layer_types": kinds,
        "num_heads_per_layer": [int(h) for h in per_layer] if per_layer else [int(net.num_heads)] * len(kinds),
        "num_kv_heads": int(net.num_kv_heads), "head_dim": int(net.head_dim),
        "sliding_window": int(net.sliding_window),
        "num_dense_layers": int(net.num_dense_layers), "dense_width": int(net.dense_width),
        "num_experts": int(net.num_experts), "experts_held": int(net.experts_held),
        "experts_per_token": int(net.experts_per_token), "expert_width": int(net.expert_width),
        "shared_width": int(net.n_shared_experts) * int(net.expert_width),
        "vocab_size": int(config.env.kwargs.vocab_size),
    }
    length, epochs = int(config.system.rollout_length), int(config.system.epochs)
    minibatches = int(config.system.num_minibatches)
    routed_layers = len(kinds) - model["num_dense_layers"]
    shapes = {
        "envs_per_chip": int(envs_per_chip), "rollout_length": length, "epochs": epochs,
        "num_minibatches": minibatches, "updates_per_tick": int(updates_per_tick), "model": model,
    }
    cost = update_cost(envs_per_chip, length, epochs, minibatches, model, held_pairs.get("update"))
    shapes["update_cost"] = cost
    shapes["experts_update_cost"] = cost["parts"]["experts"]
    shapes["window_attend_update_cost"] = cost["parts"]["window_scores"]
    # One decode step of the rollout: every sequence one token, the live rows
    # a mean over the rollout's positions.
    shapes["window_attend_decode_step_cost"] = _total([
        attend_decode_step_cost(envs_per_chip, mean_live_rows(WINDOW, length, model), heads, model)
        for heads in _heads_of(model, WINDOW)
    ])
    decode_rows = held_rows(float(envs_per_chip), model, held_pairs.get("rollout"))
    reached = held_experts_reached(float(envs_per_chip), model)
    shapes["experts_decode_step_cost"] = _times(
        expert_cost(decode_rows, model, False, reached * from_hbm_share(model, routed_layers)),
        routed_layers,
    )
    # The full layers' causal forward, which `attention_roofline_share` reads.
    shapes["attention_forward_cost"] = _total([
        attend_forward_cost(envs_per_chip * epochs, length, heads, triangle_pairs(length), model)
        for heads in _heads_of(model, FULL)
    ])
    return shapes
