"""The OLMoE decoder block as a token policy: sparse experts, a KV cache for
acting, a teacher-forced pass for the update — two entry points over ONE set
of parameters.

Published forward (`model_type` `olmoe`; stoix_tpu/reference/olmoe.py writes
it out plainly and is what the tests and the benchmark compare this file
with): pre-norm residual block `h = x + Attn(RMSNorm(x))`, `y = h +
MoE(RMSNorm(h))`; q and k are RMS-normalised over the whole projection
before the split into heads; RoPE in the rotate-half convention; the router
is a float32 softmax over ALL experts, then top-k, weights not renormalised;
experts are SwiGLUs; final RMSNorm and an untied `lm_head`.

  * `OlmoeLM.forward(tokens [B, T])` — teacher-forced: attention through
    `ops.best_attention` (the Pallas flash kernel on TPU).
  * `OlmoeLM.step(cache, token [B])` — one decode step through the cache.
    `KVCache.length [B]` is each sequence's next position; entries at or
    beyond it are never attended, so `reset_cache` (length := 0 where done)
    is the whole reset and costs nothing. A caller whose sequences move
    together (all start and end at once) asks `init_carry(..., together=True)`
    for ONE position, `length []`: the step reads that shape and nothing else,
    and writes the new key/value row as one slab (`write_cache_rows`).

No token is ever dropped and there is no capacity factor: the (token, slot)
pairs are sorted by expert and the three expert matmuls run as grouped
matmuls over the ragged groups (`jax.lax.ragged_dot`; XLA:TPU lowers it to a
native grouped-matmul kernel whose FLOPs are exactly the routed rows'). One
call takes another form: a decode step's chunk of the HELD experts (a few
dozen rows against hundreds of MB of float32 weights) is bound by reading the
weights, which that kernel does at 45 to 76% of their bytes' pace and slowest
where `width` is no whole number of 256 lanes, so there — on a TPU, by the
chunk's shape alone (`held_swiglu_form`) — the three products and the SwiGLU
between them are ONE Pallas pass that streams the weights in blocks that
divide them (`ops/held_swiglu.py`; PERF.md section 6, PR 48). The update and
the prefill, whose chunks are thousands of rows, keep the grouped matmuls
forward and backward.
The router's matmul and softmax run in float32 at `precision=HIGHEST` so
that expert choice does not depend on the MXU's bfloat16 pass.

Parameters, by name (the reference reads them by these names):
  embed [V, D]; layer_<i>/{input_norm [D], wq wk wv wo [D, D], q_norm k_norm
  [D], post_attn_norm [D], router [D, E], gate up [E, D, F], down [E, F, D]};
  final_norm [D]; lm_head [D, V].
Initialisation is normal(0.02) as the family does (an orthogonal QR of a
50,304 x 2,048 matrix is minutes of set-up); norms start at one.

`ValueHead` is a Dense [D -> 1] on the final-norm hidden state of the same
trunk: the critic side of `ActorCriticParams` holds only it.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from stoix_tpu.observability import SCOPES, annotate
from stoix_tpu.ops.held_swiglu import fits, held_swiglu_decode
from stoix_tpu.ops.pallas_attention import best_attention

# Keys beyond this many cache positions are read in blocks of this size: a
# decode step at position p reads ceil((p + 1) / block) blocks, not the whole
# cache.
_CACHE_BLOCK = 128


class KVCache(NamedTuple):
    # Position-major, so that a prefix of positions is one contiguous slab
    # (a decode step reads cache[:prefix] without a copy).
    k: Tuple[jax.Array, ...]  # a layer: [S, B, heads, head_dim] float32
    v: Tuple[jax.Array, ...]
    # int32, positions filled = the next token's position: [B], a sequence
    # its own, or [] where the sequences move together.
    length: jax.Array


def init_length(batch: int, together: bool) -> jax.Array:
    return jnp.zeros(() if together else (batch,), jnp.int32)


def init_cache(
    num_layers: int, batch: int, max_len: int, heads: int, head_dim: int, together: bool = False
) -> KVCache:
    zeros = lambda: tuple(
        jnp.zeros((max_len, batch, heads, head_dim), jnp.float32) for _ in range(num_layers)
    )
    return KVCache(zeros(), zeros(), init_length(batch, together))


def reset_length(length: jax.Array, done: jax.Array) -> jax.Array:
    """`length` with 0 where `done` [B]; the one position of sequences that
    move together goes to 0 once they are done, which they are together."""
    return jnp.where(jnp.all(done) if length.ndim == 0 else done, 0, length)


def reset_cache(cache: KVCache, done: jax.Array) -> KVCache:
    """Start a new sequence where `done`: nothing beyond `length` is read."""
    return cache._replace(length=reset_length(cache.length, done))


def write_cache_rows(
    cache_k: jax.Array, cache_v: jax.Array, k: jax.Array, v: jax.Array, length: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The caches [S, B, heads, head_dim] with the rows `k`, `v` [B, heads,
    head_dim] at each sequence's position `length` [B], or at the one
    position `length` [] of sequences that move together. The second is one
    slab written in place; the first is a scatter, which pins the cache's
    layout to its own, and at a head size under a lane row the reads of
    `_attend_cache` then copy the whole cache into theirs every step
    (PERF.md §6, PR 35)."""
    if length.ndim == 0:
        at = (length, 0, 0, 0)
        return (
            jax.lax.dynamic_update_slice(cache_k, k[None], at),
            jax.lax.dynamic_update_slice(cache_v, v[None], at),
        )
    at = (length, jnp.arange(k.shape[0]))
    return cache_k.at[at].set(k), cache_v.at[at].set(v)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


class Yarn(NamedTuple):
    """A rotation's YaRN keys (`rope_type` `yarn`): the inverse frequencies
    are blended by wavelength between the published ones and those divided by
    `factor`, and cos and sin are multiplied by `attention_factor`."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


def yarn_ramp(rotary_dim: int, theta: float, yarn: Yarn) -> Tuple[int, int, np.ndarray]:
    """(low, high, ramp [rotary_dim / 2]): a frequency that turns `beta` times
    over the original positions has index c(beta) = rotary_dim ln(L0 / (2 pi
    beta)) / (2 ln theta); low = floor(c(beta_fast)), high = ceil(c(
    beta_slow)), and the ramp climbs from 0 at `low` to 1 at `high`. Static:
    it does not depend on a sequence's length."""
    at = lambda beta: rotary_dim * math.log(
        yarn.original_max_position_embeddings / (2.0 * math.pi * beta)
    ) / (2.0 * math.log(theta))
    low = max(math.floor(at(yarn.beta_fast)), 0)
    high = min(math.ceil(at(yarn.beta_slow)), rotary_dim - 1)
    span = float(high - low) or 0.001
    ramp = np.clip((np.arange(rotary_dim // 2, dtype=np.float32) - low) / span, 0.0, 1.0)
    return low, high, ramp


def rope_angles(
    positions: jax.Array, head_dim: int, theta: float, yarn: Optional[Yarn] = None
) -> jax.Array:
    """The angles rotate-half turns a head by at `positions` [...]: [...,
    head_dim], the half's frequencies twice. With `yarn`, frequency i is
    (f_i / factor) ramp_i + f_i (1 - ramp_i) (`yarn_ramp`)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is not None:
        ramp = yarn_ramp(head_dim, theta, yarn)[2]
        inv_freq = inv_freq / yarn.factor * ramp + inv_freq * (1.0 - ramp)
    freqs = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.concatenate([freqs, freqs], axis=-1)


def rope(
    x: jax.Array, positions: jax.Array, theta: float, rotary_dim: Optional[int] = None,
    yarn: Optional[Yarn] = None,
) -> jax.Array:
    """x [..., heads, head_dim] rotated at `positions` [...] (rotate-half).
    With `rotary_dim` only a head's FIRST so many dims are rotated (paired
    inside them) and the rest passes; with `yarn` the frequencies are blended
    and cos and sin multiplied by its `attention_factor`."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = rope(x[..., :rotary_dim], positions, theta, None, yarn)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    head_dim = x.shape[-1]
    emb = rope_angles(positions, head_dim, theta, yarn)[..., None, :]  # broadcast over heads
    half = head_dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    if yarn is None:  # (in this order: the accepted learners' programs, op for op)
        return x * jnp.cos(emb) + rotated * jnp.sin(emb)
    scale = yarn.attention_factor
    return x * (jnp.cos(emb) * scale) + rotated * (jnp.sin(emb) * scale)


def route(
    x: jax.Array, router: jax.Array, top_k: int, renormalise: bool = False, *,
    score: str = "softmax", bias: Optional[jax.Array] = None, epsilon: float = 0.0,
    scale: float = 1.0, groups: int = 1, top_groups: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Float32 scores over all experts, then top-k: (scores [N, E], weights
    [N, k], index [N, k]). The defaults are OLMoE's: the scores are a softmax
    and the top-k weights the probabilities themselves or, with `renormalise`
    (`norm_topk_prob`), divided by their sum. Otherwise: `score` "sigmoid"
    scores each expert by itself; `bias` [E] (`expert_bias`) is added to the
    scores for the CHOICE alone, the weights stay the scores at the chosen
    experts; `epsilon` joins the sum they are divided by; `scale`
    (`routed_scaling_factor`) multiplies them; with `groups` > 1 (`n_group`)
    the choice is group-limited: the experts lie in `groups` equal groups in
    order, a group's score is the sum of its two largest score + bias, and
    the top-k are chosen inside the `top_groups` (`topk_group`) best groups."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )
    probs = jax.nn.softmax(logits, axis=-1) if score == "softmax" else jax.nn.sigmoid(logits)
    if bias is None and groups == 1:
        weights, index = jax.lax.top_k(probs, top_k)
    else:
        choice = probs if bias is None else probs + bias.astype(jnp.float32)
        if groups > 1:
            choice = _inside_best_groups(choice, groups, top_groups)
        _, index = jax.lax.top_k(choice, top_k)
        weights = jnp.take_along_axis(probs, index, axis=-1)
    if renormalise:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + epsilon if epsilon else total)
    if scale != 1.0:
        weights = weights * scale
    return probs, weights, index


def _inside_best_groups(choice: jax.Array, groups: int, top_groups: int) -> jax.Array:
    """`choice` [N, E] with -inf outside each token's `top_groups` best of
    `groups` groups, a group scored by the sum of its two largest entries."""
    grouped = choice.reshape(choice.shape[0], groups, -1)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [N, groups]
    _, best = jax.lax.top_k(group_score, top_groups)
    kept = jnp.any(jax.nn.one_hot(best, groups, dtype=bool), axis=-2)  # [N, groups]
    return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(choice.shape)


@jax.custom_vjp
def _dispatch(x: jax.Array, order: jax.Array, back: jax.Array) -> jax.Array:
    """Row p of the result is token `order[p] // k`'s row of x [N, D] (k =
    len(order) / N). `back` is `order`'s inverse, which makes the transpose a
    gather and a sum over slots instead of a scatter-add of N*k rows."""
    return jnp.take(x, order // (order.shape[0] // x.shape[0]), axis=0)


def _dispatch_fwd(x, order, back):
    return _dispatch(x, order, back), (back, x.shape[0])


def _dispatch_bwd(residuals, g):
    back, tokens = residuals
    return jnp.take(g, back, axis=0).reshape(tokens, -1, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(x: jax.Array, perm: jax.Array, inverse: jax.Array) -> jax.Array:
    """x[perm] whose transpose is g[inverse]: a gather both ways."""
    return jnp.take(x, perm, axis=0)


def _permute_fwd(x, perm, inverse):
    return jnp.take(x, perm, axis=0), inverse


def _permute_bwd(inverse, g):
    return jnp.take(g, inverse, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def moe(
    x: jax.Array, router: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array, top_k: int,
    held: Optional[Tuple[int, int]] = None, renormalise: bool = False,
    held_room_sigmas: float = 0.0, **routing: Any,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x [N, D] -> (y [N, D], stats). Every (token, slot) pair is computed:
    `stats["expert_count"]` sums to N * top_k. `routing` is `route`'s `score`,
    `bias`, `epsilon`, `scale`, `groups` and `top_groups`; with a selection
    bias the stats also count the tokens it re-routed (`bias_changed_sum`),
    with a group-limited choice the tokens whose chosen set is not the plain
    top-k of score + bias (`group_changed_sum`).

    `held` = (offset, count) says that `gate`, `up`, `down` hold only the
    experts [offset, offset + count) of the router's E (one expert-parallel
    rank's share): the router still runs over all E and the stats stay over
    all E, but y is the part of the layer's result that the held experts
    give, and only the pairs routed to them are gathered and multiplied
    (`_moe_held`; `held_room_sigmas` widens its chunk for small batches). The
    default holds them all, on the path above."""
    tokens, num_experts = x.shape[0], router.shape[-1]
    with annotate(SCOPES["moe_router"]):
        probs, weights, index = route(x, router, top_k, renormalise, **routing)
        changed = {} if routing.get("bias") is None else {
            "bias_changed_sum": _bias_changed(probs, index)
        }
        if routing.get("groups", 1) > 1:
            bias = routing.get("bias")
            choice = probs if bias is None else probs + bias.astype(jnp.float32)
            changed["group_changed_sum"] = _bias_changed(choice, index)
    if held is not None:
        out, stats = _moe_held(
            x, probs, weights, index, gate, up, down, held, held_room_sigmas
        )
        return out, {**stats, **changed}
    with annotate(SCOPES["moe_dispatch"]):
        flat = index.reshape(-1)  # pair p = token p // k, slot p % k
        order = jnp.argsort(flat, stable=True)  # pairs grouped by expert
        back = jnp.argsort(order)  # where each pair's row went
        rows = _dispatch(x, order, back)  # [N*k, D]
        experts = jnp.arange(num_experts, dtype=flat.dtype)
        counts = jnp.sum(flat[:, None] == experts[None, :], axis=0, dtype=jnp.int32)
    with annotate(SCOPES["moe_experts"]):
        hidden = jax.nn.silu(jax.lax.ragged_dot(rows, gate, counts)) * jax.lax.ragged_dot(
            rows, up, counts
        )
        routed = jax.lax.ragged_dot(hidden, down, counts)  # [N*k, D]
    with annotate(SCOPES["moe_dispatch"]):
        pairs = _permute(routed, back, order).reshape(tokens, top_k, -1)
        out = jnp.sum(pairs * weights[..., None].astype(pairs.dtype), axis=1)
    stats = {
        "expert_index": index,
        "expert_count": counts,
        "router_prob_sum": jnp.sum(probs, axis=0),
        "router_entropy_sum": -jnp.sum(probs * jnp.log(jnp.maximum(probs, 1e-30))),
        **changed,
    }
    return out, stats


def _bias_changed(probs: jax.Array, index: jax.Array) -> jax.Array:
    """Tokens whose chosen set `index` [N, k] is not the top-k of `probs`:
    of the scores alone, what the selection bias re-routed; of score + bias,
    what the group limit did."""
    member = lambda chosen: jnp.any(jax.nn.one_hot(chosen, probs.shape[-1], dtype=bool), axis=-2)
    _, plain = jax.lax.top_k(probs, index.shape[-1])
    return jnp.sum(jnp.any(member(index) != member(plain), axis=-1), dtype=jnp.int32)


class _HeldRows(NamedTuple):
    """Rows [lo, lo + rows) of the held pairs in expert-sorted order."""

    token: jax.Array  # [rows] the token whose row of x each holds
    pair: jax.Array  # [rows] its (token, slot) pair, flat
    valid: jax.Array  # [rows] bool: the row holds a held pair
    sizes: jax.Array  # [held] rows of each held expert inside the chunk


def _held_rows(
    order: jax.Array, ends: jax.Array, lo: jax.Array, rows: int, top_k: int
) -> _HeldRows:
    pair = jax.lax.dynamic_slice_in_dim(order, lo, rows)
    bounds = jnp.clip(ends, lo, lo + rows) - lo
    valid = lo + jnp.arange(rows, dtype=jnp.int32) < ends[-1]
    sizes = jnp.diff(bounds, prepend=0).astype(jnp.int32)
    return _HeldRows(pair // top_k, pair, valid, sizes)


def _held_swiglu_ragged(
    gathered: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array, sizes: jax.Array
) -> jax.Array:
    with annotate(SCOPES["moe_experts"]):
        hidden = jax.nn.silu(jax.lax.ragged_dot(gathered, gate, sizes)) * jax.lax.ragged_dot(
            gathered, up, sizes
        )
        return jax.lax.ragged_dot(hidden, down, sizes)


def held_swiglu_form(rows: int, hidden: int, width: int, count: int) -> str:
    """The form the SwiGLU of `count` held experts `[hidden, width]` takes
    here for a chunk of `rows` rows: `kernel` (`ops/held_swiglu.py`: one
    Pallas pass that streams the weights) or `ragged_dot` (three grouped
    matmuls as XLA compiles them). The kernel only on a TPU; only for a
    chunk of two `_HELD_DECODE_TILE`s at most, where reading the weights is
    all the time there is and as far as it was measured (it multiplies
    every row by every held expert, so its products grow with the rows; the
    update's and the prefill's chunks of whole `_HELD_CHUNK_TILE`s never
    come here); only where its blocks fit (`fits`); and only where the
    grouped-matmul kernel misfits the operands: `width` no whole number of
    `_GROUPED_MATMUL_LANES`, where it reads the weights at 45 to 57% of
    their bytes' pace against 58 to 76% at every width that is one (PERF.md
    section 6, PR 48)."""
    small = rows <= 2 * _HELD_DECODE_TILE and fits(rows, hidden, width, count)
    misfit = width % _GROUPED_MATMUL_LANES != 0
    return "kernel" if jax.default_backend() == "tpu" and small and misfit else "ragged_dot"


def _held_swiglu(
    gathered: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array, sizes: jax.Array
) -> jax.Array:
    """The forward pass's: the form is chosen from the chunk's shape alone."""
    if held_swiglu_form(gathered.shape[0], *gate.shape[1:], gate.shape[0]) == "ragged_dot":
        return _held_swiglu_ragged(gathered, gate, up, down, sizes)
    with annotate(SCOPES["moe_experts"]):
        return held_swiglu_decode(gathered, gate, up, down, sizes).astype(gathered.dtype)


def _chunks(ends: jax.Array, rows: int) -> jax.Array:
    return (ends[-1] + rows - 1) // rows  # chunks that hold a held pair


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _held_experts(x, weights, gate, up, down, order, slot, ends, rows):
    """The held experts' part of the layer: x [N, D], weights [N, k] ->
    [N, D]. `order[r]` is the pair in row r of the held pairs sorted by
    expert, `slot[t, j]` the row of pair (t, j) (past the held total for an
    absent pair), `ends` the cumulative held counts. A loop over chunks of
    `rows` rows, as many turns as the held pairs fill: the cost follows the
    pairs that landed here and nothing is dropped however the router skews.
    A chunk's rows are gathered from their tokens and added back to them by
    one scatter-add (a third of the time of a gather a slot, PERF.md §6,
    PR 31). A loop of data-dependent length has no reverse of its own: the
    backward pass below is the same loop, each chunk recomputed (nothing but
    the inputs is kept)."""
    top_k, flat_weights = weights.shape[1], weights.reshape(-1)

    def body(carry):
        i, out = carry
        lo = i * rows
        with annotate(SCOPES["moe_dispatch"]):
            at = _held_rows(order, ends, lo, rows, top_k)
            gathered = jnp.take(x, at.token, axis=0)  # [rows, D]
        routed = _held_swiglu(gathered, gate, up, down, at.sizes)
        with annotate(SCOPES["moe_dispatch"]):
            # Rows past the last group belong to no expert: whatever the
            # kernel left there is not a result.
            weighted = jnp.where(
                at.valid[:, None], routed * jnp.take(flat_weights, at.pair)[:, None], 0.0
            )
            out = out.at[at.token].add(weighted)
        return i + 1, out

    chunks = _chunks(ends, rows)
    _, out = jax.lax.while_loop(lambda c: c[0] < chunks, body, (jnp.int32(0), jnp.zeros_like(x)))
    return out


def _held_experts_fwd(x, weights, gate, up, down, order, slot, ends, rows):
    out = _held_experts(x, weights, gate, up, down, order, slot, ends, rows)
    return out, (x, weights, gate, up, down, order, slot, ends)


def _held_experts_bwd(rows, residuals, g):
    x, weights, gate, up, down, order, slot, ends = residuals
    top_k, flat_weights = weights.shape[1], weights.reshape(-1)

    def body(carry):
        i, (dx, dweights, dgate, dup, ddown) = carry
        lo = i * rows
        with annotate(SCOPES["moe_dispatch"]):
            at = _held_rows(order, ends, lo, rows, top_k)
            gathered = jnp.take(x, at.token, axis=0)
            g_rows = jnp.where(at.valid[:, None], jnp.take(g, at.token, axis=0), 0.0)
        routed, vjp = jax.vjp(
            lambda *operands: _held_swiglu_ragged(*operands, at.sizes), gathered, gate, up, down
        )
        with annotate(SCOPES["moe_dispatch"]):
            d_weight_rows = jnp.sum(jnp.where(at.valid[:, None], routed, 0.0) * g_rows, axis=-1)
            d_routed = g_rows * jnp.take(flat_weights, at.pair)[:, None]
        d_gathered, d_gate, d_up, d_down = vjp(d_routed)
        with annotate(SCOPES["moe_dispatch"]):
            dx = dx.at[at.token].add(jnp.where(at.valid[:, None], d_gathered, 0.0))
            rows_of = slot - lo  # [N, k]: each pair's own weight gradient
            here = (rows_of >= 0) & (rows_of < rows) & (slot < ends[-1])
            dweights = dweights + jnp.where(
                here, jnp.take(d_weight_rows, jnp.clip(rows_of, 0, rows - 1)), 0.0
            )
        return i + 1, (dx, dweights, dgate + d_gate, dup + d_up, ddown + d_down)

    chunks = _chunks(ends, rows)
    zeros = tuple(jnp.zeros_like(f) for f in (x, weights, gate, up, down))
    _, grads = jax.lax.while_loop(lambda c: c[0] < chunks, body, (jnp.int32(0), zeros))
    return (*grads, None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)

# A chunk of the held pairs' loop is this many times the pairs that uniform
# routing would land on the held experts: one turn in the common case.
_HELD_CHUNK_ROOM = 1.25
# A large chunk is a whole number of the grouped-matmul kernel's row tiles:
# 30,800 rows take eight times as long as 31,232 (PERF.md §6, PR 31).
_HELD_CHUNK_TILE = 512
# A small chunk asked for by `room_sigmas` is a whole number of these: on the
# v5e one decode step of 8 held experts (2048 x 1792) takes 0.63 ms at 192
# rows, 0.73 at 160 and 1.37 at 200; 0.53 at 64, 0.67 at 40 and at 72
# (PERF.md §6, PR 33).
_HELD_DECODE_TILE = 64
# The lanes of `width` the grouped-matmul kernel wants in whole numbers at
# such a chunk: on the v5e one decode step's three `ragged_dot`s read the
# float32 weights they reach at 58 to 76% of HBM's pace at widths 512, 768 and
# 1792, at 54 to 57% at [8, 2048, 896] and at 45 to 49% at [8, 2304, 896]
# (896 = 3.5 x 256; hidden 2304 = 4.5 x 512 alone, at width 768, reads 66 to
# 76%). There a chunk of up to two `_HELD_DECODE_TILE`s goes through
# `ops/held_swiglu.py` instead: 0.269 ms a call against 0.434 at [8, 2304,
# 896], 0.237 against 0.324 at [8, 2048, 896] and, at 128 rows, 0.239 against
# 0.430 (`held_swiglu_form`; PERF.md section 6, PR 48).
_GROUPED_MATMUL_LANES = 256


def held_chunk_rows(
    tokens: int, top_k: int, count: int, num_experts: int, room_sigmas: float = 0.0
) -> int:
    """Rows of one chunk of the held pairs' loop for `tokens` tokens choosing
    `top_k` of `num_experts` experts of which `count` are held (`_moe_held`
    says how it is sized)."""
    expected = tokens * top_k * count / num_experts
    small = _HELD_DECODE_TILE if room_sigmas else 8
    tile = _HELD_CHUNK_TILE if expected >= 8 * _HELD_CHUNK_TILE else small
    room = max(_HELD_CHUNK_ROOM * expected, expected + room_sigmas * expected**0.5)
    return min(tokens * top_k, -(-int(room) // tile) * tile)


def _moe_held(
    x: jax.Array, probs: jax.Array, weights: jax.Array, index: jax.Array,
    gate: jax.Array, up: jax.Array, down: jax.Array, held: Tuple[int, int],
    room_sigmas: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The held experts' part of the layer's result. Of the N * k routed
    pairs only the integer keys are ranked; rows of x are gathered, and
    multiplied, for the pairs that land on [offset, offset + count) alone.
    A chunk has room for `_HELD_CHUNK_ROOM` times the pairs uniform routing
    lands here. `room_sigmas` sizes it for a decode step's few pairs: room
    for at least so many standard deviations of that count above it (a
    binomial's is at most the root of its mean; a quarter more is 1.6
    deviations at 32 expected pairs, and every second turn reads the held
    experts' weights again), in whole `_HELD_DECODE_TILE`s of rows."""
    offset, count = held
    tokens, top_k = index.shape
    num_experts = probs.shape[-1]
    with annotate(SCOPES["moe_dispatch"]):
        local = index.reshape(-1) - offset
        key = jnp.where((local >= 0) & (local < count), local, count)  # absent pairs last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)  # held pairs grouped by expert
        slot = jnp.argsort(order).astype(jnp.int32).reshape(tokens, top_k)
        experts = jnp.arange(num_experts, dtype=index.dtype)
        counts = jnp.sum(index.reshape(-1)[:, None] == experts[None, :], axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(jax.lax.dynamic_slice_in_dim(counts, offset, count))
    rows = held_chunk_rows(tokens, top_k, count, num_experts, room_sigmas)
    # The last chunk may reach past the pairs: a slice that does is moved, not cut.
    order = jnp.concatenate([order, jnp.zeros((rows,), jnp.int32)])
    out = _held_experts(x, weights.astype(x.dtype), gate, up, down, order, slot, ends, rows)
    stats = {
        "expert_index": index,
        "expert_count": counts,
        "router_prob_sum": jnp.sum(probs, axis=0),
        "router_entropy_sum": -jnp.sum(probs * jnp.log(jnp.maximum(probs, 1e-30))),
    }
    return out, stats


class OlmoeLayer(nn.Module):
    hidden_size: int
    num_heads: int
    head_dim: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    rope_theta: float
    rms_eps: float

    def setup(self) -> None:
        init = nn.initializers.normal(0.02)
        ones = nn.initializers.ones
        d, e, f = self.hidden_size, self.num_experts, self.expert_width
        proj = self.num_heads * self.head_dim
        self.input_norm = self.param("input_norm", ones, (d,))
        self.wq = self.param("wq", init, (d, proj))
        self.wk = self.param("wk", init, (d, proj))
        self.wv = self.param("wv", init, (d, proj))
        self.wo = self.param("wo", init, (proj, d))
        self.q_norm = self.param("q_norm", ones, (proj,))
        self.k_norm = self.param("k_norm", ones, (proj,))
        self.post_attn_norm = self.param("post_attn_norm", ones, (d,))
        self.router = self.param("router", init, (d, e))
        self.gate = self.param("gate", init, (e, d, f))
        self.up = self.param("up", init, (e, d, f))
        self.down = self.param("down", init, (e, f, d))

    def _qkv(self, x: jax.Array, positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """x [..., D], positions [...] -> q, k, v [..., heads, head_dim]; q
        and k normalised over the whole projection, then rotated."""
        heads = lambda t: t.reshape(t.shape[:-1] + (self.num_heads, self.head_dim))
        q = heads(rms_norm(x @ self.wq, self.q_norm, self.rms_eps))
        k = heads(rms_norm(x @ self.wk, self.k_norm, self.rms_eps))
        rotate = lambda t: rope(t, positions, self.rope_theta)
        return rotate(q), rotate(k), heads(x @ self.wv)

    def _moe(self, h: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        flat = rms_norm(h, self.post_attn_norm, self.rms_eps).reshape(-1, self.hidden_size)
        with annotate(SCOPES["moe"]):
            routed, stats = moe(
                flat, self.router, self.gate, self.up, self.down, self.experts_per_token
            )
        return h + routed.reshape(h.shape), stats

    def forward(self, x: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Teacher-forced: x [B, T, D], positions 0..T-1."""
        batch, length, _ = x.shape
        with annotate(SCOPES["attention"]):
            positions = jnp.broadcast_to(jnp.arange(length), (batch, length))
            q, k, v = self._qkv(rms_norm(x, self.input_norm, self.rms_eps), positions)
            attended = best_attention(q, k, v, causal=True)  # [B, T, heads, head_dim]
            attended = attended.reshape(batch, length, -1)
            h = x + attended @ self.wo
        return self._moe(h)

    def step(
        self, x: jax.Array, cache_k: jax.Array, cache_v: jax.Array, length: jax.Array
    ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        """One token a sequence: x [B, D], this layer's cache [S, B, heads,
        head_dim], `length` [B] or [] the token's position."""
        batch = x.shape[0]
        with annotate(SCOPES["attention"]):
            positions = jnp.broadcast_to(length, (batch,))
            q, k, v = self._qkv(rms_norm(x, self.input_norm, self.rms_eps), positions)
            cache_k, cache_v = write_cache_rows(cache_k, cache_v, k, v, length)
            attended = _attend_cache(q, cache_k, cache_v, length)
            h = x + attended.reshape(batch, -1) @ self.wo
        y, stats = self._moe(h)
        return y, cache_k, cache_v, stats


def _attend_cache(
    q: jax.Array, cache_k: jax.Array, cache_v: jax.Array, length: jax.Array
) -> jax.Array:
    """softmax(q k^T / sqrt(head_dim)) v over cache positions <= `length`
    ([B] or [], the position just written). Only the leading blocks that hold a
    live position are read. q is [B, heads, head_dim] against a cache of as
    many heads, or grouped [B, kv_heads, queries a kv head, head_dim] against
    a cache [S, B, kv_heads, head_dim] (grouped-query attention: the cache is
    read once for the group)."""
    max_len, head_dim = cache_k.shape[0], q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(head_dim))
    grouped = q.ndim == 4

    def over(prefix: int):
        def attend(q, cache_k, cache_v, length):
            # One query a sequence: a matrix-vector product a head, bound by
            # reading the cache. Written as multiply-and-reduce so that XLA
            # streams the float32 cache once, instead of first writing a
            # bfloat16 copy of it in the MXU's layout for a dot.
            keys, values = cache_k[:prefix], cache_v[:prefix]
            if grouped:
                keys, values = keys[:, :, :, None], values[:, :, :, None]
            scores = jnp.sum(q[None] * keys, axis=-1) * scale  # [prefix, B, heads]
            last = jnp.broadcast_to(length, q.shape[:1])
            live = jnp.arange(prefix)[:, None, None] <= last[None, :, None]
            if grouped:
                live = live[..., None]
            scores = jnp.where(live, scores, jnp.finfo(jnp.float32).min)
            weights = jax.nn.softmax(scores, axis=0)
            return jnp.sum(weights[..., None] * values, axis=0)  # [B, heads, head_dim]

        return attend

    prefixes = list(range(_CACHE_BLOCK, max_len, _CACHE_BLOCK)) + [max_len]
    if len(prefixes) == 1:
        return over(max_len)(q, cache_k, cache_v, length)
    blocks = jnp.max(length) // _CACHE_BLOCK  # index of the last live block
    return jax.lax.switch(
        jnp.minimum(blocks, len(prefixes) - 1), [over(p) for p in prefixes],
        q, cache_k, cache_v, length,
    )


class OlmoeLM(nn.Module):
    """Embedding, `num_layers` OLMoE blocks, final norm, untied head. Both
    entry points return (logits [.., V] un-normalised, hidden [.., D] after
    the final norm, stats with a leading layer axis)."""

    vocab_size: int
    hidden_size: int
    num_heads: int
    head_dim: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    num_layers: int = 1
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5

    def setup(self) -> None:
        init = nn.initializers.normal(0.02)
        self.embed = self.param("embed", init, (self.vocab_size, self.hidden_size))
        self.layers = [
            OlmoeLayer(
                self.hidden_size, self.num_heads, self.head_dim, self.num_experts,
                self.experts_per_token, self.expert_width, self.rope_theta, self.rms_eps,
                name=f"layer_{i}",
            )
            for i in range(self.num_layers)
        ]
        self.final_norm = self.param("final_norm", nn.initializers.ones, (self.hidden_size,))
        self.lm_head = self.param("lm_head", init, (self.hidden_size, self.vocab_size))

    # What a system asks of a token policy beside its two entry points: the
    # decode carry, the layers with a router, and the experts held here
    # (None: all of them).
    held = None

    @property
    def routed_layers(self) -> int:
        return int(self.num_layers)

    @nn.nowrap
    def init_carry(self, batch: int, max_len: int, together: bool = False) -> KVCache:
        """`together`: the caller's sequences all start and end at once, so
        the carry holds one position for all of them."""
        return init_cache(
            self.num_layers, batch, max_len, self.num_heads, self.head_dim, together
        )

    @nn.nowrap
    def reset_carry(self, cache: KVCache, done: jax.Array) -> KVCache:
        return reset_cache(cache, done)

    @nn.nowrap
    def carry_bytes(self, batch: int, max_len: int) -> Dict[str, int]:
        cache = jax.eval_shape(lambda: self.init_carry(batch, max_len))
        return {"kv": sum(x.size * x.dtype.itemsize for x in cache.k + cache.v)}

    def _head(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        with annotate(SCOPES["lm_head"]):
            hidden = rms_norm(x, self.final_norm, self.rms_eps)
            return hidden @ self.lm_head, hidden

    def forward(self, tokens: jax.Array) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
        x = jnp.take(self.embed, tokens, axis=0)
        stats = []
        for layer in self.layers:
            x, layer_stats = layer.forward(x)
            stats.append(layer_stats)
        logits, hidden = self._head(x)
        return logits, hidden, _stack(stats)

    def step(
        self, cache: KVCache, token: jax.Array
    ) -> Tuple[jax.Array, jax.Array, KVCache, Dict[str, jax.Array]]:
        x = jnp.take(self.embed, token, axis=0)
        keys, values, stats = [], [], []
        for i, layer in enumerate(self.layers):
            x, cache_k, cache_v, layer_stats = layer.step(x, cache.k[i], cache.v[i], cache.length)
            keys.append(cache_k)
            values.append(cache_v)
            stats.append(layer_stats)
        logits, hidden = self._head(x)
        cache = KVCache(tuple(keys), tuple(values), cache.length + 1)
        return logits, hidden, cache, _stack(stats)


def _stack(stats: list) -> Dict[str, jax.Array]:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stats)


class ValueHead(nn.Module):
    """Scalar value on the trunk's final-norm hidden state."""

    @nn.compact
    def __call__(self, hidden: jax.Array) -> jax.Array:
        kernel = self.param("kernel", nn.initializers.normal(0.02), (hidden.shape[-1], 1))
        bias = self.param("bias", nn.initializers.zeros, (1,))
        return (hidden @ kernel)[..., 0] + bias[0]


def load_balancing_loss(stats: Dict[str, Any], num_tokens: int) -> jax.Array:
    """The HF `load_balancing_loss_func`: num_experts * sum_e (share of the
    (token, slot) pairs of all layers routed to e, summed over slots) * (mean
    router probability of e). `stats` leaves carry a leading layer axis."""
    layers, num_experts = stats["expert_count"].shape
    total = layers * num_tokens
    routed_share = jnp.sum(stats["expert_count"], axis=0).astype(jnp.float32) / total
    mean_prob = jnp.sum(stats["router_prob_sum"], axis=0) / total
    return num_experts * jnp.sum(routed_share * mean_prob)
