"""The LFM2 mixture-of-experts decoder as a token policy: a stack whose layers
differ — gated short convolutions beside grouped-query attention, two dense
feed-forwards and then routed ones — with one decode carry over both kinds of
state; one expert-parallel rank's share of each routed layer and of the
vocabulary. Three entry points over ONE set of parameters: networks/olmoe.py's
two, and `prefill` for a sequence that starts with a prompt.

Published layer (`model_type` `lfm2_moe`,
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json;
stoix_tpu/reference/lfm2.py writes it out plainly and is what the tests and
the benchmark compare this file with). Every layer l, no bias anywhere:

    h = h + mixer_l(RMSNorm_op(h));   h = h + ffn_l(RMSNorm_ffn(h))

  * `conv` mixer (`ShortConv`): [B ; C ; X] = u W_in; z = B * X; c_t =
    sum_j w_j * z_{t-K+1+j} (depthwise, causal, K = `conv_kernel`, z before
    the sequence is 0); (C * c) W_out. Its decode state is the last K - 1
    rows of z a sequence (`ConvTail`), not keys and values.
  * `full_attention` mixer (`GroupedQueryAttention`): networks/sdar.py's
    grouped-query projections with a per-head q/k RMSNorm and rotate-half
    RoPE; causal softmax. Its decode state is the keys and values (`KV`).
  * `latent_attention` (networks/mla.py) and `delta_attention`
    (networks/kda.py) mixers are other models' (Kanana-2; Ling-3.0): a
    compressed row a position (`Latent`), and a matrix a head that every
    token rewrites with three convolutions' tails (`DeltaState`).
  * `sliding_attention` is another's too (Laguna): the same
    `GroupedQueryAttention` with `window` = `sliding_window` W: query t sees
    the keys 0 <= t - j < W. Its decode state is a RING of W rows a sequence
    (`WindowKV`: position t's row at t % W), not `max_len`. Beside it that
    model gives a layer its own head count (`num_heads_per_layer`), a layer
    KIND its own rotation (`rope_parameters`: theta, the rotated part of a
    head, YaRN) and every attention layer a sigmoid gate a head before W_o
    (`attention_gate`). Another (Mellum2) runs the two kinds at ONE head count
    with no gate, its window 1,024 and its period ending with the full layer.
  * feed-forward of the first `num_dense_layers` layers (`DenseMLP`): one
    SwiGLU of width `dense_width`; of the others (`RoutedMLP`): float32
    scores over ALL `num_experts` — `router_scoring` `sigmoid`, each expert
    by itself (this model and three more), or `softmax` over all of them
    (Mellum2) —, the top-k CHOSEN by score + `expert_bias` (by the score
    alone where `router_selection_bias` is false: such a router has no
    `expert_bias` leaf), WEIGHTED by the scores themselves at the chosen
    experts over (their sum + `router_epsilon`, 1e-6 here), times
    `routed_scaling_factor`; SwiGLU experts of width `expert_width`, no
    shared expert (networks/olmoe.py::moe).
  * final RMSNorm; the head is the embedding's transpose (tied).

The chip's share: `experts_held` experts from `expert_offset` on are here
and `vocab_size` is the slice of the vocabulary held here; mixers, dense
layers and the router are whole. What the absent experts would add is left
out of the layer's result, and that partial result goes on to the next
layer. Nothing stands in for the other ranks or their exchange.

The stack is data: `layer_types` names each layer's mixer and
`num_dense_layers` says which feed-forwards are dense. A `Block` is a mixer,
a feed-forward and their two norms; a mixer has `forward` (whole sequence)
and `step` (one token against its state), and the two attention kinds also
`prefill` (whole prefix, keeping the state it leaves).

  * `Lfm2LM.forward(tokens [B, T])` — teacher-forced.
  * `Lfm2LM.step(carry, token [B])` — one decode step. `Lfm2Carry` holds one
    state a layer, of its mixer's kind, and `length` [B], each sequence's
    next position, or [] where the caller asked `init_carry(..., together=
    True)` because its sequences move together (networks/olmoe.py).
    `init_carry(batch, max_len)` and `reset_carry(carry, done)` are the
    network's own: a new sequence starts at length 0 with a zero tail; cache
    entries at or beyond `length` are never read.
  * `Lfm2LM.forward(tokens, n)` — the same pass with logits and hidden of
    the LAST n positions alone (a response after its prompt): the head is a
    function of a position, and 3,072 prefix positions of 12,288 logits a
    sequence are computed for nothing otherwise.
  * `Lfm2LM.prefill(carry, tokens [B, P])` — `forward`'s pass over a prefix
    (the causal and the banded attention's forward) that also KEEPS each
    attention layer's rotated keys and values as its decode state — a full
    layer's rows 0 .. P - 1 of its cache, a window layer's LAST min(P, W)
    positions at their ring places (position t at t % W, whatever P is to W)
    — and returns the carry at length P with the routed layers' stats; no
    head and no value. Every prefix is P long, so a carry of one position
    for all sequences stays one. A `conv`, `latent_attention` or
    `delta_attention` layer has no prefill yet and says so by name.

Parameters, by name (the reference reads them by these names):
  embed [V, D]; layer_<i>/{operator_norm [D], ffn_norm [D], mixer/{in_proj
  [D, 3D], conv [K, D], out_proj [D, D]} or mixer/{wq [D, H*hd], wk wv [D,
  KV*hd], wo [H*hd, D], q_norm k_norm [hd]}, ffn/{w1 w3 [D, F], w2 [F, D]} or
  ffn/{router [D, E], expert_bias [E] (where the router has a selection bias),
  gate up [held, D, Fm], down [held, Fm, D]}}; final_norm [D]; lm_head [D, V]
  where the head is untied.
Initialisation is normal(0.02), norms start at one; `expert_bias` is a seeded
normal(`expert_bias_scale`) buffer that only the CHOICE of experts reads, so
it takes no gradient and an optimiser step leaves it as it was. The embedding
alone takes another deviation where `embedding_init_std` says so: the first
RMSNorm brings a 0.02 row to unit size, but the residual keeps it at 0.02 an
entry beside an attention result of 0.17 (eight query heads a key/value head
add up coherently through W_o: a gain of 3.5), so behind a prefix of
thousands of tokens every later layer reads the context's mean, all
positions of a sequence choose the same experts, and how many of them are
HELD here is one draw a (sequence, layer). A deviation of 1.0 keeps the
token's own row the larger part of the residual (the benchmark's
prefilled-prompt cell sets it; PERF.md section 6, PR 47).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from stoix_tpu.networks.kda import DeltaState, KimiDeltaAttention
from stoix_tpu.networks.mla import Latent, LatentAttention
from stoix_tpu.networks.olmoe import (
    Yarn, _attend_cache, _stack, held_chunk_rows, held_swiglu_form, init_length, moe,
    reset_length, rms_norm, write_cache_rows,
)
from stoix_tpu.networks.sdar import gqa_qkv
from stoix_tpu.observability import SCOPES, annotate, get_registry
from stoix_tpu.ops.pallas_attention import best_attention, gqa_decode_attention

_INIT = nn.initializers.normal(0.02)
# Room of the held experts' chunk above the pairs uniform routing lands here,
# in standard deviations of that count: the evaluator's 32 sequences land 32
# +- 5 pairs on 8 of 32 experts, and at a quarter's room one decode step in
# twenty took a second turn, each a second read of the held experts' weights:
# a per cent of a window, swinging with the seed's routing (PERF.md §6, PR 33).
# Five deviations are 64 rows for those 32 pairs and 192 for the rollout's 128.
_HELD_ROOM_SIGMAS = 5.0


class ConvTail(NamedTuple):
    z: jax.Array  # [B, K - 1, D] the gated input's last rows, oldest first


class KV(NamedTuple):
    # Position-major as networks/olmoe.py's: a prefix of positions is one slab.
    k: jax.Array  # [S, B, kv_heads, head_dim] float32
    v: jax.Array


class WindowKV(NamedTuple):
    # A ring: position t's row lies at t % W, and row i is live while i <=
    # min(t, W - 1). Position-major as `KV`.
    k: jax.Array  # [W, B, kv_heads, head_dim] float32
    v: jax.Array


class Lfm2Carry(NamedTuple):
    # a layer: its mixer's state, ConvTail, KV, WindowKV, Latent or DeltaState
    layers: Tuple[Any, ...]
    length: jax.Array  # [B] or [] int32: positions filled = the next token's position


class ShortConv(nn.Module):
    """The gated short convolution. Input: the operator-normed hidden state."""

    hidden_size: int
    kernel: int
    trace_scope = "conv_mixer"

    def setup(self) -> None:
        d = self.hidden_size
        self.in_proj = self.param("in_proj", _INIT, (d, 3 * d))
        self.conv = self.param("conv", _INIT, (self.kernel, d))
        self.out_proj = self.param("out_proj", _INIT, (d, d))

    def forward(self, u: jax.Array) -> jax.Array:
        """u [B, T, D]: every position sees its own z and the K - 1 before."""
        length = u.shape[1]
        b, c, x = jnp.split(u @ self.in_proj, 3, axis=-1)
        with annotate(SCOPES["conv_mixer_conv"]):
            z = jnp.pad(b * x, ((0, 0), (self.kernel - 1, 0), (0, 0)))
            mixed = sum(self.conv[j] * z[:, j:j + length] for j in range(self.kernel))
            gated = c * mixed
        return gated @ self.out_proj

    def step(self, u: jax.Array, state: ConvTail, length: jax.Array):
        """u [B, D] against the tail; the tail moves on by one row."""
        b, c, x = jnp.split(u @ self.in_proj, 3, axis=-1)
        with annotate(SCOPES["conv_mixer_conv"]):
            window = jnp.concatenate([state.z, (b * x)[:, None]], axis=1)  # [B, K, D]
            gated = c * jnp.sum(self.conv * window, axis=1)
            state = ConvTail(window[:, 1:])
        return gated @ self.out_proj, state


def attend_rows(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array, last: jax.Array) -> jax.Array:
    """softmax(q k^T / sqrt(head_dim)) v over the rows <= `last` ([B] or []) of
    a growing cache or a ring [S, B, kv_heads, head_dim]: grouped queries [B,
    kv_heads, group, head_dim] -> the same shape. On a TPU, for heads of whole
    lane groups and rows in whole blocks of 128, the Pallas kernel
    `gqa_decode_attention` (a sequence's block read as whole tiles: one
    product with the keys and one with the values for all its heads, under a
    head mask, on the MXU — 0.195 ms a step for a ring of 512 rows where a
    product pair a key/value head took 0.222, PERF.md section 6, PR 45);
    else `_attend_cache`, whose multiply-and-reduce is written for the one to
    four queries a row of 64 the other stacks have (PERF.md section 6, PR 44)."""
    if jax.default_backend() == "tpu" and q.shape[3] % 128 == 0 and cache_k.shape[0] % 128 == 0:
        return gqa_decode_attention(q, cache_k, cache_v, jnp.broadcast_to(last, q.shape[:1]))
    return _attend_cache(q, cache_k, cache_v, last)


def _window_form_gauge():
    return get_registry().gauge(
        "stoix_tpu_window_attend_update",
        "1 on the form a window layer's teacher-forced attention was most recently traced in, 0 "
        "on the other: banded (the flash kernel pair, key tiles before the band not visited) or "
        "masked (every causal pair multiplied, those outside the band masked)",
    )


class GroupedQueryAttention(nn.Module):
    """Causal grouped-query attention with a per-head q/k RMSNorm and RoPE.
    Input: the operator-normed hidden state. With `window` W a query sees the
    W newest keys alone and the decode state is a ring of W rows (`WindowKV`);
    with `gate` a sigmoid gate a head, from the same input, multiplies the
    heads' results before W_o; `rotary_dim` and `yarn` are `rope`'s."""

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    rms_eps: float
    window: Optional[int] = None
    gate: bool = False
    rotary_dim: Optional[int] = None
    yarn: Optional[Yarn] = None

    @property
    def trace_scope(self) -> str:
        return "window_mixer" if self.window else "attention"

    @property
    def attend_scope(self) -> str:
        return "window_attend" if self.window else "attention_scores"

    def setup(self) -> None:
        d, q_width = self.hidden_size, self.num_heads * self.head_dim
        kv_width = self.num_kv_heads * self.head_dim
        ones = nn.initializers.ones
        self.wq = self.param("wq", _INIT, (d, q_width))
        self.wk = self.param("wk", _INIT, (d, kv_width))
        self.wv = self.param("wv", _INIT, (d, kv_width))
        self.wo = self.param("wo", _INIT, (q_width, d))
        self.q_norm = self.param("q_norm", ones, (self.head_dim,))
        self.k_norm = self.param("k_norm", ones, (self.head_dim,))
        if self.gate:
            self.wg = self.param("wg", _INIT, (d, self.num_heads))

    def _qkv(self, u: jax.Array, positions: jax.Array):
        layer = {
            "wq": self.wq, "wk": self.wk, "wv": self.wv, "q_norm": self.q_norm,
            "k_norm": self.k_norm,
        }
        return gqa_qkv(
            layer, u, positions, self.num_heads, self.num_kv_heads, self.head_dim,
            self.rope_theta, self.rms_eps, self.rotary_dim, self.yarn,
        )

    def _gated(self, attended: jax.Array, u: jax.Array) -> jax.Array:
        """attended [..., H, head_dim] times sigmoid(u W_g) a head, where `gate`."""
        return attended * jax.nn.sigmoid(u @ self.wg)[..., None] if self.gate else attended

    def _sequence(self, u: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """u [B, T, D] from position 0 on -> (the mixer's result [B, T, D], the
        rotated keys and the values [B, T, kv_heads, head_dim])."""
        batch, length, _ = u.shape
        q, k, v = self._qkv(u, jnp.broadcast_to(jnp.arange(length), (batch, length)))
        # `best_attention` (the Pallas flash kernel on a TPU) takes as many
        # key/value heads as query heads: each is repeated for its group.
        group = self.num_heads // self.num_kv_heads
        keys, values = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        if self.window:
            banded = jax.default_backend() == "tpu" and self.window < length
            for form, took in (("banded", banded), ("masked", not banded)):
                _window_form_gauge().set(float(took), {"form": form})
        with annotate(SCOPES[self.attend_scope]):
            # [B, T, heads, head_dim]
            attended = best_attention(q, keys, values, causal=True, window=self.window)
        return self._gated(attended, u).reshape(batch, length, -1) @ self.wo, k, v

    def forward(self, u: jax.Array) -> jax.Array:
        return self._sequence(u)[0]

    def prefill(self, u: jax.Array, state: Any):
        """`forward` over a prefix u [B, P, D] that also KEEPS its rotated keys
        and its values as the decode state of an empty `state`: a `KV`'s rows
        0 .. P - 1; of a `WindowKV` of W rows the LAST min(P, W) positions,
        position t at t % W as `step` writes them, whatever P is to W."""
        length, size = u.shape[1], state.k.shape[0]
        out, k, v = self._sequence(u)
        if not self.window and length > size:
            raise ValueError(f"a prefix of {length} positions does not fit a cache of {size} rows")

        def rows(x: jax.Array) -> jax.Array:
            x = jnp.swapaxes(x, 0, 1)  # position-major, as the state
            if length < size:
                return x
            # The newest `size` positions, the oldest of them at ITS place.
            return jnp.roll(x[length - size:], (length - size) % size, axis=0)

        write = lambda cache, new: jax.lax.dynamic_update_slice(cache, rows(new), (0, 0, 0, 0))
        return out, type(state)(write(state.k, k), write(state.v, v))

    def step(self, u: jax.Array, state: Any, length: jax.Array):
        """u [B, D] against a `KV` of `max_len` rows or, with `window`, a
        `WindowKV`: the new row goes to `length % W` and the live rows are
        those up to min(length, W - 1), in whatever order the ring holds them
        (keys are rotated at their own positions when written)."""
        batch = u.shape[0]
        q, k, v = self._qkv(u, jnp.broadcast_to(length, (batch,)))
        at, last = length, length
        if self.window:
            at, last = length % self.window, jnp.minimum(length, self.window - 1)
        state = type(state)(*write_cache_rows(state.k, state.v, k, v, at))
        grouped = q.reshape(batch, self.num_kv_heads, -1, self.head_dim)
        with annotate(SCOPES[self.attend_scope]):
            attended = attend_rows(grouped, state.k, state.v, last)
        if self.gate:
            attended = self._gated(attended.reshape(batch, self.num_heads, self.head_dim), u)
        return attended.reshape(batch, -1) @ self.wo, state


class DenseMLP(nn.Module):
    """(silu(f W_1) * f W_3) W_2 on f [N, D]; no router, so no stats."""

    hidden_size: int
    width: int
    trace_scope: str = "dense_mlp"  # `shared_expert` where it stands beside routed experts

    def setup(self) -> None:
        d, f = self.hidden_size, self.width
        self.w1 = self.param("w1", _INIT, (d, f))
        self.w3 = self.param("w3", _INIT, (d, f))
        self.w2 = self.param("w2", _INIT, (f, d))

    def __call__(self, f: jax.Array) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
        with annotate(SCOPES[self.trace_scope]):
            return (jax.nn.silu(f @ self.w1) * (f @ self.w3)) @ self.w2, None


class RoutedMLP(nn.Module):
    """The held experts' part of the routed layer on f [N, D] — scored as
    `score` says (`sigmoid`: each expert by itself; `softmax`: over all of
    them), the top-k chosen by score + `expert_bias` where the router has a
    `selection_bias` (else by the score alone, and there is no such leaf) —
    plus, with `shared_width`, the shared expert every token passes (a
    `DenseMLP` under `shared`): what every rank computes alike, so the ranks'
    parts add up to the uncut layer with it counted once."""

    hidden_size: int
    num_experts: int  # the router's width: every expert of the layer
    experts_held: int  # of which this rank holds so many,
    expert_offset: int  # from this one on
    experts_per_token: int
    width: int
    scaling_factor: float
    bias_scale: float
    epsilon: float = 1e-6  # joins the chosen scores' sum the weights are divided by
    shared_width: int = 0
    groups: int = 1  # `n_group`: the experts lie in so many groups, of which the
    top_groups: int = 1  # `topk_group` best are open to a token's choice
    score: str = "sigmoid"  # or "softmax"
    selection_bias: bool = True

    def setup(self) -> None:
        d, e, held, f = self.hidden_size, self.num_experts, self.experts_held, self.width
        self.router = self.param("router", _INIT, (d, e))
        if self.selection_bias:
            self.expert_bias = self.param(
                "expert_bias", nn.initializers.normal(self.bias_scale), (e,)
            )
        self.gate = self.param("gate", _INIT, (held, d, f))
        self.up = self.param("up", _INIT, (held, d, f))
        self.down = self.param("down", _INIT, (held, f, d))
        if self.shared_width:
            self.shared = DenseMLP(d, self.shared_width, trace_scope="shared_expert")

    def __call__(self, f: jax.Array) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
        with annotate(SCOPES["moe"]):
            out, stats = moe(
                f, self.router, self.gate, self.up, self.down, self.experts_per_token,
                held=(self.expert_offset, self.experts_held), renormalise=True,
                held_room_sigmas=_HELD_ROOM_SIGMAS, score=self.score,
                bias=self.expert_bias if self.selection_bias else None,
                epsilon=self.epsilon, scale=self.scaling_factor, groups=self.groups,
                top_groups=self.top_groups,
            )
        if self.shared_width:
            out = out + self.shared(f)[0]
        return out, stats


class Block(nn.Module):
    """h + mixer(norm(h)), then h + ffn(norm(h)); -> (h, the router's stats
    or None). The mixer's scope covers its norm and its state's write."""

    mixer: nn.Module
    ffn: nn.Module
    hidden_size: int
    rms_eps: float

    def setup(self) -> None:
        ones = nn.initializers.ones
        self.operator_norm = self.param("operator_norm", ones, (self.hidden_size,))
        self.ffn_norm = self.param("ffn_norm", ones, (self.hidden_size,))

    def _ffn(self, h: jax.Array):
        normed = rms_norm(h, self.ffn_norm, self.rms_eps).reshape(-1, self.hidden_size)
        out, stats = self.ffn(normed)
        return h + out.reshape(h.shape), stats

    def forward(self, x: jax.Array):
        with annotate(SCOPES[self.mixer.trace_scope]):
            h = x + self.mixer.forward(rms_norm(x, self.operator_norm, self.rms_eps))
        return self._ffn(h)

    def prefill(self, x: jax.Array, state: Any):
        """`forward` over a prefix that also fills the mixer's empty `state`."""
        if not hasattr(self.mixer, "prefill"):
            raise NotImplementedError(
                f"a {type(self.mixer).__name__} mixer has no prefill yet: a prompt is written "
                "into the decode state of full_attention and sliding_attention layers only"
            )
        with annotate(SCOPES[self.mixer.trace_scope]):
            mixed, state = self.mixer.prefill(rms_norm(x, self.operator_norm, self.rms_eps), state)
            h = x + mixed
        return (*self._ffn(h), state)

    def step(self, x: jax.Array, state: Any, length: jax.Array):
        with annotate(SCOPES[self.mixer.trace_scope]):
            mixed, state = self.mixer.step(
                rms_norm(x, self.operator_norm, self.rms_eps), state, length
            )
            h = x + mixed
        return (*self._ffn(h), state)


class Lfm2LM(nn.Module):
    """Embedding over the vocabulary slice, one `Block` a `layer_types` entry,
    final norm, tied head over the slice. Both entry points return (logits
    [.., V] un-normalised, hidden [.., D] after the final norm, the routed
    layers' stats with a leading layer axis)."""

    vocab_size: int
    hidden_size: int
    # a layer: "conv" | "full_attention" | "sliding_attention" | "latent_attention" |
    # "delta_attention"
    layer_types: Sequence[str]
    num_dense_layers: int
    dense_width: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    experts_held: int
    experts_per_token: int
    expert_width: int
    expert_offset: int = 0
    conv_kernel: int = 3
    routed_scaling_factor: float = 1.0
    expert_bias_scale: float = 0.01
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    router_epsilon: float = 1e-6
    # The `deepseek_v3` keys of a `latent_attention` layer (networks/mla.py) ...
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # ... of the shared expert beside the routed ones: `n_shared_experts`
    # SwiGLUs of `expert_width`, built as one of that many times the width ...
    n_shared_experts: int = 0
    # ... of a `bailing_hybrid` stack: the head-wise output gate of its latent
    # layers, the floor of a `delta_attention` layer's log-decay
    # (networks/kda.py; its heads are `num_heads` of `head_dim`, its taps
    # `conv_kernel`), the router's group-limited choice ...
    attention_gate: bool = False
    kda_lower_bound: float = -5.0
    n_group: int = 1
    topk_group: int = 1
    # ... of a `laguna` stack: the window of its `sliding_attention` layers, a
    # layer's own number of query heads (else `num_heads`), a layer KIND's
    # rotation (else `rope_theta` over the whole head): {kind: {rope_theta,
    # partial_rotary_factor, rope_type "default" | "yarn" with factor,
    # original_max_position_embeddings, beta_fast, beta_slow,
    # attention_factor}}, the published block as it is; `attention_gate` is
    # then the gate of its grouped-query layers too ...
    sliding_window: int = 0
    num_heads_per_layer: Optional[Sequence[int]] = None
    rope_parameters: Optional[Mapping[str, Mapping[str, Any]]] = None
    # ... of a `mellum` stack: a softmax router (`router_scoring`; "sigmoid" is the
    # four stacks above) with no selection bias, so no `expert_bias` leaf ...
    router_scoring: str = "sigmoid"
    router_selection_bias: bool = True
    # ... and of the head: the embedding's transpose, or a matrix of its own.
    tie_word_embeddings: bool = True
    # The embedding's initial standard deviation (every other matrix: 0.02).
    # At 0.02 a row is an eighth of the first attention layer's result, so from
    # a long prefix every position of a sequence routes as its context's mean
    # does; at 1.0 the token decides (the module docstring, Initialisation).
    embedding_init_std: float = 0.02

    @property
    def held(self) -> Tuple[int, int]:
        return int(self.expert_offset), int(self.experts_held)

    @property
    def routed_layers(self) -> int:
        return len(self.layer_types) - int(self.num_dense_layers)

    def _rotation(self, kind: str) -> Tuple[float, Optional[int], Optional[Yarn]]:
        """(theta, the rotated part of a head or None for all of it, YaRN's
        keys or None) of the attention layers of `kind`."""
        stated = dict((self.rope_parameters or {}).get(kind) or {})
        if not stated:
            return self.rope_theta, None, None
        rotary_dim = int(self.head_dim * float(stated.get("partial_rotary_factor", 1.0)))
        yarn = None
        if stated.get("rope_type", "default") == "yarn":
            yarn = Yarn(
                float(stated["factor"]), int(stated["original_max_position_embeddings"]),
                float(stated["beta_fast"]), float(stated["beta_slow"]),
                float(stated["attention_factor"]),
            )
        return (
            float(stated["rope_theta"]), None if rotary_dim == self.head_dim else rotary_dim, yarn
        )

    def _mixer(self, kind: str, index: int = 0) -> nn.Module:
        heads = self.num_heads
        if self.num_heads_per_layer is not None:
            heads = int(self.num_heads_per_layer[index])
        if kind == "conv":
            return ShortConv(self.hidden_size, self.conv_kernel)
        if kind in ("full_attention", "sliding_attention"):
            if kind == "sliding_attention" and self.sliding_window < 1:
                raise ValueError("a sliding_attention layer needs sliding_window >= 1")
            theta, rotary_dim, yarn = self._rotation(kind)
            return GroupedQueryAttention(
                self.hidden_size, heads, self.num_kv_heads, self.head_dim, theta, self.rms_eps,
                window=int(self.sliding_window) if kind == "sliding_attention" else None,
                gate=self.attention_gate, rotary_dim=rotary_dim, yarn=yarn,
            )
        if kind == "latent_attention":
            return LatentAttention(
                self.hidden_size, self.num_heads, self.kv_lora_rank, self.qk_nope_head_dim,
                self.qk_rope_head_dim, self.v_head_dim, self.rope_theta, self.rms_eps,
                self.attention_gate,
            )
        if kind == "delta_attention":
            return KimiDeltaAttention(
                self.hidden_size, self.num_heads, self.head_dim, self.conv_kernel,
                self.kda_lower_bound, self.rms_eps,
            )
        raise ValueError(
            f"layer_types names {kind!r}: a mixer is conv, full_attention, sliding_attention, "
            "latent_attention or delta_attention"
        )

    def _ffn(self, index: int) -> nn.Module:
        if index < self.num_dense_layers:
            return DenseMLP(self.hidden_size, self.dense_width)
        return RoutedMLP(
            self.hidden_size, self.num_experts, self.experts_held, self.expert_offset,
            self.experts_per_token, self.expert_width, self.routed_scaling_factor,
            self.expert_bias_scale, self.router_epsilon,
            self.n_shared_experts * self.expert_width, self.n_group, self.topk_group,
            score=self.router_scoring, selection_bias=self.router_selection_bias,
        )

    def setup(self) -> None:
        embed_init = (
            _INIT if self.embedding_init_std == 0.02
            else nn.initializers.normal(self.embedding_init_std)
        )
        self.embed = self.param("embed", embed_init, (self.vocab_size, self.hidden_size))
        self.layers = [
            Block(self._mixer(kind, i), self._ffn(i), self.hidden_size, self.rms_eps, name=f"layer_{i}")
            for i, kind in enumerate(self.layer_types)
        ]
        self.final_norm = self.param("final_norm", nn.initializers.ones, (self.hidden_size,))
        if not self.tie_word_embeddings:
            self.lm_head = self.param("lm_head", _INIT, (self.hidden_size, self.vocab_size))

    def _head(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        with annotate(SCOPES["lm_head"]):
            hidden = rms_norm(x, self.final_norm, self.rms_eps)
            return hidden @ (self.embed.T if self.tie_word_embeddings else self.lm_head), hidden

    def forward(
        self, tokens: jax.Array, head_positions: Optional[int] = None
    ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
        """With `head_positions` n, logits and hidden are of the LAST n
        positions alone (a response after its prompt): the stats stay over
        every position."""
        x = jnp.take(self.embed, tokens, axis=0)
        stats = []
        for layer in self.layers:
            x, layer_stats = layer.forward(x)
            stats.append(layer_stats)
        if head_positions is not None:
            x = x[:, x.shape[1] - head_positions:]
        logits, hidden = self._head(x)
        return logits, hidden, _stack([s for s in stats if s is not None])

    def prefill(
        self, carry: Lfm2Carry, tokens: jax.Array
    ) -> Tuple[Lfm2Carry, Dict[str, jax.Array]]:
        """The teacher-forced pass over a prefix `tokens` [B, P] that writes
        every layer's state into an EMPTY `carry` and returns it at length P:
        what P `step`s from position 0 would leave, in one pass and with no
        head. Every sequence's prefix is P long, so a carry of one position
        for all of them stays one."""
        x = jnp.take(self.embed, tokens, axis=0)
        states, stats = [], []
        for layer, state in zip(self.layers, carry.layers):
            x, layer_stats, state = layer.prefill(x, state)
            states.append(state)
            stats.append(layer_stats)
        carry = Lfm2Carry(tuple(states), carry.length + tokens.shape[1])
        return carry, _stack([s for s in stats if s is not None])

    def step(
        self, carry: Lfm2Carry, token: jax.Array
    ) -> Tuple[jax.Array, jax.Array, Lfm2Carry, Dict[str, jax.Array]]:
        x = jnp.take(self.embed, token, axis=0)
        states, stats = [], []
        for layer, state in zip(self.layers, carry.layers):
            x, layer_stats, state = layer.step(x, state, carry.length)
            states.append(state)
            stats.append(layer_stats)
        logits, hidden = self._head(x)
        carry = Lfm2Carry(tuple(states), carry.length + 1)
        return logits, hidden, carry, _stack([s for s in stats if s is not None])

    @nn.nowrap
    def init_carry(self, batch: int, max_len: int, together: bool = False) -> Lfm2Carry:
        """`together`: the caller's sequences all start and end at once, so
        the carry holds one position for all of them."""
        tail = lambda: ConvTail(
            jnp.zeros((batch, self.conv_kernel - 1, self.hidden_size), jnp.float32)
        )
        cache = lambda rows=max_len: jnp.zeros(
            (rows, batch, self.num_kv_heads, self.head_dim), jnp.float32
        )
        ring = int(self.sliding_window)  # rows a window layer needs, whatever `max_len`
        latent = lambda: Latent(jnp.zeros(
            (batch, max_len, self.kv_lora_rank + self.qk_rope_head_dim), jnp.float32
        ))
        width = self.num_heads * self.head_dim
        delta = lambda: DeltaState(
            jnp.zeros((batch, self.num_heads, self.head_dim, self.head_dim), jnp.float32),
            jnp.zeros((batch, self.conv_kernel - 1, 3 * width), jnp.float32),
            jnp.zeros((batch,), bool),
        )
        fresh = {
            "conv": tail, "full_attention": lambda: KV(cache(), cache()),
            "sliding_attention": lambda: WindowKV(cache(ring), cache(ring)),
            "latent_attention": latent, "delta_attention": delta,
        }
        return Lfm2Carry(
            tuple(fresh[kind]() for kind in self.layer_types), init_length(batch, together)
        )

    @nn.nowrap
    def reset_carry(self, carry: Lfm2Carry, done: jax.Array) -> Lfm2Carry:
        """Start a new sequence where `done`: its conv tails are what precedes
        a sequence (zeros, 16 KB), and so is a delta layer's matrix state,
        which the next step reads whole — as zeros, where `fresh` says so
        (networks/kda.py: no pass over the matrices here); nothing of a KV
        cache or of the latent rows beyond `length` is read, nor of a ring
        beyond min(`length`, W - 1)."""

        def fresh(state: Any) -> Any:
            if isinstance(state, ConvTail):
                return ConvTail(jnp.where(done[:, None, None], 0.0, state.z))
            if isinstance(state, DeltaState):
                return DeltaState(
                    state.s, jnp.where(done[:, None, None], 0.0, state.conv), state.fresh | done
                )
            return state

        return Lfm2Carry(
            tuple(fresh(state) for state in carry.layers), reset_length(carry.length, done)
        )

    @nn.nowrap
    def held_swiglu_form(self, tokens: int) -> str:
        """The form the held experts' SwiGLU takes in a pass over `tokens`
        tokens (`olmoe.held_swiglu_form` of the chunk `RoutedMLP` asks for)."""
        rows = held_chunk_rows(
            tokens, self.experts_per_token, self.experts_held, self.num_experts, _HELD_ROOM_SIGMAS
        )
        return held_swiglu_form(rows, self.hidden_size, self.expert_width, self.experts_held)

    @nn.nowrap
    def carry_bytes(self, batch: int, max_len: int) -> Dict[str, int]:
        carry = jax.eval_shape(lambda: self.init_carry(batch, max_len))
        size = lambda kind: sum(
            x.size * x.dtype.itemsize
            for state in carry.layers if isinstance(state, kind) for x in state
        )
        kinds = {
            "conv_tail": ConvTail, "kv": KV, "window_kv": WindowKV, "latent": Latent,
            "delta_state": DeltaState,
        }
        return {
            name: size(kind) for name, kind in kinds.items()
            if any(isinstance(state, kind) for state in carry.layers)
        }
