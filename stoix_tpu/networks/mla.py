"""Multi-head latent attention (`model_type` `deepseek_v3`, no query
compression) as a mixer of networks/lfm2.py's stack: a compressed decode
state, and two ways to attend over ONE set of weights.

With u the operator-normed hidden state, H heads, n = `qk_nope_head_dim`, r =
`qk_rope_head_dim`, c = `kv_lora_rank`, v = `v_head_dim` (published: 32, 128,
64, 512, 128), no bias anywhere:

    q_h = W_q,h u = [q_nope_h (n) ; q_rope_h (r)]
    [l ; k_r] = W_kva u;   l^ = RMSNorm(l) (its own weight);   k_r one for all heads
    [k_nope_h ; v_h] = W_kvb,h l^
    RoPE (interleaved pairs) on q_rope_h and k_r;   k_h = [k_nope_h ; k_r]
    o_h = sum_t softmax_t(q_h . k_h,t / sqrt(n + r)) v_h,t;   y = W_o [o_1 .. o_H]

  * `LatentAttention.forward` (whole sequences, the update): the equations as
    they stand — keys and values expanded a head through W_kvb, then
    `best_attention` with queries and keys n + r wide and values v wide.
  * `LatentAttention.step` (one token, the decode) ABSORBS the expansion:
    with W_kvb,h = [W_uk,h ; W_uv,h], q~_h = W_uk,h^T q_nope_h (c wide), the
    score is (q~_h . l^_t + q_rope_h . k_r,t) / sqrt(n + r), o~_h = sum_t p_t
    l^_t and o_h = W_uv,h o~_h: the same numbers, and the decode state is one
    row [l^_t ; rotated k_r,t] a position for ALL heads (`Latent`: c + r = 576
    numbers against H * (n + r + v) = 10,240 of an expanded cache). Expanding
    the cached rows every step would be 550 GFLOP a layer a step at 128
    sequences of 512; it is not on the decode path, and the gauge
    `stoix_tpu_mla_decode{form}` says which form the traced step took.

`attend_latent` is the decode's attention: H query heads against ONE row a
position, thirty operations a byte of cache, so a matrix product a sequence
(the MXU) and not the multiply-and-reduce of `olmoe._attend_cache`. The rows
are sequence-major, [B, S, c + r]: a sequence's live rows are one matrix, the
product's batch axis leads, and a step's new row is one
`dynamic_update_slice` at the one position of sequences that move together.

`gate` (off by default; `gated_attention_proj_granularity_type` `head_wise`
of a `bailing_hybrid` stack) multiplies each head's result by sigmoid(u W_g)_h
before W_o, in both entry points.

Parameters, by name: wq [D, H (n + r)], wkv_a [D, c + r], kv_norm [c], wkv_b
[c, H (n + v)], wo [H v, D], with `gate` wg [D, H]; normal(0.02), the norm
starts at one.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from stoix_tpu.networks.olmoe import _CACHE_BLOCK, rms_norm
from stoix_tpu.observability import SCOPES, annotate, get_registry
from stoix_tpu.ops.pallas_attention import best_attention, latent_decode_attention

_INIT = nn.initializers.normal(0.02)


class Latent(NamedTuple):
    # Sequence-major: a sequence's rows are one [S, c + r] matrix.
    rows: jax.Array  # [B, S, kv_lora_rank + qk_rope_head_dim] float32: [l^ ; rotated k_r]


def rope_interleaved(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [..., dim] rotated at `positions` (broadcast against x's leading
    axes), the pairs being neighbours (x_2i, x_2i+1) (`rope_interleave`):
    (x_2i + i x_2i+1) e^{i p theta^(-2i/dim)}. The partner of each lane is
    one lane away, so the pairs stay where they lie."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = jnp.repeat(positions[..., None].astype(jnp.float32) * inv_freq, 2, axis=-1)
    even = jnp.arange(dim) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
    return x * jnp.cos(angles) + partner * jnp.sin(angles)


def write_latent_row(rows: jax.Array, row: jax.Array, length: jax.Array) -> jax.Array:
    """`rows` [B, S, w] with `row` [B, w] at each sequence's position `length`
    [B], or at the one position `length` [] of sequences that move together:
    one slab in place (a scatter pins the cache's layout and brings
    whole-cache copies back, PERF.md §6, PR 35)."""
    if length.ndim == 0:
        return jax.lax.dynamic_update_slice(rows, row[:, None], (0, length, 0))
    return rows.at[jnp.arange(row.shape[0]), length].set(row)


def attend_latent(q: jax.Array, rows: jax.Array, length: jax.Array, rank: int, scale: float) -> jax.Array:
    """softmax(q . rows^T * scale) rows[..., :rank] over the positions <=
    `length` ([B] or [], the position just written): q [B, H, c + r] absorbed
    queries against rows [B, S, c + r] -> [B, H, c]. Two batched matrix
    products a sequence, over the leading blocks that hold a live position
    alone; the softmax in float32. On a TPU the Pallas kernel
    (`latent_decode_attention`: the float32 rows read once), elsewhere the
    same products in plain JAX."""
    max_len = rows.shape[1]
    if jax.default_backend() == "tpu" and max_len % _CACHE_BLOCK == 0:
        lengths = jnp.broadcast_to(length, q.shape[:1])
        return latent_decode_attention(q, rows, lengths, rank=rank, scale=scale, block=_CACHE_BLOCK)

    def over(prefix: int):
        def attend(q, rows, length):
            live_rows = rows[:, :prefix]
            scores = jnp.einsum("bhc,btc->bht", q, live_rows) * scale
            last = jnp.broadcast_to(length, q.shape[:1])
            live = jnp.arange(prefix)[None, None, :] <= last[:, None, None]
            weights = jax.nn.softmax(jnp.where(live, scores, jnp.finfo(jnp.float32).min), axis=-1)
            # A row past `length` was never written, and a program that drops
            # its final carry may find there whatever the memory held (XLA:TPU
            # then allocates the cache without filling it, PERF.md §6, PR 38):
            # weight 0 times a NaN is a NaN, so such a row counts as zeros.
            values = jnp.where(live[:, 0, :, None], live_rows[..., :rank], 0.0)
            return jnp.einsum("bht,btc->bhc", weights, values)

        return attend

    prefixes = list(range(_CACHE_BLOCK, max_len, _CACHE_BLOCK)) + [max_len]
    if len(prefixes) == 1:
        return over(max_len)(q, rows, length)
    blocks = jnp.max(length) // _CACHE_BLOCK  # index of the last live block
    return jax.lax.switch(
        jnp.minimum(blocks, len(prefixes) - 1), [over(p) for p in prefixes], q, rows, length
    )


def _decode_form_gauge():
    return get_registry().gauge(
        "stoix_tpu_mla_decode",
        "1 on the form of latent attention the most recently traced decode step took, 0 on the "
        "other: absorbed (W_uk into the query, W_uv into the output, attention over the latent "
        "rows) or expanded (keys and values of every cached position through W_kvb)",
    )


class LatentAttention(nn.Module):
    """Causal multi-head latent attention. Input: the operator-normed hidden
    state."""

    hidden_size: int
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_eps: float
    gate: bool = False  # a sigmoid gate a head on the heads' results, before W_o
    trace_scope = "attention"

    def setup(self) -> None:
        d, h, c = self.hidden_size, self.num_heads, self.kv_lora_rank
        n, r, v = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        self.wq = self.param("wq", _INIT, (d, h * (n + r)))
        self.wkv_a = self.param("wkv_a", _INIT, (d, c + r))
        self.kv_norm = self.param("kv_norm", nn.initializers.ones, (c,))
        self.wkv_b = self.param("wkv_b", _INIT, (c, h * (n + v)))
        self.wo = self.param("wo", _INIT, (h * v, d))
        if self.gate:
            self.wg = self.param("wg", _INIT, (d, h))

    def _gated(self, attended: jax.Array, u: jax.Array) -> jax.Array:
        """attended [..., H, v] times sigmoid(u W_g) a head, where `gate`."""
        return attended * jax.nn.sigmoid(u @ self.wg)[..., None] if self.gate else attended

    @property
    def scale(self) -> float:
        return float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def _queries(self, u: jax.Array, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """u [..., D] -> (q_nope [..., H, n], rotated q_rope [..., H, r])."""
        q = (u @ self.wq).reshape(u.shape[:-1] + (self.num_heads, -1))
        q_nope, q_rope = jnp.split(q, [self.qk_nope_head_dim], axis=-1)
        with annotate(SCOPES["latent_project"]):
            return q_nope, rope_interleaved(q_rope, positions[..., None], self.rope_theta)

    def _latent(self, u: jax.Array, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """u [..., D] -> (l^ [..., c] normalised, rotated k_r [..., r])."""
        with annotate(SCOPES["latent_project"]):
            latent, k_rope = jnp.split(u @ self.wkv_a, [self.kv_lora_rank], axis=-1)
            return (
                rms_norm(latent, self.kv_norm, self.rms_eps),
                rope_interleaved(k_rope, positions, self.rope_theta),
            )

    def forward(self, u: jax.Array) -> jax.Array:
        batch, length, _ = u.shape
        positions = jnp.broadcast_to(jnp.arange(length), (batch, length))
        q_nope, q_rope = self._queries(u, positions)
        latent, k_rope = self._latent(u, positions)
        with annotate(SCOPES["latent_attend"]):
            expanded = (latent @ self.wkv_b).reshape(batch, length, self.num_heads, -1)
            k_nope, v = jnp.split(expanded, [self.qk_nope_head_dim], axis=-1)
            k_rope = jnp.broadcast_to(k_rope[:, :, None], q_rope.shape)
            q, k = jnp.concatenate([q_nope, q_rope], -1), jnp.concatenate([k_nope, k_rope], -1)
            attended = best_attention(q, k, v, causal=True)  # [B, T, H, v]
        return self._gated(attended, u).reshape(batch, length, -1) @ self.wo

    def step(self, u: jax.Array, state: Latent, length: jax.Array):
        """u [B, D] against the latent rows; the new row is written first."""
        for form, took in (("absorbed", 1.0), ("expanded", 0.0)):
            _decode_form_gauge().set(took, {"form": form})
        positions = jnp.broadcast_to(length, u.shape[:1])
        q_nope, q_rope = self._queries(u, positions)
        latent, k_rope = self._latent(u, positions)
        with annotate(SCOPES["latent_project"]):
            rows = write_latent_row(state.rows, jnp.concatenate([latent, k_rope], -1), length)
        with annotate(SCOPES["latent_attend"]):
            w_uk, w_uv = jnp.split(
                self.wkv_b.reshape(self.kv_lora_rank, self.num_heads, -1),
                [self.qk_nope_head_dim], axis=-1,
            )  # [c, H, n], [c, H, v]
            absorbed = jnp.einsum("bhn,chn->bhc", q_nope, w_uk)
            attended = attend_latent(
                jnp.concatenate([absorbed, q_rope], -1), rows, length, self.kv_lora_rank, self.scale
            )
            out = jnp.einsum("bhc,chv->bhv", attended, w_uv)
        return self._gated(out, u).reshape(u.shape[0], -1) @ self.wo, Latent(rows)
