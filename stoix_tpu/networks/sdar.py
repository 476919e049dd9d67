"""The SDAR mixture-of-experts decoder as a token policy that generates by
diffusion over blocks: one expert-parallel rank's share of each layer, a
grouped-query KV cache for acting, a teacher-forced pass over `[clean copy ;
noisy copies]` for the update — two entry points over ONE set of parameters.

Published layer (`model_type` `sdar_moe`,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json;
stoix_tpu/reference/sdar.py writes it out plainly and is what the tests and
the benchmark compare this file with): pre-norm residual block; grouped-query
attention (query head h reads key/value head h // (heads / kv_heads)) with an
RMSNorm over each head's `head_dim` on q and on k (one weight vector each)
before rotate-half RoPE; a float32 softmax router over ALL `num_experts`,
top-k, weights renormalised to sum to one (`norm_topk_prob`); SwiGLU experts;
no shared expert, no bias; final RMSNorm and an untied head.

The chip's share: `experts_held` experts from `expert_offset` on are here
(`gate`, `up`, `down` hold only them), and `vocab_size` is the slice of the
vocabulary held here; attention and the router are whole. What the absent
experts would add is left out of the layer's result (networks/olmoe.py::moe
with `held`), and that partial result goes on to the next layer. Nothing
stands in for the other ranks or their exchange.

Generation: a sequence is blocks of `block_length` positions. The positions
of a block go through the model TOGETHER and attend every committed block in
the cache and one another, in both directions.
  * `block_step(params, cache, tokens [E, B], block, write)` — one pass over
    block number `block` of every sequence (all sequences are at the same
    block: the schedule is static). A denoise pass (`write=False`) leaves the
    cache as it was; the commit pass (`write=True`) of the finished block
    writes its keys and values at its positions and computes no logits.
  * `trunk_copies(params, clean [n, B + R], noisy [n, S, R])` — teacher
    forced: the clean sequence (prompt block and response) and S noisy copies
    of the response, (1 + S) positions a response token. A query in copy c,
    block b attends a key in copy c', block b' iff (c' = 0 and b' < b) or
    (c' = c and b' = b), and every copy's positions are the tokens' own. Returns the noisy copies'
    final-norm hidden states; `head` gives logits for those one asks for.
At equal parameters the two agree: the clean copy's keys and values are what
the commit passes cached, and copy s of block b sees what denoise pass s saw.

Parameters, by name (the reference reads them by these names):
  embed [V, D]; layer_<i>/{input_norm [D], wq [D, H*hd], wk wv [D, KV*hd], wo
  [H*hd, D], q_norm k_norm [hd], post_attn_norm [D], router [D, E], gate up
  [held, D, F], down [held, F, D]}; final_norm [D]; lm_head [D, V].
Initialisation is normal(0.02); norms start at one.

Not a flax module: the entry points are plain functions of the parameter
tree, so that a layer of the teacher-forced pass can be rematerialised
(`jax.checkpoint`) — the update keeps one layer's activations at a time,
and of the others their input and their attention's result. On a TPU that
attention is one Pallas kernel forward and one backward over the block mask's
tiles (`ops/pallas_attention.py::block_mask_attention`); elsewhere the plain
masked products of `_attend_copies`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from stoix_tpu.networks.olmoe import (
    Yarn, _stack, held_chunk_rows, held_swiglu_form, moe, rms_norm, rope, rope_angles,
)
from stoix_tpu.observability import SCOPES, annotate
from stoix_tpu.ops.pallas_attention import (
    BLOCK_MASK_RESIDUALS,
    block_mask_attention,
    block_mask_layout,
)
from stoix_tpu.ops.qk_norm_rope import norm_rope_form, qk_norm_rope

# A block step at block b reads the leading cache blocks of this many
# positions that hold a committed position, not the whole cache.
_CACHE_BLOCK = 128
_MASKED = jnp.finfo(jnp.float32).min
# Sequences whose attention score matrices ([heads, P, clean] float32 each) are
# live together in the update; the rest of a minibatch waits its turn.
_ATTENTION_CHUNK = 2
# Runs of response blocks in the update's attention, each multiplied with the
# clean keys up to its own end: 8 runs read 56% of the rows' whole width (the
# allowed pairs are 50%).
_KEY_GROUPS = 8


class BlockCache(NamedTuple):
    # Position-major as networks/olmoe.py's: a prefix of positions is one slab.
    k: Tuple[jax.Array, ...]  # a layer: [S, E, kv_heads, head_dim] float32
    v: Tuple[jax.Array, ...]


def init_cache(
    num_layers: int, batch: int, max_len: int, kv_heads: int, head_dim: int
) -> BlockCache:
    zeros = lambda: tuple(
        jnp.zeros((max_len, batch, kv_heads, head_dim), jnp.float32) for _ in range(num_layers)
    )
    return BlockCache(zeros(), zeros())


def gqa_qkv(
    layer: Dict[str, jax.Array], normed: jax.Array, positions: jax.Array, num_heads: int,
    num_kv_heads: int, head_dim: int, rope_theta: float, rms_eps: float,
    rotary_dim: Optional[int] = None, yarn: Optional[Yarn] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Grouped-query projections with the family's per-head q/k norm: normed
    [..., D], positions [...] -> q [..., heads, head_dim], k and v [...,
    kv_heads, head_dim]; q and k normalised over each head's `head_dim`
    (`layer["q_norm"]`, `layer["k_norm"]`: one weight vector each), then
    rotated. Where `norm_rope_form` says `kernel` (a TPU, heads of whole lane
    groups, sequences of a row tile or more: the update's teacher-forced
    pass), norm and rotation are ONE Pallas pass over each projection's rows
    where they lie (`ops/qk_norm_rope.py`), q written a head a sublane as the
    block-mask kernels read it; elsewhere `rms_norm` + `rope`.
    networks/lfm2.py's attention layers project through it too; with
    `rotary_dim` (only a head's first dims are rotated) or `yarn` (blended
    frequencies, scaled cos and sin) the rotation is `rope`'s, on the plain
    path: the kernel pairs a lane with the one half a HEAD away."""
    heads = lambda t, n: t.reshape(t.shape[:-1] + (n, head_dim))
    whole_head = rotary_dim in (None, head_dim) and yarn is None
    if whole_head and normed.ndim > 1 and norm_rope_form(normed.shape[-2], head_dim) == "kernel":
        rows = normed.shape[-2]
        angles = rope_angles(positions, head_dim, rope_theta).reshape(-1, rows, head_dim)

        def norm_rope(projected, weight, n):
            folded = projected.reshape(-1, rows, n * head_dim)  # (leading axes alone: no copy)
            out = qk_norm_rope(folded, weight, angles, heads=n, eps=rms_eps)
            return out.reshape(projected.shape[:-1] + (n, head_dim))

        q = norm_rope(normed @ layer["wq"], layer["q_norm"], num_heads)
        k = norm_rope(normed @ layer["wk"], layer["k_norm"], num_kv_heads)
        return q, k, heads(normed @ layer["wv"], num_kv_heads)
    q = rms_norm(heads(normed @ layer["wq"], num_heads), layer["q_norm"], rms_eps)
    k = rms_norm(heads(normed @ layer["wk"], num_kv_heads), layer["k_norm"], rms_eps)
    rotate = lambda t: rope(t, positions, rope_theta, rotary_dim, yarn)
    return rotate(q), rotate(k), heads(normed @ layer["wv"], num_kv_heads)


@dataclasses.dataclass(frozen=True)
class SdarLM:
    """Embedding over the vocabulary slice, `num_layers` SDAR layers (this
    rank's experts), final norm, head over the slice."""

    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int  # the router's width: every expert of the layer
    experts_held: int  # of which this rank holds so many,
    experts_per_token: int
    expert_width: int
    block_length: int
    expert_offset: int = 0  # from this one on
    num_layers: int = 1
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6

    @property
    def held(self) -> Tuple[int, int]:
        return int(self.expert_offset), int(self.experts_held)

    def init(self, key: jax.Array) -> Dict[str, Any]:
        d, f, e, held = self.hidden_size, self.expert_width, self.num_experts, self.experts_held
        q_width, kv_width = self.num_heads * self.head_dim, self.num_kv_heads * self.head_dim
        normal = lambda key, shape: 0.02 * jax.random.normal(key, shape, jnp.float32)
        ones = lambda n: jnp.ones((n,), jnp.float32)
        keys = iter(jax.random.split(key, 2 + 8 * self.num_layers))
        params: Dict[str, Any] = {"embed": normal(next(keys), (self.vocab_size, d))}
        for i in range(self.num_layers):
            params[f"layer_{i}"] = {
                "input_norm": ones(d),
                "wq": normal(next(keys), (d, q_width)),
                "wk": normal(next(keys), (d, kv_width)),
                "wv": normal(next(keys), (d, kv_width)),
                "wo": normal(next(keys), (q_width, d)),
                "q_norm": ones(self.head_dim),
                "k_norm": ones(self.head_dim),
                "post_attn_norm": ones(d),
                "router": normal(next(keys), (d, e)),
                "gate": normal(next(keys), (held, d, f)),
                "up": normal(next(keys), (held, d, f)),
                "down": normal(next(keys), (held, f, d)),
            }
        params["final_norm"] = ones(d)
        params["lm_head"] = normal(next(keys), (d, self.vocab_size))
        return {"params": params}

    def init_cache(self, batch: int, max_len: int) -> BlockCache:
        return init_cache(self.num_layers, batch, max_len, self.num_kv_heads, self.head_dim)

    # ------------------------------------------------------------------ #
    # What both entry points share
    # ------------------------------------------------------------------ #

    def _qkv(self, layer: Dict[str, jax.Array], x: jax.Array, positions: jax.Array):
        normed = rms_norm(x, layer["input_norm"], self.rms_eps)
        return gqa_qkv(
            layer, normed, positions, self.num_heads, self.num_kv_heads, self.head_dim,
            self.rope_theta, self.rms_eps,
        )

    def _grouped(self, q: jax.Array) -> jax.Array:
        """[..., heads, head_dim] -> [..., kv_heads, heads / kv_heads, head_dim]."""
        return q.reshape(
            q.shape[:-2] + (self.num_kv_heads, self.num_heads // self.num_kv_heads, self.head_dim)
        )

    def _moe(self, layer: Dict[str, jax.Array], h: jax.Array):
        flat = rms_norm(h, layer["post_attn_norm"], self.rms_eps).reshape(-1, self.hidden_size)
        with annotate(SCOPES["moe"]):
            routed, stats = moe(
                flat, layer["router"], layer["gate"], layer["up"], layer["down"],
                self.experts_per_token, held=self.held, renormalise=True,
            )
        return h + routed.reshape(h.shape), stats

    def final_norm(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        with annotate(SCOPES["lm_head"]):
            return rms_norm(x, params["params"]["final_norm"], self.rms_eps)

    def head(self, params: Dict[str, Any], hidden: jax.Array) -> jax.Array:
        """Un-normalised logits over the slice of final-norm hidden states."""
        with annotate(SCOPES["lm_head"]):
            return hidden @ params["params"]["lm_head"]

    # ------------------------------------------------------------------ #
    # Acting: one pass over a block through the cache
    # ------------------------------------------------------------------ #

    def _attend_block(
        self, q: jax.Array, k: jax.Array, v: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
        start: jax.Array,
    ) -> jax.Array:
        """q [E, B, heads, hd] over the cache positions < `start` and the
        block's own k, v [E, B, kv, hd] -> [E, B, heads * hd]."""
        max_len = cache_k.shape[0]
        scale = 1.0 / jnp.sqrt(jnp.float32(self.head_dim))
        q = self._grouped(q)  # [E, B, g, r, hd]

        def over(prefix: int):
            def attend(q, k, v, cache_k, cache_v, start):
                keys, values = cache_k[:prefix], cache_v[:prefix]
                cached = jnp.einsum("ebgrd,segd->egrbs", q, keys) * scale
                live = jnp.arange(prefix) < start
                cached = jnp.where(live, cached, _MASKED)
                own = jnp.einsum("ebgrd,ecgd->egrbc", q, k) * scale
                weights = jax.nn.softmax(jnp.concatenate([cached, own], axis=-1), axis=-1)
                return jnp.einsum("egrbs,segd->ebgrd", weights[..., :prefix], values) + jnp.einsum(
                    "egrbc,ecgd->ebgrd", weights[..., prefix:], v
                )

            return attend

        # (a last block of under half a block is read with the one before it)
        prefixes = list(range(_CACHE_BLOCK, max_len - _CACHE_BLOCK // 2, _CACHE_BLOCK)) + [max_len]
        with annotate(SCOPES["attention_scores"]):
            if len(prefixes) == 1:
                out = over(max_len)(q, k, v, cache_k, cache_v, start)
            else:
                blocks = jnp.clip((start - 1) // _CACHE_BLOCK, 0, len(prefixes) - 1)
                branches = [over(p) for p in prefixes]
                out = jax.lax.switch(blocks, branches, q, k, v, cache_k, cache_v, start)
        return out.reshape(out.shape[:2] + (-1,))

    def block_step(
        self, params: Dict[str, Any], cache: BlockCache, tokens: jax.Array, block: jax.Array,
        write: bool = False,
    ) -> Tuple[jax.Array, BlockCache, Dict[str, jax.Array]]:
        """tokens [E, B] of block number `block` (a scalar: every sequence is
        at the same block) -> (final-norm hidden [E, B, D], cache, stats with
        a leading layer axis). `write` is static: a denoise pass returns the
        cache it was given, the commit pass the cache with the block's keys
        and values at positions block * B .. block * B + B - 1."""
        tree = params["params"]
        length = tokens.shape[1]
        start = jnp.asarray(block, jnp.int32) * length
        positions = jnp.broadcast_to(start + jnp.arange(length), tokens.shape)
        x = jnp.take(tree["embed"], tokens, axis=0)
        keys, values, stats = list(cache.k), list(cache.v), []
        for i in range(self.num_layers):
            layer = tree[f"layer_{i}"]
            with annotate(SCOPES["attention"]):
                q, k, v = self._qkv(layer, x, positions)
                attended = self._attend_block(q, k, v, keys[i], values[i], start)
                if write:
                    at = (start, 0, 0, 0)
                    keys[i] = jax.lax.dynamic_update_slice(keys[i], jnp.swapaxes(k, 0, 1), at)
                    values[i] = jax.lax.dynamic_update_slice(values[i], jnp.swapaxes(v, 0, 1), at)
                h = x + attended @ layer["wo"]
            x, layer_stats = self._moe(layer, h)
            stats.append(layer_stats)
        return self.final_norm(params, x), BlockCache(tuple(keys), tuple(values)), _stack(stats)

    # ------------------------------------------------------------------ #
    # The update: [clean ; noisy copies] under the block mask
    # ------------------------------------------------------------------ #

    def _attend_copies(self, q: jax.Array, k: jax.Array, v: jax.Array, clean: int, copies: int):
        """One sequence. q [P, heads, hd], k and v [P, kv, hd], P = clean +
        copies * (clean - B): the clean positions first. Every position, clean
        or noisy, sees the clean keys of the blocks before its own and its own
        block's keys in its own copy. The response blocks are taken in
        `_KEY_GROUPS` runs, all copies of a run together: a run's queries are
        multiplied with the clean keys up to its last block only, a little
        over half of the rows' whole width in all. The plain statement of the
        mask: what every backend but a TPU runs (`copies_attention`), and what
        the kernel that a TPU runs instead is tested against."""
        size = self.block_length
        scale = 1.0 / jnp.sqrt(jnp.float32(self.head_dim))
        q = self._grouped(q)
        response = clean - size
        blocks, rows = response // size, 1 + copies  # rows of response blocks: the clean copy first
        # [P / B, B, ...]: the prompt block first
        blocked = lambda t: t.reshape((-1, size) + t.shape[1:])
        q_blocks, k_blocks, v_blocks = blocked(q), blocked(k), blocked(v)
        own = jnp.einsum("nbgrd,ncgd->grnbc", q_blocks, k_blocks) * scale  # [g, r, P / B, B, B]

        weights = jax.nn.softmax(own[:, :, 0], axis=-1)  # the prompt block sees itself alone
        outs = [jnp.einsum("grbc,cgd->bgrd", weights, v_blocks[0])]

        by_row = lambda t: t[1:].reshape((rows, blocks) + t.shape[1:])  # [rows, blocks, B, ...]
        q_rows, v_rows = by_row(q_blocks), by_row(v_blocks)
        own_rows = own[:, :, 1:].reshape(own.shape[:2] + (rows, blocks, size, size))
        runs = max(n for n in range(1, _KEY_GROUPS + 1) if blocks % n == 0)
        per = blocks // runs
        run_outs = []
        for first in range(0, blocks, per):
            queries = q_rows[:, first:first + per].reshape((rows * per * size,) + q.shape[1:])
            values = v_rows[:, first:first + per].reshape((rows * per, size) + v.shape[1:])
            mine = own_rows[:, :, :, first:first + per]
            mine = mine.reshape(own.shape[:2] + (rows * per * size, size))
            # Response block i is block i + 1: it sees the clean positions < B * (i + 1).
            prefix = size * (first + per)
            block_of_query = jnp.tile(jnp.repeat(first + jnp.arange(per), size), rows)
            earlier = jnp.einsum("qgrd,kgd->grqk", queries, k[:prefix]) * scale
            seen = jnp.arange(prefix)[None, :] // size <= block_of_query[:, None]
            earlier = jnp.where(seen, earlier, _MASKED)
            weights = jax.nn.softmax(jnp.concatenate([earlier, mine], axis=-1), axis=-1)
            own_weights = weights[..., prefix:]
            own_weights = own_weights.reshape(weights.shape[:2] + (rows * per, size, size))
            out = jnp.einsum("grqk,kgd->qgrd", weights[..., :prefix], v[:prefix]) + jnp.einsum(
                "grnbc,ncgd->nbgrd", own_weights, values
            ).reshape(queries.shape)
            run_outs.append(out.reshape((rows, per, size) + q.shape[1:]))
        outs.append(jnp.concatenate(run_outs, axis=1).reshape((-1,) + q.shape[1:]))
        return jnp.concatenate(outs).reshape(q.shape[0], -1)

    def held_swiglu_form(self, tokens: int) -> str:
        """The form the held experts' SwiGLU takes in a pass over `tokens`
        tokens (`olmoe.held_swiglu_form` of the chunk `_moe` asks for)."""
        rows = held_chunk_rows(tokens, self.experts_per_token, self.experts_held, self.num_experts)
        return held_swiglu_form(rows, self.hidden_size, self.expert_width, self.experts_held)

    def copies_attention(self, clean: int, copies: int) -> Dict[str, int]:
        """How `trunk_copies` multiplies its scores here, by what it can see
        (the backend and the shapes): `kernel` 1 for the Pallas kernel over
        the block mask's tiles (a TPU, heads of whole lanes), 0 for the plain
        products of `_attend_copies`; the tiles of 128 x 128 (query, key)
        positions that hold an allowed pair, which are all the kernel visits,
        and all tiles."""
        layout = block_mask_layout(self.block_length, clean, copies)
        kernel = jax.default_backend() == "tpu" and self.head_dim % 128 == 0
        return {
            "kernel": int(kernel), "tiles_visited": layout.tiles_visited,
            "tiles_total": layout.tiles_total,
        }

    def _layer_copies(
        self, layer: Dict[str, jax.Array], x: jax.Array, positions: jax.Array, clean: int
    ):
        """One layer over `[clean ; noisy copies]`. The scores go through
        `ops/pallas_attention.py::block_mask_attention` where
        `copies_attention` says so: nothing of [queries, keys] reaches HBM,
        forward or backward, and what the backward kernel reads of the forward
        (`BLOCK_MASK_RESIDUALS`: the result and each row's log-sum-exp) is
        kept by name. Elsewhere a few sequences' score matrices at a time
        (`_ATTENTION_CHUNK`), recomputed in the backward pass, and the result
        kept as `"attended"`. Either way the layer's backward pass
        (`trunk_copies`) multiplies no scores forward a second time."""
        copies = (x.shape[1] - clean) // (clean - self.block_length)
        with annotate(SCOPES["attention"]):
            q, k, v = self._qkv(layer, x, jnp.broadcast_to(positions, x.shape[:2]))
            kernel = self.copies_attention(clean, copies)["kernel"]
            with annotate(SCOPES["attention_scores"]):
                if kernel:
                    attended = block_mask_attention(
                        q, k, v, block_length=self.block_length, clean=clean, copies=copies
                    )
                else:
                    attend = jax.checkpoint(
                        lambda qkv: self._attend_copies(*qkv, clean=clean, copies=copies)
                    )
                    chunk = min(_ATTENTION_CHUNK, x.shape[0])
                    attended = jax.lax.map(attend, (q, k, v), batch_size=chunk)
            if not kernel:  # the kernel names its own result, in the layout it reads
                attended = checkpoint_name(attended, BLOCK_MASK_RESIDUALS[0])
            h = x + attended @ layer["wo"]
        return self._moe(layer, h)

    def trunk_copies(
        self, params: Dict[str, Any], clean: jax.Array, noisy: jax.Array
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """clean [n, B + R] (prompt block, then the response as generated),
        noisy [n, S, R] (the response as it stood before each of the S denoise
        passes of its blocks) -> (final-norm hidden of the noisy copies [n, S,
        R, D], stats over all n * (B + R + S * R) positions, layer axis
        first). Each layer is rematerialised in the backward pass, from its
        input and what its attention's backward pass reads of the forward
        (`BLOCK_MASK_RESIDUALS`: the result and, where the kernel ran, each
        row's log-sum-exp)."""
        tree = params["params"]
        batch, length = clean.shape
        copies, response = noisy.shape[1], noisy.shape[2]
        tokens = jnp.concatenate([clean, noisy.reshape(batch, -1)], axis=1)
        own = jnp.arange(length)
        positions = jnp.concatenate([own, jnp.tile(own[length - response:], copies)])
        x = jnp.take(tree["embed"], tokens, axis=0)
        stats = []
        keep_attended = jax.checkpoint_policies.save_only_these_names(*BLOCK_MASK_RESIDUALS)
        for i in range(self.num_layers):
            run = lambda layer, x: self._layer_copies(layer, x, positions, length)
            x, layer_stats = jax.checkpoint(run, policy=keep_attended)(tree[f"layer_{i}"], x)
            stats.append(layer_stats)
        hidden = self.final_norm(params, x[:, length:])
        return hidden.reshape(batch, copies, response, -1), _stack(stats)
