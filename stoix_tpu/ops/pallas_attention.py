"""Pallas TPU flash attention — the fused hot-op behind the transformer torso.

The pure-JAX `full_attention` (ops/ring_attention.py) materializes the full
[S, S] score matrix in HBM; XLA fuses some of it but the memory traffic still
scales O(S^2), forward and backward. `flash_attention` runs both directions in
VMEM: a forward kernel (online soft-max; saves the result and each row's
log-sum-exp) and a backward kernel (dq, dk, dv from those and d_result, the
weights recomputed a tile at a time), neither of which writes anything of
[queries, keys] size to HBM.

Layout notes (see /opt/skills/guides/pallas_guide.md):
  - operands are [B, H, width, S], positions minor: how XLA:TPU itself lays a
    `[B, S, heads, width]` activation between the projections and here, so
    the transposes around the kernels move nothing (`_flash_call`); a head's
    tile is whole rows, whatever its width (192 | 128, 128, 64, 32);
  - grid = (B, H / heads a step, ceil(S / tile)); a step holds its heads'
    K/V (and in the backward dk and dv) whole in VMEM and walks them with
    `pl.ds` slices of the lanes, a tile at a time, up to the diagonal when
    causal; heads a step and the tile are chosen at trace time from the
    shapes (`_heads_a_step`, `_tile`);
  - scores are [keys, queries], so the soft-max's sums run down the sublanes
    and its statistics are rows; accumulators are fp32 regardless of input
    dtype and all matmuls request `preferred_element_type=float32`, so bf16
    inputs still accumulate in fp32 on the MXU;
  - sequence padding to the block size is masked with statically-known
    lengths; masks use 2-D `broadcasted_iota` (TPU needs ≥2-D iota).

What is compiled where. Every kernel here is compiled by Mosaic on TPU
(`interpret=False`, the default) and checked there against its plain-JAX
reference by `chip_smoke.py`, and for a described v5e at the benchmark cells'
shapes by `tests/test_tpu_compile.py`. `flash_attention` and
`block_mask_attention` (the block-diffusion update's attention, further down)
have a Pallas backward: one kernel forward, one backward, nothing of [queries,
keys] in HBM either way; the gauge `stoix_tpu_attention_backward{form}` says
which form `flash_attention`'s backward rule was traced in. The BACKWARD of
`flash_attention_chunk` (the ring's per-chunk kernel) is plain JAX: its
`jax.custom_vjp` recomputes `_block_attend` and differentiates that — exact,
but it materializes the chunk's scores. Without a `custom_vjp`, reverse-mode
through a `pallas_call` whose body reads `pl.program_id` fails in JAX's
generic pallas_call JVP rule, so the learner could not take a gradient step on
the chip.

`flash_attention` is a drop-in for `full_attention` ([B, S, H, D] in/out) and
is the default `attention_fn` for the transformer torso on TPU; on other
backends `best_attention` takes the pure-JAX path (the Pallas interpreter is
orders of magnitude slower than XLA's fused attention on CPU). `interpret=True`
is something a test asks for to validate the kernel body off-TPU; no training
path selects it.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stoix_tpu.observability import SCOPES, annotate, get_registry
from stoix_tpu.ops.ring_attention import _block_attend, full_attention

_NEG_INF = float("-inf")
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fold_block(q, k_blk, v_blk, mask, carry):
    """One K/V block folded into the online-softmax accumulator (m, l, acc).

    The single shared body for every kernel in this module — the -inf /
    finite-proxy guards live only here. `mask` may be None (no masking).
    q [Bq, D] is pre-scaled fp32; k_blk/v_blk [Bk, D] fp32."""
    m_acc, l_acc, acc = carry
    scores = jax.lax.dot_general(
        q, k_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Bq, Bk]
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)
    m_blk = jnp.max(scores, axis=-1, keepdims=True)  # [Bq, 1]
    m_new = jnp.maximum(m_acc, m_blk)
    # Rows with nothing unmasked yet keep -inf; exp(-inf - -inf) is NaN,
    # so shift by a finite proxy and zero the weights via the mask.
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(scores - m_safe)  # [Bq, Bk]
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_acc), jnp.exp(m_acc - m_safe), 0.0)
    l_new = l_acc * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v_blk,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Bq, D]
    return m_new, l_new, acc * alpha + pv


def _init_carry(block_q: int, head_dim: int):
    return (
        jnp.full((block_q, 1), _NEG_INF, jnp.float32),
        jnp.zeros((block_q, 1), jnp.float32),
        jnp.zeros((block_q, head_dim), jnp.float32),
    )


def _walk(
    q_tile, block_q: int, block_k: int, kv_tiles: int, kv_len: int, causal: bool, fold, carry,
    window: Optional[int] = None,
):
    """`fold(columns of a key tile, mask or None, carry)` over the key tiles
    query tile `q_tile` sees: first tiles [0, whole), which hold only pairs
    that are allowed (every key real, and at or before the tile's first query
    when `causal`) and need no mask, then [whole, last), which hold some.
    Tiles from `last` on lie wholly in the future and are not visited. A mask
    is [keys, queries]: the scores lie so in both kernels.

    With `window` W (causal: query t sees the keys 0 <= t - j < W) the walk
    starts at the first tile the band reaches, the tile of key `first query
    - W + 1`; tiles on the band's lower edge are masked, those wholly inside
    it (at or after the tile's LAST query's oldest key) are not, those on the
    diagonal are masked as before. Tiles before the band are not visited."""
    whole, last = kv_len // block_k, kv_tiles
    if causal:
        first = q_tile * block_q
        whole = jnp.minimum((first + 1) // block_k, whole)
        last = jnp.minimum((first + block_q + block_k - 1) // block_k, last)
    q_pos = q_tile * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)

    def step(j, carry, masked):
        mask = None
        if masked:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
            mask = k_pos < kv_len  # strip the padded tail
            if causal:
                mask = jnp.logical_and(mask, q_pos >= k_pos)
            if window is not None:
                mask = jnp.logical_and(mask, q_pos - k_pos < window)
        return fold(pl.ds(pl.multiple_of(j * block_k, block_k), block_k), mask, carry)

    if window is not None:
        start = jnp.maximum(first - window + 1, 0) // block_k
        inside = jnp.maximum(first + block_q - window + block_k - 1, 0) // block_k
        inside = jnp.clip(inside, start, whole)  # (no tile lies wholly inside a narrow band)
        carry = jax.lax.fori_loop(start, inside, functools.partial(step, masked=True), carry)
        carry = jax.lax.fori_loop(inside, whole, functools.partial(step, masked=False), carry)
        return jax.lax.fori_loop(whole, last, functools.partial(step, masked=True), carry)
    carry = jax.lax.fori_loop(0, whole, functools.partial(step, masked=False), carry)
    return jax.lax.fori_loop(whole, last, functools.partial(step, masked=True), carry)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float, block_k: int, causal: bool, kv_len: int,
    window: Optional[int],
):
    """One query tile of some heads of one sequence, whose keys and values
    stay in VMEM across its query tiles. Every operand is [heads, width,
    positions], positions minor: a head's tile is whole rows, the scores are
    [keys, queries], so the soft-max's sums run down the sublanes and its
    statistics — and the log-sum-exp the backward kernel reads — are rows, and
    the result [width, queries] is a plain product of the values with them."""
    heads, _, block_q = q_ref.shape
    walk = functools.partial(
        _walk, pl.program_id(2), block_q, block_k, k_ref.shape[2] // block_k, kv_len, causal,
        window=window,
    )

    def one_head(h, _):
        q = q_ref[h].astype(jnp.float32) * scale  # [D, Bq]

        def fold(cols, mask, carry):
            m_acc, l_acc, acc = carry
            scores = _dot(k_ref[h, :, cols].astype(jnp.float32), q, _TN)  # [Bk, Bq]
            if mask is not None:
                scores = jnp.where(mask, scores, _MASKED_SCORE)
            # A causal walk's first tile holds key 0, which every query sees,
            # so the maximum is a real score from there on and a masked one's
            # weight is exp(-huge) = 0. A banded walk's first tile may lie
            # wholly before a late query's band: that query leaves it with
            # maximum -huge, weights exp(0) = 1 and a sum of garbage, and the
            # first tile that holds one of its keys (the band's newest key is
            # the query's own, so there is one) scales all of it by alpha =
            # exp(-huge - real) = 0.
            m_new = jnp.maximum(m_acc, jnp.max(scores, axis=0, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m_acc - m_new)
            l_new = alpha * l_acc + jnp.sum(p, axis=0, keepdims=True)
            return m_new, l_new, alpha * acc + _dot(v_ref[h, :, cols].astype(jnp.float32), p, _NN)

        # The accumulator is as wide as the values, which may differ from q and k.
        m_acc, l_acc, acc = walk(fold, (
            jnp.full((1, block_q), _MASKED_SCORE, jnp.float32),
            jnp.zeros((1, block_q), jnp.float32),
            jnp.zeros((v_ref.shape[1], block_q), jnp.float32),
        ))
        o_ref[h] = (acc / l_acc).astype(o_ref.dtype)
        lse_ref[pl.ds(h, 1), :] = m_acc + jnp.log(l_acc)
        return 0

    # A loop, not `heads` copies of the body: the program Mosaic is handed,
    # and the seconds of set-up that lowering it takes, do not grow with them.
    jax.lax.fori_loop(0, heads, one_head, 0)


def _flash_bwd_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
    *, scale: float, block_k: int, causal: bool, kv_len: int, window: Optional[int],
):
    """One query tile: dq of its queries, and its share of the sequence's dk
    and dv, which stay in VMEM (float32) across the query tiles. The weights
    are recomputed a tile at a time from the saved log-sum-exp and the same
    scaled q the forward multiplied; laid as the forward's, [keys, queries],
    dq, dk and dv [width, positions] are plain products of them."""
    heads, _, block_q = q_ref.shape
    q_tile = pl.program_id(2)

    @pl.when(q_tile == 0)
    def _zero():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    walk = functools.partial(
        _walk, q_tile, block_q, block_k, k_ref.shape[2] // block_k, kv_len, causal, window=window
    )
    def one_head(h, _):
        q = q_ref[h].astype(jnp.float32) * scale  # [D, Bq]
        d_out = do_ref[h].astype(jnp.float32)  # [Dv, Bq]
        delta = jnp.sum(d_out * o_ref[h].astype(jnp.float32), axis=0, keepdims=True)  # [1, Bq]
        lse = lse_ref[pl.ds(h, 1), :]

        def fold(cols, mask, dq):
            k_blk = k_ref[h, :, cols].astype(jnp.float32)  # [D, Bk]
            p = jnp.exp(_dot(k_blk, q, _TN) - lse)  # [Bk, Bq]
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            ds = p * (_dot(v_ref[h, :, cols].astype(jnp.float32), d_out, _TN) - delta)
            dv_ref[h, :, cols] += _dot(d_out, p, _NT)
            dk_ref[h, :, cols] += _dot(q, ds, _NT)  # (q is scaled already)
            return dq + _dot(k_blk, ds, _NN)

        dq = walk(fold, jnp.zeros(q.shape, jnp.float32))
        dq_ref[h] = (dq * scale).astype(dq_ref.dtype)
        return 0

    jax.lax.fori_loop(0, heads, one_head, 0)


def _out_struct(shape, dtype, *arrays: jax.Array) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct for a pallas_call out_shape, carrying the union of
    the inputs' varying-mesh-axes: under shard_map (where vma checking
    applies) the out_shape must state how the output varies; it varies
    wherever any input does."""
    vma: frozenset = frozenset()
    for a in arrays:
        vma = vma | jax.typeof(a).vma
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _pad_axis(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# What a grid step's resident blocks may take of VMEM: keys and values, and in
# the backward kernel dk and dv, each held twice by the pipeline.
_FLASH_RESIDENT_BYTES = 32 * 1024 * 1024


# The widest tile a walk takes. Measured at the token cells' shapes on a v5e
# (PERF.md section 6, PR 39): a step's products are too small to fill the
# MXUs' pipelines at 128 x 128, and at S = 512 one tile of the whole sequence,
# its upper triangle masked, beats two tiles a side that skip a quarter.
_FLASH_TILE = 512


def _tile(block: int, length: int) -> int:
    """The tile a walk takes along an axis of `length` positions, padded to
    `block`s: the largest multiple of `block` up to `_FLASH_TILE` that divides
    the padded length — or `block` itself where it is no whole number of lane
    tiles, which only a test asks for, to see several tiles of a short
    sequence."""
    if block % 128:
        return block
    padded = length + (-length) % block
    return max(t for t in range(block, max(block, _FLASH_TILE) + 1, block) if padded % t == 0)


def _heads_a_step(h: int, d: int, d_v: int, kv_len: int, itemsize: int) -> int:
    """Heads a grid step, from what the kernel sees: the most, up to 8, that
    divide H and whose keys, values, dk and dv fit `_FLASH_RESIDENT_BYTES`."""
    resident = lambda n: 2 * n * (d + d_v) * kv_len * (itemsize + 4)
    fits = [n for n in range(2, 9) if h % n == 0 and resident(n) <= _FLASH_RESIDENT_BYTES]
    return max(fits, default=1)


def _flash_call(kernel, name, causal, block_q, block_k, interpret, q, k, v, more, outs, window=None):
    """The call both kernels share: a grid of (sequence, heads a step, query
    tile) over q, k, v [B, S, H, D | D_v] seen as [B, H, width, S]. That is
    how XLA:TPU lays a `[.., heads, width]` activation whose width is not a
    whole number of lane tiles (192, 64), and how its projections write and
    read one whose width is (128): the transposes here and on the results
    change no byte's place in the compiled learners (`tests/test_tpu_compile.py`
    holds them to that), and only a length that is no whole number of tiles is
    copied, to pad it. `more` are further operands and `outs` the results,
    each named by kind: "q" / "v" a query tile D / D_v wide, "k" / "kv" the
    sequence's keys D / D_v wide, "lse" a row a head. `window` is the banded
    walk's (`_walk`)."""
    b, s, h, d = q.shape
    d_v = v.shape[-1]
    block_q, block_k = _tile(block_q, s), _tile(block_k, s)
    s_q, s_kv = s + (-s) % block_q, s + (-s) % block_k
    heads = _heads_a_step(h, d, d_v, s_kv, q.dtype.itemsize)
    lay = lambda x, block: _pad_axis(jnp.transpose(x, (0, 2, 3, 1)), 3, block)
    tile = lambda width: pl.BlockSpec((None, heads, width, block_q), lambda n, g, i: (n, g, 0, i))
    keys = lambda width: pl.BlockSpec((None, heads, width, s_kv), lambda n, g, i: (n, g, 0, 0))
    kinds = {
        "q": (tile(d), (b, h, d, s_q), q.dtype),
        "v": (tile(d_v), (b, h, d_v, s_q), q.dtype),
        "k": (keys(d), (b, h, d, s_kv), jnp.float32),
        "kv": (keys(d_v), (b, h, d_v, s_kv), jnp.float32),
        "lse": (
            pl.BlockSpec((None, None, heads, block_q), lambda n, g, i: (n, g, 0, i)),
            (b, h // heads, heads, s_q), jnp.float32,
        ),
    }
    operands = [lay(q, block_q), lay(k, block_k), lay(v, block_k)] + [
        x if kind == "lse" else lay(x, block_q) for kind, x in more
    ]
    results = pl.pallas_call(
        functools.partial(
            kernel, scale=d**-0.5, block_k=block_k, causal=causal, kv_len=s, window=window
        ),
        grid=(b, h // heads, s_q // block_q),
        in_specs=[kinds[kind][0] for kind in ["q", "k", "kv"] + [kind for kind, _ in more]],
        out_specs=[kinds[kind][0] for kind in outs],
        out_shape=[_out_struct(*kinds[kind][1:], *operands) for kind in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name=name,
        interpret=interpret,
    )(*operands)
    # Strip the padding; a result a head is [B, S, H, width] again.
    return [
        x if kind == "lse" else jnp.transpose(x[..., :s], (0, 3, 1, 2))
        for kind, x in zip(outs, results)
    ]


def _flash_forward(q, k, v, causal, block_q, block_k, interpret, window=None):
    """-> (out [B, S, H, D_v], log-sum-exp [B, H / heads a step, heads a step,
    padded S] float32)."""
    return _flash_call(
        _flash_kernel, "flash_attention", causal, block_q, block_k, interpret, q, k, v, [],
        ["v", "lse"], window,
    )


def _backward_form_gauge():
    return get_registry().gauge(
        "stoix_tpu_attention_backward",
        "1 on the form flash_attention's backward rule was traced in: pallas (one kernel: dq, "
        "dk and dv from the saved result and log-sum-exp) or plain (full_attention recomputed "
        "and differentiated)",
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, window):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret, window)[0]


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret, window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, window, residuals, d_out):
    for form, took in (("pallas", 1.0), ("plain", 0.0)):
        _backward_form_gauge().set(took, {"form": form})
    q, k, v, out, lse = residuals
    dq, dk, dv = _flash_call(
        _flash_bwd_kernel, "flash_attention_bwd", causal, block_q, block_k, interpret, q, k, v,
        [("v", out), ("v", d_out), ("lse", lse)], ["q", "k", "kv"], window,
    )
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret", "window")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused online-softmax attention. [B, S, H, D] -> [B, S, H, D].

    Self-attention shapes only (q and k share a sequence length). The values'
    head size may differ from the queries' and keys' (latent attention: 192
    and 128): the scale is the queries' 1/sqrt(D), the output [B, S, H, D_v].
    Forward and backward are one Pallas kernel each (`jax.custom_vjp`): the
    forward saves its result and each row's log-sum-exp, the backward
    recomputes the scores a tile at a time in VMEM and returns dq, dk and dv,
    so nothing of [queries, keys] size reaches HBM in either direction and
    `jax.grad` through this function is the gradient of `full_attention` at
    (q, k, v) to float32's rounding. Both read q, k, v and write their
    results positions-minor, as XLA:TPU lays them between the projections
    and here (`_flash_call`); several heads a grid step (`_heads_a_step`), a
    tile chosen from the length (`_tile`), key tiles past the diagonal not
    visited when `causal`. With `window` W (causal only) query t sees the
    keys 0 <= t - j < W, and in both kernels the key tiles before the band
    are not visited either (`_walk`); a window that holds the whole sequence
    is the causal program. Operands are multiplied at DEFAULT precision and
    accumulated in float32; the soft-max statistics are float32.
    `interpret` runs the Pallas interpreter (slow; a test asks for it).
    """
    if window is not None and not causal:
        raise ValueError("a window is a band below the diagonal: it needs causal=True")
    if window is not None and window >= q.shape[1]:
        window = None
    return _flash(q, k, v, causal, block_q, block_k, interpret, window)


def best_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False, window: Optional[int] = None
):
    """Backend dispatch: the Pallas kernel on TPU, pure-JAX elsewhere. Both
    branches are differentiable; which one a program took is read from its
    jaxpr (`pallas_call`), which is what chip_smoke.py checks. `window`: the
    banded mask of both."""
    if jax.default_backend() == "tpu":
        return flash_attention(q, k, v, causal=causal, window=window)
    return full_attention(q, k, v, causal=causal, window=window)


def _flash_chunk_kernel(
    q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref, m_ref, l_ref,
    *, scale: float, block_k: int, causal: bool
):
    """One K/V chunk's UNNORMALIZED contribution + online-softmax stats.

    Like `_flash_kernel` but (a) query/key positions come from refs (the
    caller supplies GLOBAL positions, so a ring-attention shard can attend a
    rotated K/V block correctly) and (b) the outputs are the raw streaming
    accumulator (acc, m, l) so the caller can fold several chunks — this is
    exactly ring attention's per-block contract."""
    block_q, head_dim = q_ref.shape
    s_kv = k_ref.shape[0]
    num_kv_blocks = s_kv // block_k

    q = q_ref[:].astype(jnp.float32) * scale
    q_pos = qpos_ref[:].reshape(block_q, 1)  # [Bq, 1] int32 global positions

    def body(j, carry):
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_blk = k_ref[rows, :].astype(jnp.float32)
        v_blk = v_ref[rows, :].astype(jnp.float32)
        if causal:
            k_pos = kpos_ref[rows, :].reshape(1, block_k)
            mask = q_pos >= k_pos
        else:
            mask = None
        return _fold_block(q, k_blk, v_blk, mask, carry)

    if causal:
        # Positions are contiguous ascending within a ring chunk; key blocks
        # entirely in this query block's future contribute nothing — bound
        # the walk (blocks whose first key position <= the max query pos).
        max_q = qpos_ref[block_q - 1, 0]
        k0 = kpos_ref[0, 0]
        last = jnp.clip((max_q - k0) // block_k + 1, 0, num_kv_blocks)
    else:
        last = num_kv_blocks
    m_acc, l_acc, acc = jax.lax.fori_loop(
        0, last, body, _init_carry(block_q, head_dim)
    )
    o_ref[:] = acc
    # Fully-masked rows keep m = -inf internally; emit a finite proxy (their
    # l and acc are 0, so the caller's accumulator fold stays NaN-free) —
    # same guard as the pure-JAX _block_attend.
    m_ref[:] = jnp.where(jnp.isfinite(m_acc), m_acc, 0.0)
    l_ref[:] = l_acc


def _fold_heads(x: jax.Array, b: int, h: int, d: int) -> jax.Array:
    """[B, S, H, D] -> [B*H, S, D]."""
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, x.shape[1], d)


def _chunk_forward(q, k, v, q_positions, k_positions, causal, block_q, block_k, interpret):
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    scale = d**-0.5
    fold = functools.partial(_fold_heads, b=b, h=h, d=d)
    qf, kf, vf = fold(q), fold(k), fold(v)
    qpos = q_positions.astype(jnp.int32).reshape(s_q, 1)
    kpos = k_positions.astype(jnp.int32).reshape(s_kv, 1)

    kernel = functools.partial(
        _flash_chunk_kernel, scale=scale, block_k=block_k, causal=causal
    )
    pv, m, l = pl.pallas_call(
        kernel,
        grid=(b * h, s_q // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s_kv, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s_kv, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((s_kv, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _out_struct((b * h, s_q, d), jnp.float32, qf, kf, vf, qpos, kpos),
            _out_struct((b * h, s_q, 1), jnp.float32, qf, kf, vf, qpos, kpos),
            _out_struct((b * h, s_q, 1), jnp.float32, qf, kf, vf, qpos, kpos),
        ],
        name="flash_attention_chunk",
        interpret=interpret,
    )(qf, kf, vf, qpos, kpos)

    pv = jnp.transpose(pv.reshape(b, h, s_q, d), (0, 2, 1, 3))  # [B, Sq, H, D]
    m = m.reshape(b, h, s_q)
    l = l.reshape(b, h, s_q)
    return pv, m, l


def _chunk_reference(q, k, v, q_positions, k_positions, causal):
    """The chunk kernel's contract in plain JAX: fp32 `_block_attend` with the
    causal mask built from the same global positions, outputs in the kernel's
    (pv, m, l) order. The kernel's backward differentiates THIS."""
    if causal:
        mask = (q_positions[:, None] >= k_positions[None, :])[None, None]
    else:
        mask = None
    f32 = lambda x: x.astype(jnp.float32)
    m, pv, l = _block_attend(f32(q), f32(k), f32(v), q.shape[-1] ** -0.5, mask)
    return pv, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _chunk(q, k, v, q_positions, k_positions, causal, block_q, block_k, interpret):
    return _chunk_forward(
        q, k, v, q_positions, k_positions, causal, block_q, block_k, interpret
    )


def _chunk_fwd(q, k, v, q_positions, k_positions, causal, block_q, block_k, interpret):
    out = _chunk_forward(
        q, k, v, q_positions, k_positions, causal, block_q, block_k, interpret
    )
    return out, (q, k, v, q_positions, k_positions)


def _chunk_bwd(causal, block_q, block_k, interpret, residuals, cotangents):
    q, k, v, q_positions, k_positions = residuals
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _chunk_reference(
            q_, k_, v_, q_positions, k_positions, causal
        ),
        q, k, v,
    )
    # Integer positions carry no gradient.
    return (*vjp(cotangents), None, None)


_chunk.defvjp(_chunk_fwd, _chunk_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention_chunk(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    k_positions: jax.Array,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Per-chunk streaming attention for ring composition.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; q_positions [Sq] / k_positions [Sk]
    are GLOBAL sequence positions (int32), contiguous and ascending, for
    causal masking across rotated blocks. Requires Sq % block_q == 0 and
    Sk % block_k == 0 (ring shards are uniformly sized). Returns
    (pv [B, Sq, H, D] unnormalized fp32, m [B, H, Sq] fp32 running max,
    l [B, H, Sq] fp32 normalizer) — the exact contract of ring attention's
    per-block accumulator fold.

    The forward is the Pallas kernel; the backward (`jax.custom_vjp`) is plain
    JAX — it recomputes `_block_attend` in fp32 and differentiates that.
    `interpret` runs the Pallas interpreter (slow; a test asks for it).
    """
    s_q, s_kv = q.shape[1], k.shape[1]
    if s_q % block_q or s_kv % block_k:
        raise ValueError(
            f"block sizes must divide the chunk lengths: got Sq={s_q} vs "
            f"block_q={block_q}, Sk={s_kv} vs block_k={block_k}"
        )
    return _chunk(
        q, k, v, q_positions, k_positions, causal, block_q, block_k, interpret
    )


# --------------------------------------------------------------------------- #
# Block-diffusion attention: `[clean ; noisy copies]` under the block mask
# --------------------------------------------------------------------------- #

# A masked score. Finite, so that a row with nothing allowed in the tiles seen
# so far holds garbage, not NaN, and the first allowed score (alpha = exp(this
# - a real maximum) = 0) wipes it.
_MASKED_SCORE = -0.7 * float(np.finfo(np.float32).max)
# The backward kernel holds a sequence's keys, values, dk and dv and some ten
# [heads * tile, 2 tiles] temporaries: over the 16 MiB a kernel gets unasked,
# well inside a v5e's 128.
_VMEM_LIMIT = 64 * 1024 * 1024


class BlockMaskLayout(NamedTuple):
    """The block mask of `[clean ; S noisy copies]` as the kernels walk it:
    static numpy, a function of (block length, clean length, copies, tile).

    The sequence is cut into tiles where it lies, the last one filled up with
    padding: nothing is moved to line a copy up with a tile (a copy of q or of
    the result costs more than the tiles that lining up saves). `mask`
    [padded, padded] says which query sees which key; a padded position sees
    and is seen by the padding of its own tile alone, so no row is empty and
    no real query sees padding. `plan` holds, query tile by query tile, the
    key tiles with an allowed pair in them as chunks of one or two tiles:
    pairs of neighbouring tiles all of whose pairs are allowed (for the
    tile's real queries), single such tiles, pairs and singles of the others,
    each with the number of its pattern in `bias` ([patterns, tile, tile]: 0
    where allowed, `_MASKED_SCORE` elsewhere); `stride` numbers a query tile,
    the four counts first and the four lists at `offsets`."""

    tile: int
    clean: int
    response: int
    copies: int
    mask: np.ndarray
    plan: np.ndarray
    stride: int
    offsets: Tuple[int, int, int, int]
    bias: np.ndarray
    tiles_visited: int

    @property
    def positions(self) -> int:
        return self.clean + self.copies * self.response

    @property
    def tiles(self) -> int:
        return -(-self.positions // self.tile)

    @property
    def padded(self) -> int:
        return self.tiles * self.tile

    @property
    def tiles_total(self) -> int:
        return self.tiles**2


@functools.lru_cache(maxsize=None)
def block_mask_layout(block_length: int, clean: int, copies: int, tile: int = 128) -> BlockMaskLayout:
    """`clean` = block_length + response length (the prompt block first)."""
    response = clean - block_length
    if response <= 0 or response % block_length:
        raise ValueError(f"blocks of {block_length} do not tile a response of {response}")
    positions = clean + copies * response
    tiles = -(-positions // tile)
    at = np.arange(tiles * tile)
    real = at < positions
    copy = np.where(at < clean, 0, 1 + (at - clean) // response)
    # A noisy copy starts at block 1: the prompt is block 0 of the clean copy alone.
    block = np.where(copy == 0, at // block_length, (at - clean) % response // block_length + 1)
    own = np.where(real, copy * (2 + response // block_length) + block, -1 - at // tile)
    clean_block = np.where(real & (copy == 0), block, np.iinfo(np.int32).max)
    mask = (clean_block[None, :] < np.where(real, block, 0)[:, None]) | (own[None, :] == own[:, None])

    by_tile = mask.reshape(tiles, tile, tiles, tile).transpose(0, 2, 1, 3)  # [q tile, k tile, q, k]
    real_rows = real.reshape(tiles, tile)
    patterns: List[np.ndarray] = []
    rows, visited = [], 0
    for i in range(tiles):
        full, part = [], []
        for j in range(tiles):
            seen = by_tile[i, j]
            if seen[real_rows[i]].all():  # what a padded query sees is dropped
                full.append(j)
            elif seen.any():
                found = [n for n, pattern in enumerate(patterns) if np.array_equal(pattern, seen)]
                if not found:
                    patterns.append(seen)
                part.append((j, found[0] if found else len(patterns) - 1))
        visited += len(full) + len(part)
        full2, full1 = [], []
        while full:
            if len(full) > 1 and full[1] == full[0] + 1:
                full2.append(full[0])
                full = full[2:]
            else:
                full1.append(full[0])
                full = full[1:]
        part2 = [part[j] + part[j + 1] for j in range(0, len(part) - 1, 2)]
        rows.append((full2, full1, part2, part[len(part) - len(part) % 2:]))
    widths = (1, 1, 4, 2)  # numbers an entry: tiles, and patterns beside the masked ones
    sizes = [max(1, max(len(row[kind]) for row in rows)) for kind in range(4)]
    offsets = np.cumsum([4] + [size * width for size, width in zip(sizes, widths)])
    plan = np.zeros((tiles, offsets[-1]), np.int32)
    for i, row in enumerate(rows):
        for kind in range(4):
            flat = np.asarray(row[kind], np.int32).reshape(-1)
            plan[i, kind] = len(row[kind])
            plan[i, offsets[kind]:offsets[kind] + flat.size] = flat
    patterns = patterns or [np.ones((tile, tile), bool)]  # (a mask of whole tiles has none)
    bias = np.where(np.stack(patterns), 0.0, _MASKED_SCORE).astype(np.float32)
    return BlockMaskLayout(
        tile, clean, response, copies, mask, plan.reshape(-1), int(offsets[-1]),
        tuple(int(o) for o in offsets[:4]), bias, visited,
    )


def _to_col(row):
    """[1, n] -> [n, 1] with a select and a sum: exact, and no relayout."""
    n = row.shape[1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == jax.lax.broadcasted_iota(
        jnp.int32, (n, n), 1
    )
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _stack_heads(ref, group: int, head_dim: int, real_rows):
    """A tile of a group's query heads -> [group * tile, head_dim]: the heads
    one after another, so that one product with a key tile serves them all.
    `ref` is [tile, group * head_dim] (heads side by side, as the output
    projection reads and writes them) or [tile, group, head_dim] (a head a
    sublane, as the rotation writes q and reads dq: read so, no copy of q
    stands between the two). Rows past the sequence's end (`real_rows`
    [tile, 1] false: the last tile's, which hold whatever was there) become
    zeros."""
    head = (lambda r: ref[:, r, :]) if len(ref.shape) == 3 else (
        lambda r: ref[:, r * head_dim:(r + 1) * head_dim]
    )
    return jnp.concatenate([jnp.where(real_rows, head(r), 0.0) for r in range(group)], axis=0)


def _real_rows(tile: int, positions: int):
    at = pl.program_id(2) * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    return at < positions


def _biased(scores, bias, group: int):
    """A pattern's bias [tile, keys] on the scores of a group's heads [group *
    tile, keys]."""
    if bias is None:
        return scores
    rows, keys = scores.shape
    return (scores.reshape(group, rows // group, keys) + bias[None]).reshape(rows, keys)


def _walk_chunks(plan_ref, layout: BlockMaskLayout, fold) -> None:
    """`fold(key tiles, their patterns or None)` over the chunks of this
    program's query tile, as `layout.plan` lists them: tuples of one or two
    scalars."""
    base = pl.program_id(2) * layout.stride

    def full_pair(j, _):
        a = plan_ref[base + layout.offsets[0] + j]
        fold((a, a + 1), None)
        return 0

    def full_single(j, _):
        fold((plan_ref[base + layout.offsets[1] + j],), None)
        return 0

    def masked_pair(j, _):
        a, bias_a, b, bias_b = (plan_ref[base + layout.offsets[2] + 4 * j + n] for n in range(4))
        fold((a, b), (bias_a, bias_b))
        return 0

    def masked_single(j, _):
        entry = base + layout.offsets[3] + 2 * j
        fold((plan_ref[entry],), (plan_ref[entry + 1],))
        return 0

    counts = layout.plan.reshape(layout.tiles, -1)[:, :4].max(axis=0)
    for kind, body in enumerate((full_pair, full_single, masked_pair, masked_single)):
        if counts[kind]:  # (a kind no query tile has is not traced: two tiles may not exist)
            jax.lax.fori_loop(0, plan_ref[base + kind], body, 0)


def _key_rows(ref, tiles, tile: int):
    """The rows of the key tiles `tiles` of a [padded, head_dim] block."""
    rows = [ref[pl.ds(pl.multiple_of(t * tile, tile), tile), :] for t in tiles]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


def _patterns(ref, patterns, axis: int):
    """The bias of a chunk's tiles, side by side along the keys' axis."""
    if patterns is None:
        return None
    return jnp.concatenate([ref[n] for n in patterns], axis=axis) if len(patterns) > 1 else ref[patterns[0]]


def _block_mask_fwd_kernel(
    plan_ref, q_ref, k_ref, vt_ref, bias_ref, o_ref, lse_ref, q_scr, m_scr, l_scr, acc_scr,
    *, layout: BlockMaskLayout, group: int,
):
    """One query tile of one key/value head's query heads, the sequence's keys
    and values staying in VMEM. Scores are [keys, queries]: the soft-max's
    sums run down the sublanes and its statistics are rows, a sixteenth of
    what columns take. Two walks over the tile's chunks: the first finds each
    row's maximum and sum (and from them the log-sum-exp the backward kernel
    reads), the second multiplies p = exp(score - maximum) / sum with the
    values — the NORMALISED weights, and the exp taken where the plain path
    and the rollout's cache attention take it, so that the three hand the MXU
    the same numbers to round: exp(score - log-sum-exp) is the same weight to
    float32's rounding but another float, and one in a hundred then rounds
    the other way."""
    tile, head_dim = layout.tile, k_ref.shape[1]
    scale = head_dim**-0.5
    q_scr[...] = _stack_heads(q_ref, group, head_dim, _real_rows(tile, layout.positions))
    m_scr[...] = jnp.full_like(m_scr, _MASKED_SCORE)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def scores(tiles, patterns):
        # Scaled after the product, as the plain path does it: the operands
        # the MXU rounds are then the same numbers.
        found = _dot(_key_rows(k_ref, tiles, tile), q_scr[...], _NT) * scale  # [keys, queries]
        bias = _patterns(bias_ref, patterns, 0)
        return found if bias is None else found + bias

    def statistics(tiles, patterns):
        found = scores(tiles, patterns)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(found, axis=0, keepdims=True))
        l_scr[...] = jnp.exp(m_prev - m_new) * l_scr[...] + jnp.sum(
            jnp.exp(found - m_new), axis=0, keepdims=True
        )
        m_scr[...] = m_new

    _walk_chunks(plan_ref, layout, statistics)
    lse_ref[...] = m_scr[...] + jnp.log(l_scr[...])
    l_scr[...] = 1.0 / l_scr[...]

    def attend(tiles, patterns):
        p = jnp.exp(scores(tiles, patterns) - m_scr[...]) * l_scr[...]
        values = [vt_ref[t] for t in tiles]  # [head_dim, tile] each
        values = values[0] if len(values) == 1 else jnp.concatenate(values, axis=1)
        acc_scr[...] += _dot(values, p, _NN)  # [head_dim, queries]

    _walk_chunks(plan_ref, layout, attend)
    for r in range(group):
        o_ref[:, r * head_dim:(r + 1) * head_dim] = acc_scr[:, r * tile:(r + 1) * tile].T.astype(
            o_ref.dtype
        )


def _block_mask_bwd_kernel(
    plan_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, bias_ref, dq_ref, dkt_ref, dvt_ref,
    q_scr, do_scr, qt_scr, dot_scr, lse_scr, delta_scr, dq_scr,
    *, layout: BlockMaskLayout, group: int,
):
    """One query tile: dq of its queries, and its share of the sequence's dk
    and dv, which stay in VMEM across the query tiles, transposed ([tiles,
    head_dim, tile]) so that they too are plain products — of the tile's q and
    d_out transposed once, with the same p and ds that give dq — and the
    group's heads add up in the contraction. Scores are [queries, keys]."""
    tile, head_dim = layout.tile, k_ref.shape[1]
    scale = head_dim**-0.5

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dkt_ref[...] = jnp.zeros_like(dkt_ref)
        dvt_ref[...] = jnp.zeros_like(dvt_ref)

    real_rows = _real_rows(tile, layout.positions)
    q_scr[...] = _stack_heads(q_ref, group, head_dim, real_rows)
    do_scr[...] = _stack_heads(do_ref, group, head_dim, real_rows)
    delta_scr[...] = jnp.sum(
        do_scr[...] * _stack_heads(o_ref, group, head_dim, real_rows), axis=1, keepdims=True
    )
    for r in range(group):
        rows = slice(r * tile, (r + 1) * tile)
        lse_scr[rows, :] = jnp.where(real_rows, _to_col(lse_ref[:, rows]), 0.0)
        qt_scr[:, rows] = q_scr[rows, :].T * scale  # (dk = ds^T q scale: scaled once, here)
        dot_scr[:, rows] = do_scr[rows, :].T
    dq_scr[...] = jnp.zeros_like(dq_scr)

    def fold(tiles, patterns):
        k, v = _key_rows(k_ref, tiles, tile), _key_rows(v_ref, tiles, tile)
        scores = _biased(_dot(q_scr[...], k, _NT) * scale, _patterns(bias_ref, patterns, 1), group)
        p = jnp.exp(scores - lse_scr[...])
        ds = p * (_dot(do_scr[...], v, _NT) - delta_scr[...])
        dq_scr[...] += _dot(ds, k, _NN)
        dvt = _dot(dot_scr[...], p, _NN)  # [head_dim, keys]
        dkt = _dot(qt_scr[...], ds, _NN)
        for n, t in enumerate(tiles):
            dvt_ref[t] += dvt[:, n * tile:(n + 1) * tile]
            dkt_ref[t] += dkt[:, n * tile:(n + 1) * tile]

    _walk_chunks(plan_ref, layout, fold)
    for r in range(group):
        dq_ref[:, r, :] = (dq_scr[r * tile:(r + 1) * tile, :] * scale).astype(dq_ref.dtype)


def _block_mask_call(kernel, name, spec, q, ins, outs, scratch):
    """The call both kernels share: a grid of (sequence, key/value head, query
    tile), the plan prefetched; `ins` and `outs` name each operand's block
    spec, `outs` with its shape."""
    layout_key, heads, kv_heads, interpret = spec
    layout = block_mask_layout(*layout_key)
    n, positions, _, group, head_dim = q.shape
    tile, tiles = layout.tile, layout.tiles
    specs = {
        "q": pl.BlockSpec((None, tile, None, group, head_dim), lambda b, g, i, plan: (b, i, g, 0, 0)),
        "out": pl.BlockSpec((None, tile, group * head_dim), lambda b, g, i, plan: (b, i, g)),
        "kv": pl.BlockSpec((None, layout.padded, head_dim), lambda b, g, i, plan: (b, 0, g)),
        "lse": pl.BlockSpec((None, None, 1, group * tile), lambda b, g, i, plan: (b, g, 0, i)),
        "bias": pl.BlockSpec((layout.bias.shape[0], tile, tile), lambda b, g, i, plan: (0, 0, 0)),
        "bias_t": pl.BlockSpec(
            (layout.bias.shape[0], tile, group * tile), lambda b, g, i, plan: (0, 0, 0)
        ),
        "dkv_t": pl.BlockSpec(
            (None, None, tiles, head_dim, tile), lambda b, g, i, plan: (b, g, 0, 0, 0)
        ),
    }
    shapes = {
        "q": (q.shape, q.dtype),
        "out": ((n, positions, heads * head_dim), q.dtype),
        "lse": ((n, kv_heads, 1, tiles * group * tile), jnp.float32),
        "dkv_t": ((n, kv_heads, tiles, head_dim, tile), jnp.float32),
    }
    operands = tuple(operand for _, operand in ins)
    rows = group * tile
    sized = {
        "rows": (rows, head_dim), "rows_t": (head_dim, rows), "col": (rows, 1), "row": (1, rows)
    }
    return pl.pallas_call(
        functools.partial(kernel, layout=layout, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, kv_heads, tiles),
            in_specs=[specs[kind] for kind, _ in ins],
            out_specs=[specs[kind] for kind in outs],
            scratch_shapes=[pltpu.VMEM(sized[kind], jnp.float32) for kind in scratch],
        ),
        out_shape=[_out_struct(*shapes[kind], *operands) for kind in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name=name,
        interpret=interpret,
    )(jnp.asarray(layout.plan), *operands)


def _block_mask_forward(q, k, v, spec):
    """q [n, positions, kv_heads, group, hd], k and v [n, padded, kv_heads *
    hd] -> (out [n, positions, heads * hd], log-sum-exp [n, kv_heads, 1,
    tiles * group * tile]: a query tile's heads one after another)."""
    layout = block_mask_layout(*spec[0])
    kv_heads, group = spec[2], spec[1] // spec[2]
    n, _, kv_width = v.shape
    # Values transposed a tile, [n, kv_heads, tiles, hd, tile], and a pattern's
    # bias transposed and repeated for the group's heads: small arrays both.
    v_t = jnp.transpose(
        v.reshape(n, layout.tiles, layout.tile, kv_heads, kv_width // kv_heads), (0, 3, 1, 4, 2)
    )
    bias_t = jnp.asarray(np.tile(layout.bias.transpose(0, 2, 1), (1, 1, group)))
    return _block_mask_call(
        _block_mask_fwd_kernel, "block_mask_attention_fwd", spec, q,
        [("q", q), ("kv", k), ("dkv_t", v_t), ("bias_t", bias_t)], ["out", "lse"],
        ["rows", "row", "row", "rows_t"],
    )


def _block_mask_backward(q, k, v, out, lse, d_out, spec):
    bias = jnp.asarray(block_mask_layout(*spec[0]).bias)
    dq, dk_t, dv_t = _block_mask_call(
        _block_mask_bwd_kernel, "block_mask_attention_bwd", spec, q,
        [("q", q), ("kv", k), ("kv", v), ("out", out), ("out", d_out), ("lse", lse), ("bias", bias)],
        ["q", "dkv_t", "dkv_t"],
        ["rows", "rows", "rows_t", "rows_t", "col", "col", "rows"],
    )
    # [n, kv_heads, tiles, hd, tile] -> [n, padded, kv_heads * hd]: small arrays.
    back = lambda t: jnp.transpose(t, (0, 2, 4, 1, 3)).reshape(k.shape).astype(k.dtype)
    return dq, back(dk_t), back(dv_t)


# What the backward pass reads of the forward: kept by name, so that a
# rematerialised caller (`jax.checkpoint` with a policy that saves these names)
# does not run the forward kernel a second time.
BLOCK_MASK_RESIDUALS = ("attended", "attended_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _block_mask(q, k, v, spec):
    return _block_mask_forward(q, k, v, spec)[0]


def _block_mask_fwd(q, k, v, spec):
    out, lse = _block_mask_forward(q, k, v, spec)
    out = checkpoint_name(out, BLOCK_MASK_RESIDUALS[0])
    lse = checkpoint_name(lse, BLOCK_MASK_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _block_mask_bwd(spec, residuals, d_out):
    # The backward pass's ops carry the scope the forward's do, whatever name
    # stack the rule is traced under.
    with annotate(SCOPES["attention_scores"]):
        return _block_mask_backward(*residuals, d_out, spec)


_block_mask.defvjp(_block_mask_fwd, _block_mask_bwd)


def block_mask_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, block_length: int, clean: int, copies: int,
    tile: int = 128, interpret: bool = False,
) -> jax.Array:
    """The block-diffusion update's attention, forward and backward in Pallas.

    q [n, P, heads, hd], k and v [n, P, kv_heads, hd], P = clean + copies *
    (clean - block_length): the clean copy (prompt block, then the response)
    first, then the noisy copies of the response. A query in copy c, block b
    sees a key in copy c', block b' iff (c' = 0 and b' < b) or (c' = c and b'
    = b); query head h reads key/value head h // (heads / kv_heads). Returns
    [n, P, heads * hd], what `networks/sdar.py::_attend_copies` returns a
    sequence.

    One forward kernel over the (query tile, key tile) pairs that hold an
    allowed pair (`block_mask_layout`: 49 of 169 at clean 516, 2 copies), a
    key/value head's query heads stacked into one product a chunk, the
    soft-max in two walks (log-sum-exp, then the normalised weights times the
    values), and one backward kernel over the same pairs from the saved
    output and log-sum-exp (`BLOCK_MASK_RESIDUALS`). q and its cotangent are
    read and written where and as they lie ([n, P, heads, hd]: a head a
    sublane), the result and its cotangent as the output projection has them
    ([n, P, heads * hd]); only k and v (an eighth of q here) are copied,
    padded to whole tiles. Operands are multiplied as they come, at DEFAULT
    precision, and accumulated in float32. `interpret` runs the Pallas
    interpreter (a test asks for it)."""
    n, positions, heads, head_dim = q.shape
    kv_heads = k.shape[2]
    layout = block_mask_layout(block_length, clean, copies, tile)
    if positions != layout.positions:
        raise ValueError(f"{positions} positions, the layout has {layout.positions}")
    padded = lambda x: jnp.pad(
        x.reshape(n, positions, -1), ((0, 0), (0, layout.padded - positions), (0, 0))
    )
    spec = ((block_length, clean, copies, tile), heads, kv_heads, interpret)
    grouped = q.reshape(n, positions, kv_heads, heads // kv_heads, head_dim)
    return _block_mask(grouped, padded(k), padded(v), spec)


# --------------------------------------------------------------------------- #
# Latent attention's decode: many query heads against ONE row a position
# --------------------------------------------------------------------------- #

# Sequences a grid step: their live blocks are walked together, so a step of
# 128 sequences is 16 x (live blocks) grid steps and not 128 x.
_LATENT_SEQUENCES = 8


def _latent_decode_kernel(
    lengths_ref, longest_ref, q_ref, rows_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, rank: int, block: int, sequences: int,
):
    """One block of `block` positions of `sequences` sequences: every head's
    score against each row (the row's whole width), the online softmax, and
    the weights times the rows' first `rank` columns. Blocks past the last
    live position of these sequences are neither fetched anew (the index map
    repeats the last live block) nor computed."""
    first, j = pl.program_id(0) * sequences, pl.program_id(1)
    heads = q_ref.shape[1]

    @pl.when(j == 0)
    def _start():
        m_scr[...] = jnp.full_like(m_scr, _MASKED_SCORE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block <= longest_ref[pl.program_id(0)])
    def _fold():
        at = j * block + jax.lax.broadcasted_iota(jnp.int32, (heads, block), 1)
        row_at = j * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        for n in range(sequences):
            # A row past the sequence's length was never written and may hold
            # anything (a NaN times a weight of 0 is a NaN): it counts as zeros.
            rows = jnp.where(row_at <= lengths_ref[first + n], rows_ref[n], 0.0)  # [block, rank + rotated]
            scores = _dot(q_ref[n], rows, _NT) * scale  # [heads, block]
            scores = jnp.where(at <= lengths_ref[first + n], scores, _MASKED_SCORE)
            m_prev = m_scr[n]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[n] = alpha * l_scr[n] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[n] = alpha * acc_scr[n] + _dot(p, rows[:, :rank], _NN)
            m_scr[n] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def latent_decode_attention(
    q: jax.Array, rows: jax.Array, lengths: jax.Array, *, rank: int, scale: float,
    block: int = 128, interpret: bool = False,
) -> jax.Array:
    """softmax(q . rows^T * scale) rows[..., :rank] over each sequence's
    positions <= `lengths` [B] (the position just written): q [B, H, w]
    absorbed queries against the latent rows [B, S, w] -> [B, H, rank].

    The decode of multi-head latent attention: all H heads share one row a
    position, so a sequence's scores and values are two matrix products
    ([H, w] x [w, block] and [H, block] x [block, rank]) and the kernel is
    bound by reading the float32 rows once, the live blocks alone — XLA's own
    plan for the same two products first writes a bfloat16 copy of the cache
    in another layout every step (PERF.md §6, PR 38). Operands are multiplied
    as they come, at DEFAULT precision (one bfloat16 pass: casting them first
    changed neither the time nor one bit of the result on the chip), and
    accumulated in float32; the softmax is float32, online over the blocks. `interpret` runs the
    Pallas interpreter (a test asks for it)."""
    batch, heads, width = q.shape
    max_len = rows.shape[1]
    if max_len % block:
        raise ValueError(f"blocks of {block} positions do not tile a cache of {max_len}")
    sequences = _LATENT_SEQUENCES if batch % _LATENT_SEQUENCES == 0 else 1
    lengths = lengths.astype(jnp.int32)
    longest = jnp.max(lengths.reshape(batch // sequences, sequences), axis=1)  # a grid step's sequences
    kernel = functools.partial(
        _latent_decode_kernel, scale=scale, rank=rank, block=block, sequences=sequences
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch // sequences, max_len // block),
            in_specs=[
                pl.BlockSpec((sequences, heads, width), lambda b, j, *_: (b, 0, 0)),
                pl.BlockSpec(
                    (sequences, block, width),
                    lambda b, j, lengths_ref, longest_ref: (b, jnp.minimum(j, longest_ref[b] // block), 0),
                ),
            ],
            out_specs=pl.BlockSpec((sequences, heads, rank), lambda b, j, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((sequences, heads, 1), jnp.float32),
                pltpu.VMEM((sequences, heads, 1), jnp.float32),
                pltpu.VMEM((sequences, heads, rank), jnp.float32),
            ],
        ),
        out_shape=_out_struct((batch, heads, rank), q.dtype, q, rows),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        name="latent_decode_attention",
        interpret=interpret,
    )(lengths, longest, q, rows)


# --------------------------------------------------------------------------- #
# Grouped-query attention's decode: a group's queries against ONE row a
# position a key/value head, over a growing cache or a ring
# --------------------------------------------------------------------------- #


# Sequences a grid step: a block of 128 rows of 8 sequences is 4 MiB of keys
# and 4 MiB of values, each held twice while the next is fetched.
_GQA_SEQUENCES = 8


def _gqa_decode_kernel(
    last_ref, longest_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, block: int, sequences: int, kv_heads: int,
):
    """One block of `block` rows of `sequences` sequences' caches. A sequence's
    block [block, kv_heads, head_dim] is read as it lies, as [block * kv_heads,
    head_dim] (column `r * kv_heads + g` is row r of key/value head g: with
    eight heads every (8, 128) tile stays where it is), so ALL its query heads
    take one product with the keys, an online softmax over the columns and one
    product with the values; a query head keeps the columns of its own
    key/value head up to the last live row, every other column is masked and
    weighs exactly 0. Blocks past the last live row of these sequences are
    neither fetched anew (the index map repeats the last live block) nor
    computed."""
    first, j = pl.program_id(0) * sequences, pl.program_id(1)
    heads, head_dim = q_ref.shape[1:]
    columns = block * kv_heads

    @pl.when(j == 0)
    def _start():
        m_scr[...] = jnp.full_like(m_scr, _MASKED_SCORE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block <= longest_ref[pl.program_id(0)])
    def _fold():
        # Once a grid step: a query head's own columns by their number, the
        # others by one no sequence reaches.
        column = jax.lax.broadcasted_iota(jnp.int32, (heads, columns), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, columns), 0)
        own = jax.lax.rem(column, kv_heads) == jax.lax.div(head, heads // kv_heads)
        own_column = jnp.where(own, column, np.iinfo(np.int32).max)
        row = jax.lax.broadcasted_iota(jnp.int32, (block, 1, 1), 0)
        for n in range(sequences):
            live = last_ref[first + n] + 1 - j * block  # this block's live rows (0 or fewer: none)
            # A row past the live ones was never written and may hold
            # anything (a NaN times a weight of 0 is a NaN): it counts as zeros.
            keys = jnp.where(row < live, k_ref[:, n], 0.0).reshape(columns, head_dim)
            values = jnp.where(row < live, v_ref[:, n], 0.0).reshape(columns, head_dim)
            scores = _dot(q_ref[n], keys, _NT) * scale  # [heads, columns]
            scores = jnp.where(own_column < live * kv_heads, scores, _MASKED_SCORE)
            m_prev = m_scr[n]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[n] = alpha * l_scr[n] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[n] = alpha * acc_scr[n] + _dot(p, values, _NN)
            m_scr[n] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def gqa_decode_attention(
    q: jax.Array, cache_k: jax.Array, cache_v: jax.Array, last: jax.Array, *,
    block: int = 128, interpret: bool = False,
) -> jax.Array:
    """softmax(q k^T / sqrt(head_dim)) v over each sequence's rows <= `last`
    [B] (its last live row): q [B, kv_heads, group, head_dim] grouped queries
    against caches [S, B, kv_heads, head_dim] (position-major, a growing cache
    or a ring: the rows' order does not matter) -> [B, kv_heads, group,
    head_dim].

    The decode of grouped-query attention at 6 or 8 queries a key/value head,
    on 8 or 4 key/value heads.
    A grid step fetches `block` rows of `_GQA_SEQUENCES` sequences; a
    sequence's rows [block, kv_heads, head_dim] are read as they lie, as
    [block * kv_heads, head_dim] — at eight key/value heads each (8, 128)
    tile is one row's heads and stays where it is — so all the query heads
    take ONE product with the keys and one with the values on the MXU, under
    a mask that leaves a head its own key/value head's live rows, and the
    kernel is bound by reading the float32 rows once, the live blocks alone.
    Reading one key/value head at a time (`k_ref[:, n, g, :]`: one sublane of
    each of 128 tiles, 64 such operands a grid step) took longer than the
    block's DMA: a ring of 512 rows at 32 sequences, written and attended as
    a scan's carry, took 0.222 ms a step and takes 0.195, of which 0.164 are
    its bytes at the chip's peak (PERF.md section 6, PR 45).
    At FOUR key/value heads of eight queries (Mellum2: an (8, 128) tile would
    be two rows' heads) XLA hands the caches over tiled (4, 128), so nothing
    is padded in HBM, and the kernel is the same program: in the cell that
    decodes 16 sequences three windows deep it takes 0.097 ms a step for a
    ring of 1,024 rows (0.082 are its bytes at the chip's peak) and 0.303 ms
    for a growing cache at 3,328 live rows on average (0.266), 84% and 88% of
    the byte bound (PERF.md section 6, PR 47).
    `networks/olmoe.py::_attend_cache`'s multiply-and-reduce does the same
    sums on the vector unit, which at one to four queries a row is free
    beside the read and at eight is not (0.633). Operands are multiplied as
    they come, at DEFAULT precision, and accumulated in float32; the softmax
    is float32, online over the blocks. `interpret` runs the Pallas
    interpreter (a test asks for it)."""
    batch, kv_heads, group, head_dim = q.shape
    heads = kv_heads * group
    max_len = cache_k.shape[0]
    if max_len % block:
        raise ValueError(f"blocks of {block} rows do not tile a cache of {max_len}")
    sequences = _GQA_SEQUENCES if batch % _GQA_SEQUENCES == 0 else 1
    last = last.astype(jnp.int32)
    longest = jnp.max(last.reshape(batch // sequences, sequences), axis=1)  # a grid step's sequences
    kernel = functools.partial(
        _gqa_decode_kernel, scale=head_dim**-0.5, block=block, sequences=sequences, kv_heads=kv_heads
    )
    rows = pl.BlockSpec(
        (block, sequences, kv_heads, head_dim),
        lambda b, j, last_ref, longest_ref: (jnp.minimum(j, longest_ref[b] // block), b, 0, 0),
    )
    queries = pl.BlockSpec((sequences, heads, head_dim), lambda b, j, *_: (b, 0, 0))
    attended = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch // sequences, max_len // block),
            in_specs=[queries, rows, rows],
            out_specs=queries,
            scratch_shapes=[
                pltpu.VMEM((sequences, heads, 1), jnp.float32),
                pltpu.VMEM((sequences, heads, 1), jnp.float32),
                pltpu.VMEM((sequences, heads, head_dim), jnp.float32),
            ],
        ),
        out_shape=_out_struct((batch, heads, head_dim), q.dtype, q, cache_k, cache_v),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        name="gqa_decode_attention",
        interpret=interpret,
    )(last, longest, q.reshape(batch, heads, head_dim), cache_k, cache_v)
    return attended.reshape(q.shape)
