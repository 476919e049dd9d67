"""Pallas TPU flash attention — the fused hot-op behind the transformer torso.

The pure-JAX `full_attention` (ops/ring_attention.py) materializes the full
[S, S] score matrix in HBM; XLA fuses some of it but the memory traffic still
scales O(S^2). This kernel runs the online-softmax recurrence entirely in
VMEM: each grid step holds one query block plus one (batch*head)'s K/V in
VMEM, streams K/V blocks through the MXU, and never writes scores to HBM.

Layout notes (see /opt/skills/guides/pallas_guide.md):
  - grid = (B*H, ceil(S / block_q)); one kernel instance owns one query block;
  - K/V for the (b, h) slice live in VMEM whole (S×D ≤ ~2 MB at S=8192, D=64,
    bf16) and are walked with `pl.ds` dynamic slices, block_k at a time;
  - accumulators (m, l, acc) are fp32 regardless of input dtype; all matmuls
    request `preferred_element_type=float32` so bf16 inputs still accumulate
    in fp32 on the MXU;
  - sequence padding to the block size is masked with statically-known
    lengths; causal masking uses 2-D `broadcasted_iota` (TPU needs ≥2-D iota).

What is compiled where. Both forward kernels (`flash_attention`,
`flash_attention_chunk`) are compiled by Mosaic on TPU (`interpret=False`,
the default) and checked there against `full_attention` by `chip_smoke.py`.
The BACKWARD of both is plain JAX: each has a `jax.custom_vjp` whose backward
recomputes the reference (`full_attention` / `_block_attend`) and
differentiates that — exact, but it materializes the [S, S] scores the
forward avoids (a Pallas backward is a later optimisation). Without the
`custom_vjp`, reverse-mode through a `pallas_call` whose body reads
`pl.program_id` fails in JAX's generic pallas_call JVP rule, so the learner
could not take a gradient step on the chip.

`flash_attention` is a drop-in for `full_attention` ([B, S, H, D] in/out) and
is the default `attention_fn` for the transformer torso on TPU; on other
backends `best_attention` takes the pure-JAX path (the Pallas interpreter is
orders of magnitude slower than XLA's fused attention on CPU). `interpret=True`
is something a test asks for to validate the kernel body off-TPU; no training
path selects it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from stoix_tpu.ops.ring_attention import _block_attend, full_attention

_NEG_INF = float("-inf")


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


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, *, scale: float, block_k: int, causal: bool, kv_len: int
):
    block_q, head_dim = q_ref.shape
    s_pad = k_ref.shape[0]
    num_kv_blocks = s_pad // block_k

    q = q_ref[:].astype(jnp.float32) * scale  # [Bq, D]
    q_block_idx = pl.program_id(1)
    q_pos = q_block_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(j, carry):
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_blk = k_ref[rows, :].astype(jnp.float32)
        v_blk = v_ref[rows, :].astype(jnp.float32)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < kv_len  # strip the padded tail
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        return _fold_block(q, k_blk, v_blk, mask, carry)

    if causal:
        # Blocks fully in the future contribute nothing; bound the walk at the
        # last block that can contain key ≤ the block's max query position.
        last = jnp.minimum(
            (q_block_idx * block_q + block_q + block_k - 1) // block_k,
            num_kv_blocks,
        )
    else:
        last = num_kv_blocks
    m_acc, l_acc, acc = jax.lax.fori_loop(
        0, last, body, _init_carry(block_q, head_dim)
    )

    l_safe = jnp.where(l_acc == 0.0, 1.0, l_acc)
    o_ref[:] = (acc / l_safe).astype(o_ref.dtype)


def _fold_heads(x: jax.Array, b: int, h: int, d: int) -> jax.Array:
    """[B, S, H, D] -> [B*H, S, D]."""
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, x.shape[1], d)


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


def _flash_forward(q, k, v, causal, block_q, block_k, interpret):
    b, s, h, d = q.shape
    scale = d**-0.5
    fold = functools.partial(_fold_heads, b=b, h=h, d=d)
    qf, kf, vf = fold(q), fold(k), fold(v)
    qf = _pad_axis(qf, 1, block_q)
    kf = _pad_axis(kf, 1, block_k)
    vf = _pad_axis(vf, 1, block_k)
    s_q_pad, s_kv_pad = qf.shape[1], kf.shape[1]

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_k=block_k, causal=causal, kv_len=s
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, s_q_pad // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s_kv_pad, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s_kv_pad, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=_out_struct((b * h, s_q_pad, d), q.dtype, qf, kf, vf),
        name="flash_attention",
        interpret=interpret,
    )(qf, kf, vf)

    out = out[:, :s]  # strip query padding
    return jnp.transpose(out.reshape(b, h, s, d), (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret), (q, k, v)


def _flash_bwd(causal, block_q, block_k, interpret, residuals, g):
    # Plain-JAX backward: recompute full_attention and differentiate it.
    _, vjp = jax.vjp(functools.partial(full_attention, causal=causal), *residuals)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused online-softmax attention. [B, S, H, D] -> [B, S, H, D].

    Self-attention shapes only (q and k share a sequence length). The forward
    is the Pallas kernel; the backward (`jax.custom_vjp`) recomputes
    `full_attention` in plain JAX and returns ITS gradient, so `jax.grad`
    through this function is the gradient of `full_attention` at (q, k, v).
    `interpret` runs the Pallas interpreter (slow; a test asks for it).
    """
    return _flash(q, k, v, causal, block_q, block_k, interpret)


def best_attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False):
    """Backend dispatch: the Pallas kernel on TPU, pure-JAX elsewhere. Both
    branches are differentiable; which one a program took is read from its
    jaxpr (`pallas_call`), which is what chip_smoke.py checks."""
    if jax.default_backend() == "tpu":
        return flash_attention(q, k, v, causal=causal)
    return full_attention(q, k, v, causal=causal)


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
