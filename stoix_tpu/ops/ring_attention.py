"""Ring attention — sequence-parallel exact attention over a mesh axis.

The reference has no attention at all (SURVEY.md §5 long-context: sequence
length is handled by lax.scan/burn-in). The TPU build makes long-context
first-class: this module computes EXACT softmax attention with the sequence
dimension sharded over a mesh axis, rotating key/value blocks around the ring
with `jax.lax.ppermute` (ICI neighbor exchange) while each device accumulates
its queries' output with the online-softmax (flash-attention) recurrence.

Why this shape on TPU:
  - memory: each device holds S/R of the sequence; no device ever
    materializes the full [S, S] score matrix — long sequences scale with
    ring size instead of exploding VMEM/HBM;
  - comms: the K/V block rotation is a neighbor `ppermute`, which XLA lowers
    to ICI point-to-point transfers that overlap with the per-block attention
    compute (R-1 hops, each hiding a block matmul);
  - numerics: the online-softmax accumulator (running max m, normalizer l,
    unnormalized output acc) is the numerically stable streaming form; the
    final output is bitwise-close to full attention (tests pin allclose).

Public API:
    ring_attention(q, k, v, axis_name, causal=False)  — inside shard_map,
        [B, S_local, H, D] per device; returns [B, S_local, H, D].
    make_ring_attention(mesh, axis)                   — host-side wrapper that
        shard_maps over `axis` with batch replicated, sequence sharded.
    full_attention(q, k, v, causal=False)             — the single-device
        reference implementation (also the block kernel's oracle in tests).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Plain softmax attention. [B, S, H, D] -> [B, S, H, D]; with values of
    another head size D_v than q's and k's, [B, S, H, D_v] (the scale is q's).
    With `window` W (causal only) query t sees the keys 0 <= t - j < W: the
    banded mask."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        if window is not None and window < s_k:
            mask = mask & ~jnp.tril(jnp.ones((s_q, s_k), bool), -window)
        scores = jnp.where(mask, scores, -jnp.inf)
    elif window is not None:
        raise ValueError("a window is a band below the diagonal: it needs causal=True")
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _block_attend(q, k, v, scale, mask: Optional[jax.Array]):
    """One K/V block's contribution: returns (scores_max, exp_scores@v,
    exp_scores row-sums) for the online-softmax accumulator."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B, H, Sq, Sk]
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)  # [B, H, Sq]
    # Guard fully-masked rows: exp(-inf - -inf) would be NaN.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])  # [B, H, Sq, Sk]
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)  # [B, Sq, H, D]
    l = jnp.sum(p, axis=-1)  # [B, H, Sq]
    return m_safe, pv, l


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    use_flash: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Exact attention with sequence sharded over `axis_name` (call inside
    shard_map). Per-device shapes [B, S_local, H, D].

    The K/V block starts as the local shard and rotates one neighbor per step;
    after R steps every device has attended to every block. For causal masks
    the block's global offset is derived from the rotating source index.

    `use_flash` routes each block's contribution through the Pallas
    flash-attention chunk kernel (ops/pallas_attention.flash_attention_chunk)
    — same (m, pv, l) accumulator contract, fused in VMEM, compiled by Mosaic;
    its backward is plain JAX (see that module). Left at None it is on when
    the backend is TPU and the kernel block size (128) divides the shard
    length, and the pure-JAX `_block_attend` otherwise. `interpret=True` runs
    the kernel in the Pallas interpreter: a test asks for that (together with
    `use_flash=True`) to validate the kernel off-TPU; this function never
    chooses it.
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    s_local = q.shape[1]
    block = min(128, s_local)
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu" and s_local % 128 == 0
    elif use_flash and s_local % block:
        raise ValueError(
            f"use_flash=True needs the shard length ({s_local}) to be a "
            f"multiple of the kernel block ({block})"
        )

    # Online-softmax accumulators — always fp32 (both the pure-JAX and the
    # Pallas chunk paths fold fp32 block stats; bf16 inputs still accumulate
    # exactly). They are constant-initialized but become device-varying
    # through the scan — mark them varying over every mesh axis q varies
    # over (not just the ring axis: on a 2D data x seq mesh the batch is
    # sharded over 'data' too) so the scan carry types line up under
    # shard_map.
    b, s, h, d = q.shape
    m_acc = jnp.full((b, h, s), -jnp.inf, jnp.float32)  # running max
    l_acc = jnp.zeros((b, h, s), jnp.float32)  # running normalizer
    o_acc = jnp.zeros((b, s, h, d), jnp.float32)  # unnormalized output
    vma = tuple(jax.typeof(q).vma or (axis_name,))
    m_acc, l_acc, o_acc = jax.lax.pcast((m_acc, l_acc, o_acc), vma, to="varying")

    q_pos = my_idx * s_local + jnp.arange(s_local)  # global query positions

    def step(carry, r):
        m_acc, l_acc, o_acc, k_blk, v_blk = carry
        # The block currently held arrived from device (my_idx + r) % R.
        src = (my_idx + r) % axis_size
        k_pos = src * s_local + jnp.arange(s_local)
        if use_flash:
            from stoix_tpu.ops.pallas_attention import flash_attention_chunk

            pv_blk, m_blk, l_blk = flash_attention_chunk(
                q, k_blk, v_blk, q_pos, k_pos, causal=causal,
                block_q=block, block_k=block, interpret=interpret,
            )
        else:
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]
                mask = mask[None, None]  # broadcast over [B, H]
            else:
                mask = None
            m_blk, pv_blk, l_blk = _block_attend(q, k_blk, v_blk, scale, mask)

        m_new = jnp.maximum(m_acc, m_blk)
        # Rescale both accumulators onto the new max.
        alpha = jnp.exp(m_acc - m_new)  # old-acc scale
        beta = jnp.exp(m_blk - m_new)  # new-block scale
        l_new = l_acc * alpha + l_blk * beta
        o_new = o_acc * _bhs_to_bshd(alpha) + pv_blk * _bhs_to_bshd(beta)

        # Rotate K/V to the next neighbor (XLA overlaps this with compute).
        # The last iteration's rotation would be discarded — skip the hop
        # (r is replicated, so every device takes the same branch).
        def rotate(blks):
            perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]
            return tuple(jax.lax.ppermute(b, axis_name, perm) for b in blks)

        k_next, v_next = jax.lax.cond(
            r < axis_size - 1, rotate, lambda blks: blks, (k_blk, v_blk)
        )
        return (m_new, l_new, o_new, k_next, v_next), None

    (m_acc, l_acc, o_acc, _, _), _ = jax.lax.scan(
        step, (m_acc, l_acc, o_acc, k, v), jnp.arange(axis_size)
    )
    # Normalize; fully-masked rows (l == 0) return zeros.
    l_safe = jnp.where(l_acc == 0.0, 1.0, l_acc)
    return (o_acc / _bhs_to_bshd(l_safe)).astype(q.dtype)


def _bhs_to_bshd(x: jax.Array) -> jax.Array:
    """[B, H, S] -> [B, S, H, 1] for broadcasting against [B, S, H, D]."""
    return jnp.transpose(x, (0, 2, 1))[..., None]


def make_ring_attention(mesh: Mesh, axis: str = "data", causal: bool = False):
    """Host-side wrapper: global [B, S, H, D] arrays with S sharded over
    `axis`; batch/heads replicated. Returns a jitted callable."""
    seq_spec = P(None, axis)

    ring = jax.jit(
        jax.shard_map(
            partial(ring_attention, axis_name=axis, causal=causal),
            mesh=mesh,
            in_specs=(seq_spec, seq_spec, seq_spec),
            out_specs=seq_spec,
        )
    )
    return ring
