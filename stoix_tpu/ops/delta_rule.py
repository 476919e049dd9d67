"""The gated delta rule with a per-channel decay (Kimi Delta Attention, Kimi
Linear, arXiv:2510.26692 section 3): ONE recurrence in three forms.

A head's state is a matrix S [d_k, d_v] that every token rewrites. With q_t,
k_t [d_k], v_t [d_v], a log-decay g_t [d_k] <= 0 a channel and a write
strength beta_t in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,   S_0 = 0
    o_t = S_t^T q_t

which is: decay the rows, read what the state holds under k_t, and move that
answer a share beta_t of the way to v_t — S' = Diag(exp(g_t)) S_{t-1}, u_t =
beta_t (v_t - S'^T k_t), S_t = S' + k_t u_t^T.

  * `delta_rule_step` — one token against its state (the decode), every
    product elementwise in float32 (a matrix-vector product a head moves its
    matrix once whatever multiplies it). On a TPU, for states of whole tiles,
    the Pallas kernel `delta_rule_step_kernel`: a head's matrix is read into
    VMEM once, both reads and the write are made there, and it is written
    back in place — one read and one write of every state a step. Elsewhere
    the same sums in plain JAX, which XLA compiles to two passes over the
    states (a fusion that reads them for both reductions, one that reads
    them again and writes): three units of traffic for the kernel's two.
  * `delta_rule_scan` — whole sequences position by position, `lax.scan` over
    `delta_rule_step_plain`: the recurrence as it is written, and what the
    other two are held to.
  * `delta_rule_chunked` — whole sequences in chunks of C positions (the
    update). Inside a chunk, with G_t the running sum of g from the chunk's
    start, k+_t = k_t exp(G_t), k-_t = k_t exp(-G_t), q+_t = q_t exp(G_t):

        u_s = beta_s (v_s - S_0^T k+_s - sum_{r<s} (k+_s . k-_r) u_r)
        o_t = S_0^T q+_t + sum_{s<=t} (q+_t . k-_s) u_s
        S_C = Diag(exp(G_C)) S_0 + sum_s (k_s exp(G_C - G_s)) u_s^T

    so with A = strictly-lower(K+ K-^T) and T = (I + Diag(beta) A)^-1
    Diag(beta): U = T V - (T K+) S_0. A, T, T V, T K+ and the masked q+ k-
    products need no state and are made for every chunk at once; the loop
    over chunks carries S alone, three small matrix products a turn. T is the
    inverse of a unit lower-triangular C x C matrix, by forward substitution
    in float32. Dividing by exp(G) is what bounds C: the caller keeps g >=
    `lower_bound` (-5: `kda_lower_bound`), so exp(-G) <= exp(5 * 16) = exp(80)
    is finite in float32 (exp(88)) at C = 16, and every product k+_s . k-_r
    with r < s is exp(G_s - G_r) <= 1 a channel. The backward pass is JAX's
    own of these products and of the loop (`jax.grad` of this function is
    `jax.grad` of the scan's, tests/test_ling3_ppo.py); the caller
    rematerialises (networks/kda.py).

Shapes: q, k, g [B, T, H, d_k], v [B, T, H, d_v], beta [B, T, H]; a state [B,
H, d_k, d_v] float32. A sequence whose length is no multiple of C is padded
with positions that write nothing (k = 0, beta = 0) and decay nothing (g = 0).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 16
# Heads a grid step of the decode kernel: their exp(g), k and q rows (3 x 8)
# become columns by ONE transpose of a 128 x 128 tile, and 8 matrices of 128
# x 128 float32 are 512 KiB in and as much out.
_KERNEL_HEADS = 8
_LANES = 128


def delta_rule_step(
    state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """state [B, H, d_k, d_v]; q, k, g [B, H, d_k]; v [B, H, d_v]; beta [B,
    H] -> (o [B, H, d_v], the state one token on): the kernel on a TPU where
    the state is whole tiles, else the plain sums."""
    _, heads, d_k, d_v = state.shape
    tiles = d_k == _LANES and d_v % _LANES == 0 and heads % _KERNEL_HEADS == 0
    on_chip = jax.default_backend() == "tpu" and tiles
    return (delta_rule_step_kernel if on_chip else delta_rule_step_plain)(state, q, k, v, g, beta)


def delta_rule_step_plain(
    state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The first pass reads the decayed state under k and under q; the
    second writes S' + k u^T, and o = S'^T q + (k . q) u needs no third. g =
    -inf is a decay of nothing left: the state is read as zeros whatever it
    holds (how a caller starts a new sequence without a pass over the state)."""
    decay = jnp.exp(g)[..., None]
    decayed = jnp.where(decay > 0.0, state * decay, 0.0)
    under_k = jnp.sum(decayed * k[..., None], axis=-2)
    under_q = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - under_k)
    out = under_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return out, decayed + k[..., None] * u[..., None, :]


def _step_kernel(state_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, new_ref, *, heads):
    """`heads` heads of one sequence. The vectors arrive as rows (lane-major);
    a decay and a key multiply the state's ROWS, so exp(g), k and q are turned
    into columns by one transpose of their rows stacked in a square tile."""
    q, k = q_ref[0], k_ref[0]  # [heads, d_k]
    rows = jnp.concatenate([jnp.exp(g_ref[0]), k, q], axis=0)  # [3 heads, d_k]
    fill = jnp.zeros((rows.shape[1] - rows.shape[0], rows.shape[1]), rows.dtype)
    cols = jnp.concatenate([rows, fill], axis=0).T  # [d_k, d_k]: column j is row j
    k_dot_q = jnp.sum(k * q, axis=-1, keepdims=True)  # [heads, 1]
    for h in range(heads):
        decay, k_col, q_col = (cols[:, at + h:at + h + 1] for at in (0, heads, 2 * heads))
        decayed = jnp.where(decay > 0.0, state_ref[0, h] * decay, 0.0)  # [d_k, d_v]
        under_k = jnp.sum(decayed * k_col, axis=0, keepdims=True)  # [1, d_v]
        under_q = jnp.sum(decayed * q_col, axis=0, keepdims=True)
        u = beta_ref[0, h:h + 1] * (v_ref[0, h:h + 1] - under_k)
        out_ref[0, h:h + 1] = under_q + k_dot_q[h:h + 1] * u
        new_ref[0, h] = decayed + k_col * u


def delta_rule_step_kernel(
    state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """`delta_rule_step` as one Pallas kernel: a grid step holds
    `_KERNEL_HEADS` heads' matrices of one sequence in VMEM, and the new
    state takes the old one's place (`input_output_aliases`). d_k = 128, d_v
    a multiple of 128, heads a multiple of `_KERNEL_HEADS`. `interpret` runs
    the Pallas interpreter (a test asks for it)."""
    batch, heads, d_k, d_v = state.shape
    step = _KERNEL_HEADS
    vectors = lambda width: pl.BlockSpec((1, step, width), lambda b, h: (b, h, 0))
    matrices = pl.BlockSpec((1, step, d_k, d_v), lambda b, h: (b, h, 0, 0))
    out, new = pl.pallas_call(
        functools.partial(_step_kernel, heads=step),
        grid=(batch, heads // step),
        in_specs=[matrices, vectors(d_k), vectors(d_k), vectors(d_v), vectors(d_k), vectors(d_v)],
        out_specs=[vectors(d_v), matrices],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct(state.shape, state.dtype)
        ],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name="delta_rule_step",
        interpret=interpret,
    )(state, q, k, v, g, jnp.broadcast_to(beta[..., None], v.shape))
    return out, new


def _initial(state: Optional[jax.Array], k: jax.Array, v: jax.Array) -> jax.Array:
    if state is not None:
        return state
    batch, _, heads, d_k = k.shape
    return jnp.zeros((batch, heads, d_k, v.shape[-1]), jnp.float32)


def delta_rule_scan(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Position by position -> (o [B, T, H, d_v], the state after T): the
    plain sums, which JAX differentiates (the kernel has no backward)."""

    def one(state, at):
        out, state = delta_rule_step_plain(state, *at)
        return state, out

    by_position = lambda x: jnp.swapaxes(x, 0, 1)
    state, out = jax.lax.scan(
        one, _initial(state, k, v), tuple(by_position(x) for x in (q, k, v, g, beta))
    )
    return by_position(out), state


def _unit_lower_inverse(lower: jax.Array) -> jax.Array:
    """(I + L)^-1 of L [..., C, C] strictly lower-triangular, row by row:
    row_s = e_s - sum_{r<s} L_sr row_r. Elementwise in float32: C is 16."""
    size = lower.shape[-1]
    eye = jnp.eye(size, dtype=lower.dtype)
    rows = [jnp.broadcast_to(eye[0], lower.shape[:-1])]
    for s in range(1, size):
        above = jnp.stack(rows, axis=-2)  # [..., s, C]
        rows.append(eye[s] - jnp.sum(lower[..., s, :s, None] * above, axis=-2))
    return jnp.stack(rows, axis=-2)


def delta_rule_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    state: Optional[jax.Array] = None, chunk: int = CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """In chunks of `chunk` positions -> (o [B, T, H, d_v], the state after
    T). g >= -88 / chunk keeps exp(-G) finite in float32."""
    batch, length, heads, _ = k.shape
    state = _initial(state, k, v)
    pad = -length % chunk
    if pad:
        widen = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = (widen(x) for x in (q, k, v, g, beta))
    chunks = (length + pad) // chunk
    # [B, T, H, .] -> [N, B, H, C, .]: the loop over chunks takes its slices as they lie
    blocks = lambda x: x.reshape(batch, chunks, chunk, heads, -1).transpose(1, 0, 3, 2, 4)
    q, k, v, g = (blocks(x) for x in (q, k, v, g))
    beta = blocks(beta[..., None])[..., 0]  # [N, B, H, C]

    decay = jnp.cumsum(g, axis=-2)  # G_t: from the chunk's start to t, inclusive
    decay_end = decay[..., -1:, :]
    k_in, k_out, q_in = k * jnp.exp(decay), k * jnp.exp(-decay), q * jnp.exp(decay)
    k_end = k * jnp.exp(decay_end - decay)  # what a write at s is worth at the chunk's end
    below = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    pairs = jnp.einsum("nbhsd,nbhrd->nbhsr", k_in, k_out)
    solve = _unit_lower_inverse(jnp.where(below, pairs, 0.0) * beta[..., None])
    solve = solve * beta[..., None, :]  # T = (I + Diag(beta) A)^-1 Diag(beta)
    written = jnp.einsum("nbhsr,nbhrd->nbhsd", solve, v)  # T V
    held = jnp.einsum("nbhsr,nbhrd->nbhsd", solve, k_in)  # T K+
    seen = jnp.where(
        below | jnp.eye(chunk, dtype=bool), jnp.einsum("nbhsd,nbhrd->nbhsr", q_in, k_out), 0.0
    )

    def one(state, at):
        written, held, q_in, seen, k_end, decay_end = at
        u = written - jnp.einsum("bhsk,bhkv->bhsv", held, state)
        out = jnp.einsum("bhsk,bhkv->bhsv", q_in, state) + jnp.einsum("bhsr,bhrv->bhsv", seen, u)
        state = decay_end[..., None] * state + jnp.einsum("bhsk,bhsv->bhkv", k_end, u)
        return state, out

    state, out = jax.lax.scan(
        one, state, (written, held, q_in, seen, k_end, jnp.exp(decay_end[..., 0, :]))
    )
    # [N, B, H, C, d_v] -> [B, T, H, d_v]
    out = out.transpose(1, 0, 3, 2, 4).reshape(batch, chunks * chunk, heads, -1)
    return out[:, :length], state
