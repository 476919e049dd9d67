"""The gated delta rule with a per-channel decay (Kimi Delta Attention, Kimi
Linear, arXiv:2510.26692 section 3): ONE recurrence in four forms.

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
    other three are held to (the reference's and the tests').
  * `delta_rule_chunked` — whole sequences in chunks of C positions, plain
    JAX: the update off a TPU and for shapes that are no whole tiles (the CPU
    tests, the tiny presets), and what says what the kernel pair computes.
    Inside a chunk, with G_t the running sum of g from the chunk's
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
    `jax.grad` of the scan's, tests/test_ling3_ppo.py).
  * `delta_rule_update_kernel` — the same sums as a Pallas kernel pair (the
    update on a TPU; `delta_rule_update` chooses). A grid step is one chunk
    of 64 positions of 8 heads, read from q, k, v, g [B, T, H, d] where they
    lie; the chunk axis is the sequential one and a head's state stays in
    VMEM across it (transposed, [d_v, d_k]: a decay then multiplies lanes),
    so neither the state nor any chunk product goes to HBM. One chunk of 64
    takes a quarter of the turns of four of 16; what bounded C — dividing by
    exp(G) — is done inside sub-blocks of 16 positions, from the sub-block's
    MIDDLE (exponents within +-40 at g = -5, where the plain form's reach
    -80 and lose the smallest products to float32's floor). T is the inverse
    of the whole 64 x 64 unit lower-triangular matrix, by forward
    substitution a column a turn, elementwise in float32; every product is a
    float32 `dot_general` at DEFAULT precision accumulated in float32. The
    backward pass is a kernel of its own behind a `jax.custom_vjp`: the
    chunks in reverse with the state's gradient in VMEM, a chunk's products
    made again from its operands and the state it started from, which the
    forward pass wrote (8 x 64 KiB a sequence a head: 128 MiB a layer at 8 x
    512 tokens); its residuals are the operands and those states.

Shapes: q, k, g [B, T, H, d_k], v [B, T, H, d_v], beta [B, T, H]; a state [B,
H, d_k, d_v] float32. A sequence whose length is no multiple of C is padded
with positions that write nothing (k = 0, beta = 0) and decay nothing (g = 0).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# float32 operands, float32 accumulation, DEFAULT precision: a @ b.T, a @ b, a.T @ b
from stoix_tpu.ops.pallas_attention import _NN, _NT, _TN, _dot

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


# The update's kernel pair (`delta_rule_update_kernel`). A grid step holds one
# chunk of `UPDATE_CHUNK` positions of `_KERNEL_HEADS` heads; the chunk axis is
# the sequential one and a head's state stays in VMEM across it. The decays
# are divided inside sub-blocks of `_SUB` positions: exp(-sum g) over 16
# positions at g = -5 is exp(80), over 64 it is exp(320) and no float32.
UPDATE_CHUNK = 64
_SUB = 16


def delta_rule_update(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Whole sequences -> (o [B, T, H, d_v], the state after T): the kernel
    pair on a TPU where d_k = 128, d_v is a multiple of 128, the heads divide
    by the kernel's block and T is a multiple of its chunk; elsewhere
    `delta_rule_chunked` (`update_form` says which)."""
    form = update_form(k.shape[1], k.shape[2], k.shape[3], v.shape[3])
    rule = delta_rule_update_kernel if form == "kernel" else delta_rule_chunked
    return rule(q, k, v, g, beta, state)


def update_form(length: int, heads: int, d_k: int, d_v: int) -> str:
    """The form `delta_rule_update` takes here for sequences of these sizes."""
    tiles = d_k == _LANES and d_v % _LANES == 0 and heads % _KERNEL_HEADS == 0
    tiles = tiles and length % UPDATE_CHUNK == 0
    return "kernel" if jax.default_backend() == "tpu" and tiles else "chunked"


def _running_sum(x: jax.Array, period: int, reverse: bool = False) -> jax.Array:
    """x [C, d] summed down its rows from each block of `period` rows' start
    to the row, inclusive (`reverse`: from the row to the block's end), in
    log2(period) shifted adds — elementwise float32, no product."""
    size = x.shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) % period
    shift = 1
    while shift < period:
        if reverse:
            x = x + jnp.where(at + shift < period, pltpu.roll(x, size - shift, axis=0), 0.0)
        else:
            x = x + jnp.where(at >= shift, pltpu.roll(x, shift, axis=0), 0.0)
        shift *= 2
    return x


def _unit_lower_inverse_in_kernel(lower: jax.Array) -> jax.Array:
    """(I + L)^-1 of L [C, C] strictly lower-triangular by forward
    substitution, a column of L a turn: I + L is the product of (I + l_s
    e_s^T) over its columns l_s, so its inverse is (I - l_s e_s^T) applied in
    turn, X <- X - l_s X[s]. Elementwise float32; rows above s are not
    touched (their l_s is zero)."""
    size = lower.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, lower.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, lower.shape, 1)
    x = jnp.where(rows == cols, 1.0, 0.0)
    for s in range(size - 1):
        top = (s + 1) // 8 * 8  # whole sublane tiles
        below = x[top:] - lower[top:, s:s + 1] * x[s:s + 1, :]
        x = below if top == 0 else jnp.concatenate([x[:top], below], axis=0)
    return x


class _Chunk(NamedTuple):
    """What one chunk of one head makes of q, k, g without its state."""

    total: jax.Array  # [C, d] G_t: the sum of g from the chunk's start to t, inclusive
    grow: jax.Array  # [C, d] exp(G_t - M_i), M_i = G in the middle of t's sub-block i
    k_up: jax.Array  # [C, d] k grow
    q_up: jax.Array  # [C, d] q grow
    shrink: Tuple[jax.Array, ...]  # a sub-block i: [C, d] exp(M_i - G_r), 1 on the rows after i
    pairs: jax.Array  # [C, C] k+_s . k-_r, kept strictly below the diagonal
    seen: jax.Array  # [C, C] q+_t . k-_s, kept on and below the diagonal


def _chunk(q: jax.Array, k: jax.Array, g: jax.Array) -> _Chunk:
    """The products between the chunk's own positions. Row s of sub-block i
    against column r <= s: (k_s exp(G_s - M_i)) . (k_r exp(M_i - G_r)), M_i
    the sum up to the middle of i — on i's own rows both exponents lie within
    half a sub-block's decay of zero (exp(+-40) at g = -5: no float32 is lost
    at either end), on the rows before i the second is at most 1."""
    size = q.shape[0]
    sub = min(size, _SUB)
    local = _running_sum(g, sub)
    starts, middles = [jnp.zeros_like(g[:1])], []
    for i in range(size // sub):
        middles.append(starts[i] + local[i * sub + sub // 2 - 1:i * sub + sub // 2])
        starts.append(starts[i] + local[(i + 1) * sub - 1:(i + 1) * sub])
    by_row = lambda sums: jnp.concatenate(
        [jnp.broadcast_to(x, (sub, g.shape[1])) for x in sums], axis=0
    )
    total = local + by_row(starts[:-1])
    grow = jnp.exp(total - by_row(middles))
    k_up, q_up = k * grow, q * grow
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    shrink, found = [], []
    for i, middle in enumerate(middles):
        own = slice(i * sub, (i + 1) * sub)
        shrink.append(jnp.exp(jnp.where(row < (i + 1) * sub, middle - total, 0.0)))
        found.append(_dot(jnp.concatenate([k_up[own], q_up[own]], axis=0), k * shrink[-1], _NT))
    rows = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    pairs = jnp.concatenate([x[:sub] for x in found], axis=0)
    seen = jnp.concatenate([x[sub:] for x in found], axis=0)
    return _Chunk(
        total, grow, k_up, q_up, tuple(shrink), jnp.where(rows > cols, pairs, 0.0),
        jnp.where(rows >= cols, seen, 0.0),
    )


def _update_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, out_ref, end_ref, *rest, heads):
    """One chunk of `heads` heads: the state [d_v, d_k] (transposed: a decay
    then multiplies its lanes) moves on in `state_ref`. With a `starts_ref`,
    the state each chunk started from is kept for the backward pass."""
    starts_ref, state_ref = rest if len(rest) == 2 else (None,) + rest
    d_k, d_v = start_ref.shape[-2:]
    at = pl.program_id(2)

    @pl.when(at == 0)
    def _():
        for h in range(heads):
            state_ref[h] = start_ref[h].T

    for h in range(heads):
        q, k, g, beta = q_ref[:, h], k_ref[:, h], g_ref[:, h], beta_ref[:, h:h + 1]
        chunk = _chunk(q, k, g)
        solve = _unit_lower_inverse_in_kernel(beta * chunk.pairs)
        decay, end = jnp.exp(chunk.total), chunk.total[-1:]  # (`end`: G_C, the whole chunk's decay)
        state = state_ref[h]
        if starts_ref is not None:
            starts_ref[h] = state
        held = _dot(jnp.concatenate([k * decay, q * decay], axis=0), state, _NT)  # [2 C, d_v]
        size = q.shape[0]
        u = _dot(solve, beta * (v_ref[:, h] - held[:size]), _NN)
        out_ref[:, h] = held[size:] + _dot(chunk.seen, u, _NN)
        state_ref[h] = jnp.exp(end) * state + _dot(u, k * jnp.exp(end - chunk.total), _TN)

    @pl.when(at == pl.num_programs(2) - 1)
    def _():
        for h in range(heads):
            end_ref[h] = state_ref[h].T


def _update_bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, d_out_ref, starts_ref, d_end_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, d_beta_ref, d_start_ref, d_state_ref, *, heads,
):
    """The chunks in reverse: the gradient by the state [d_v, d_k] moves back
    in `d_state_ref`; a chunk's own products are made again from its
    operands and the state it started from."""
    d_k, d_v = d_end_ref.shape[-2:]
    at = pl.program_id(2)

    @pl.when(at == 0)
    def _():
        for h in range(heads):
            d_state_ref[h] = d_end_ref[h].T

    for h in range(heads):
        q, k, g, beta = q_ref[:, h], k_ref[:, h], g_ref[:, h], beta_ref[:, h:h + 1]
        d_out = d_out_ref[:, h]
        size, sub = q.shape[0], min(q.shape[0], _SUB)
        chunk = _chunk(q, k, g)
        solve = _unit_lower_inverse_in_kernel(beta * chunk.pairs)
        decay, end = jnp.exp(chunk.total), chunk.total[-1:]
        whole, to_end = jnp.exp(end), jnp.exp(end - chunk.total)
        k_in, q_in, k_end = k * decay, q * decay, k * to_end
        state, d_state = starts_ref[h], d_state_ref[h]
        left = v_ref[:, h] - _dot(k_in, state, _NT)  # V - K+ S_0
        u = _dot(solve, beta * left, _NN)

        d_u = _dot(chunk.seen, d_out, _TN) + _dot(k_end, d_state, _NT)
        d_scaled = _dot(solve, d_u, _TN)  # by beta (V - K+ S_0)
        d_left = beta * d_scaled
        rows = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
        lower = rows > cols
        d_solve = jnp.where(lower, -_dot(d_scaled, u, _NT), 0.0)  # by I + Diag(beta) A
        d_beta_ref[:, h:h + 1] = jnp.sum(d_scaled * left, axis=1, keepdims=True) + jnp.sum(
            d_solve * chunk.pairs, axis=1, keepdims=True
        )
        d_pairs = beta * d_solve
        d_seen = jnp.where(rows >= cols, _dot(d_out, u, _NT), 0.0)
        dv_ref[:, h] = d_left

        through = _dot(jnp.concatenate([-d_left, d_out], axis=0), state, _NN)  # [2 C, d_k]
        dk_end = _dot(u, d_state, _NN) * to_end
        dk_in, dq, dk_out = [], [], jnp.zeros_like(k)
        for i, shrink in enumerate(chunk.shrink):
            own = slice(i * sub, (i + 1) * sub)
            both = jnp.concatenate([d_pairs[own], d_seen[own]], axis=0)  # [2 sub, C]
            back = _dot(both, k * shrink, _NN)
            dk_in.append(back[:sub] * chunk.grow[own])
            dq.append(back[sub:] * chunk.grow[own])
            up = jnp.concatenate([chunk.k_up[own], chunk.q_up[own]], axis=0)
            dk_out += _dot(both, up, _TN) * shrink
        dq = through[size:] * decay + jnp.concatenate(dq, axis=0)
        dk_in = through[:size] * decay + jnp.concatenate(dk_in, axis=0)
        dq_ref[:, h] = dq
        dk_ref[:, h] = dk_in + dk_out + dk_end
        # G enters as exp(G) on q and k_in, exp(-G) on k_out, exp(G_C - G) on k_end and
        # exp(G_C) on the state
        d_total = dq * q + (dk_in - dk_out - dk_end) * k
        d_last = jnp.sum(dk_end * k, axis=0, keepdims=True) + whole * jnp.sum(
            d_state * state, axis=0, keepdims=True
        )
        last = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) == size - 1
        dg_ref[:, h] = _running_sum(d_total + jnp.where(last, d_last, 0.0), size, reverse=True)
        d_state_ref[h] = whole * d_state + _dot(
            jnp.concatenate([d_out, -d_left], axis=0), jnp.concatenate([q_in, k_in], axis=0), _TN
        )

    @pl.when(at == pl.num_programs(2) - 1)
    def _():
        for h in range(heads):
            d_start_ref[h] = d_state_ref[h].T


def _update_specs(batch, length, heads, d_k, d_v, chunk, reverse):
    """Block specs over the grid (sequence, block of heads, chunk): a chunk
    of operands [B, T, H, d] as they lie, beta [B, H / 8, T, 8], a state [B,
    H, d_k, d_v] and the chunks' starting states [B, N, H, d_v, d_k]."""
    step, chunks = _KERNEL_HEADS, length // chunk
    turn = (lambda n: chunks - 1 - n) if reverse else (lambda n: n)
    rows = lambda width: pl.BlockSpec(
        (None, chunk, step, width), lambda b, h, n: (b, turn(n), h, 0)
    )
    return {
        "keys": rows(d_k), "values": rows(d_v),
        "beta": pl.BlockSpec((None, None, chunk, step), lambda b, h, n: (b, h, turn(n), 0)),
        "state": pl.BlockSpec((None, step, d_k, d_v), lambda b, h, n: (b, h, 0, 0)),
        "starts": pl.BlockSpec((None, None, step, d_v, d_k), lambda b, h, n: (b, turn(n), h, 0, 0)),
        "grid": (batch, heads // step, chunks),
        "params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    }


def _by_head_block(beta: jax.Array) -> jax.Array:
    # [B, T, H] -> [B, H / 8, T, 8]: a block's heads are a tile's lanes
    batch, length, heads = beta.shape
    return beta.reshape(batch, length, heads // _KERNEL_HEADS, _KERNEL_HEADS).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "keep"), inline=True)
def _update_forward(q, k, v, g, beta, state, chunk, interpret, keep):
    """(jitted: a model's layers share ONE trace of the kernel's body a form,
    and one lowering)"""
    batch, length, heads, d_k = k.shape
    d_v = v.shape[-1]
    spec = _update_specs(batch, length, heads, d_k, d_v, chunk, reverse=False)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    kept = length // chunk, heads, d_v, d_k
    results = pl.pallas_call(
        functools.partial(_update_kernel, heads=_KERNEL_HEADS),
        grid=spec["grid"],
        in_specs=[
            spec["keys"], spec["keys"], spec["values"], spec["keys"], spec["beta"], spec["state"]
        ],
        out_specs=[spec["values"], spec["state"]] + [spec["starts"]] * keep,
        out_shape=[f32(*v.shape), f32(*state.shape)] + [f32(batch, *kept)] * keep,
        scratch_shapes=[pltpu.VMEM((_KERNEL_HEADS, d_v, d_k), jnp.float32)],
        compiler_params=spec["params"],
        name="delta_rule_update",
        interpret=interpret,
    )(q, k, v, g, _by_head_block(beta), state)
    return tuple(results)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _update(q, k, v, g, beta, state, chunk, interpret):
    return _update_forward(q, k, v, g, beta, state, chunk=chunk, interpret=interpret, keep=False)


def _update_fwd(q, k, v, g, beta, state, chunk, interpret):
    out, end, starts = _update_forward(
        q, k, v, g, beta, state, chunk=chunk, interpret=interpret, keep=True
    )
    return (out, end), (q, k, v, g, beta, starts)


def _update_bwd(chunk, interpret, residuals, cotangents):
    return _update_backward(*residuals, *cotangents, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"), inline=True)
def _update_backward(q, k, v, g, beta, starts, d_out, d_end, chunk, interpret):
    batch, length, heads, d_k = k.shape
    d_v = v.shape[-1]
    spec = _update_specs(batch, length, heads, d_k, d_v, chunk, reverse=True)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    by_block = _by_head_block(beta)
    dq, dk, dv, dg, d_beta, d_start = pl.pallas_call(
        functools.partial(_update_bwd_kernel, heads=_KERNEL_HEADS),
        grid=spec["grid"],
        in_specs=[
            spec["keys"], spec["keys"], spec["values"], spec["keys"], spec["beta"], spec["values"],
            spec["starts"], spec["state"],
        ],
        out_specs=[
            spec["keys"], spec["keys"], spec["values"], spec["keys"], spec["beta"], spec["state"]
        ],
        out_shape=[
            f32(*q.shape), f32(*k.shape), f32(*v.shape), f32(*g.shape), f32(*by_block.shape),
            f32(*d_end.shape),
        ],
        scratch_shapes=[pltpu.VMEM((_KERNEL_HEADS, d_v, d_k), jnp.float32)],
        compiler_params=spec["params"],
        name="delta_rule_update_bwd",
        interpret=interpret,
    )(q, k, v, g, by_block, d_out, starts, d_end)
    return dq, dk, dv, dg, d_beta.transpose(0, 2, 1, 3).reshape(beta.shape), d_start


_update.defvjp(_update_fwd, _update_bwd)


def delta_rule_update_kernel(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    state: Optional[jax.Array] = None, chunk: int = UPDATE_CHUNK, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """`delta_rule_chunked`'s sums as a Pallas kernel pair (forward; backward
    behind a `jax.custom_vjp`): d_k = 128, d_v a multiple of 128, heads a
    multiple of `_KERNEL_HEADS`, T a multiple of `chunk`, `chunk` 16 or a
    multiple of it; g >= -88 / 8 (half a sub-block). What the backward pass keeps is the
    operands and each chunk's starting state. `interpret` runs the Pallas
    interpreter (a test asks for it)."""
    return _update(q, k, v, g, beta, _initial(state, k, v), chunk, interpret)
