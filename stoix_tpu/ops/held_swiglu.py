"""The held experts' SwiGLU of a decode step as ONE weight-streaming Pallas
kernel: a few dozen gathered rows against every held expert's float32
`gate`, `up` and `down`, bound by reading those weights once.

`networks/olmoe.py::_held_swiglu` multiplies a chunk of expert-sorted rows by
three `jax.lax.ragged_dot`s. At a decode step's chunk the products are a few
GFLOP beside hundreds of MB of weights, and XLA's grouped-matmul kernel reads
them at 45 to 76% of their bytes' pace, least where its tile misfits the
operands (PERF.md section 6, PR 48, has the table). `held_swiglu_decode`
streams the weights in blocks that divide the operands as they are. A grid
step fetches, of ALL the held experts at once, one block `[E, chunk, 128]` of
`gate` and of `up` (`chunk` rows of `hidden`, 128 lanes of `width`) and one
block `[E, 128, chunk]` of `down`, a few MB together and held twice while
the next are fetched, inside the vector memory a kernel gets unasked. Every
expert multiplies ALL the chunk's rows — one grouped (batched) product over
the held experts for `gate` and one for `up`, summed over the chunks of
`hidden` — and a row keeps its own expert's `silu(gate) * up` under a mask,
zeros for the others; so `down` is ONE product over (expert, lane) with the
experts' hidden tiles side by side. The products of `down` run one tile of
`width` behind those of `gate` and `up` (a tile's hidden activations are
whole only after its last chunk), which keeps every grid step's fetch the
same few MB and the first fetch, which nothing hides, small. The `[rows,
hidden]` result stays in vector memory for the whole call. In the cell that
takes it (16 sequences, 8 held experts `[2304, 896]`) a call takes 0.245 to
0.266 ms for 0.242 ms of bytes at HBM's pace. A grid over (expert, tile) that
skips an expert no row reached took 0.237 ms alone against 0.474 for the
three `ragged_dot`s, and was not kept: a product over ONE expert's block is
no grouped product to `benchmarks/references/`' reading of a decode step
(`stated_mismatches` asks for a right operand `[E, ., .]`), and no run of
such a program is `correct` (PERF.md section 6, PR 48).

Precision is `ragged_dot`'s at DEFAULT: float32 operands handed to the MXU as
they lie, which rounds them to bfloat16 in one pass and accumulates in
float32 (the same largest error against HIGHEST as the `ragged_dot`s', to six
digits, at every measured shape). No copy of the weights in another type is
stored anywhere.

No VJP: the update's and the prefill's chunks never take this form
(`olmoe.held_swiglu_form`), and `_held_experts`' backward differentiates the
`ragged_dot`s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stoix_tpu.ops.pallas_attention import _out_struct

# A block's lanes of the experts' width.
TILE = 128
# The most a grid step fetches (its three weight blocks): held twice, they
# leave room for the rows, the result and the products inside ...
_STEP_BYTES = 5 * 1024 * 1024
# ... the vector memory a kernel gets unasked on a v5e.
_VMEM_UNASKED = 16 * 1024 * 1024


def _chunk(hidden: int, count: int) -> int:
    """Rows of `hidden` a block holds: the most whole lanes' worth that
    divides `hidden` and keeps a grid step's fetch inside `_STEP_BYTES`."""
    inside = lambda chunk: hidden % chunk == 0 and 3 * count * chunk * TILE * 4 <= _STEP_BYTES
    return max([chunk for chunk in range(TILE, hidden + 1, TILE) if inside(chunk)], default=TILE)


def fits(rows: int, hidden: int, width: int, count: int) -> bool:
    """Whether a chunk of `rows` rows at these widths is the kernel's: whole
    tiles, and inside the vector memory a kernel gets unasked the weight
    blocks (twice), the rows, the result, the three scratch arrays and the
    products of one grid step, a tenth of it left over (compiled for a
    described v5e, 8 experts at hidden 2304 are accepted with 128 rows and
    refused with 192, 16 at hidden 2048 accepted with 160 and refused with
    192: this sum is 16.0 and 16.8 MB at the two accepted, 19.3 and 18.9 at
    the two refused)."""
    whole = rows % 8 == 0 and hidden % TILE == 0 and width % TILE == 0
    chunk = _chunk(hidden, count)
    blocks = 2 * 3 * count * chunk * TILE * 4
    resident = 2 * rows * hidden * 4 + 3 * count * rows * TILE * 4
    products = count * rows * (chunk + 2 * TILE) * 4
    return whole and blocks + resident + products <= 0.9 * _VMEM_UNASKED


def _kernel(expert_ref, x_ref, gate_ref, up_ref, down_ref, out_ref, gate_scr, up_scr, hidden_scr):
    tile, k = pl.program_id(0), pl.program_id(1)
    tiles, chunks = pl.num_programs(0) - 1, pl.num_programs(1)
    count, chunk, _ = gate_ref.shape
    columns = (slice(None), pl.ds(pl.multiple_of(k * chunk, TILE), chunk))  # this chunk of `hidden`

    @pl.when((tile == 0) & (k == 0))
    def _start():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(tile > 0)
    def _down():  # of the tile before, whose hidden activations are whole
        out_ref[columns] += jnp.dot(
            hidden_scr[...], down_ref[...].reshape(count * TILE, chunk),
            preferred_element_type=jnp.float32,
        )

    @pl.when(tile < tiles)
    def _gate_and_up():
        rows = x_ref[columns]
        rows = jnp.broadcast_to(rows[None], (count,) + rows.shape)
        grouped = lambda w_ref: jax.lax.dot_general(  # [E, rows, chunk] x [E, chunk, TILE]
            rows, w_ref[...], (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )
        gate, up = grouped(gate_ref), grouped(up_ref)

        @pl.when(k == 0)
        def _first():
            gate_scr[...] = gate
            up_scr[...] = up

        @pl.when(k > 0)
        def _further():
            gate_scr[...] += gate
            up_scr[...] += up

        @pl.when(k == chunks - 1)
        def _whole():
            hidden = jax.nn.silu(gate_scr[...]) * up_scr[...]  # [E, rows, TILE]
            own = expert_ref[...][None] == jax.lax.broadcasted_iota(jnp.int32, hidden.shape, 0)
            # Another expert's rows are exactly 0 (a select, not a product),
            # so one product over (expert, lane) adds each row its own expert's.
            hidden = jnp.where(own, hidden, 0.0)
            hidden_scr[...] = jnp.concatenate([hidden[e] for e in range(count)], axis=-1)


def held_swiglu_decode(
    gathered: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array, sizes: jax.Array, *,
    interpret: bool = False,
) -> jax.Array:
    """(silu(x gate[e]) * (x up[e])) down[e] for each row x of `gathered`
    [rows, D] with e its expert: the rows lie sorted by expert, `sizes` [E]
    of them to each of `gate`, `up` [E, D, F] and `down` [E, F, D] in turn.
    -> [rows, D] float32. Rows past the last group come back as zeros.
    `interpret` runs the Pallas interpreter (a test asks for it)."""
    rows, hidden = gathered.shape
    count, _, width = gate.shape
    if not fits(rows, hidden, width, count):
        raise ValueError(
            f"no whole tiles, or too many, in {rows} rows of {count} experts [{hidden}, {width}]"
        )
    chunk, tiles = _chunk(hidden, count), width // TILE
    # Row r's expert: the groups that end at or before it (`count` past the last group).
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    expert = jnp.sum(jnp.arange(rows, dtype=jnp.int32)[:, None] >= ends[None, :], axis=1)

    # A tile of `width` runs through the chunks of `hidden` while `gate` and
    # `up` are multiplied, and `down`'s blocks follow a tile behind; the one
    # tile more than `width` has asks `gate` and `up` for the block that is
    # resident, and so does `down` in the first.
    def ahead(tile, k):
        return 0, jnp.where(tile < tiles, k, hidden // chunk - 1), jnp.minimum(tile, tiles - 1)

    def behind(tile, k):
        return 0, jnp.maximum(tile - 1, 0), jnp.where(tile > 0, k, 0)

    whole = pl.BlockSpec((rows, hidden), lambda tile, k: (0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(tiles + 1, hidden // chunk),
        in_specs=[
            pl.BlockSpec((rows, 1), lambda tile, k: (0, 0)),
            whole,
            pl.BlockSpec((count, chunk, TILE), ahead),
            pl.BlockSpec((count, chunk, TILE), ahead),
            pl.BlockSpec((count, TILE, chunk), behind),
        ],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((count, rows, TILE), jnp.float32),
            pltpu.VMEM((count, rows, TILE), jnp.float32),
            pltpu.VMEM((rows, count * TILE), jnp.float32),
        ],
        out_shape=_out_struct((rows, hidden), jnp.float32, gathered, gate, up, down, sizes),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        name="held_swiglu_decode",
        interpret=interpret,
    )(expert[:, None], gathered.astype(jnp.float32), gate, up, down)
