"""A projection's per-head RMS norm and rotate-half rotation as ONE Pallas pass
each way: what `networks/sdar.py::gqa_qkv` does to q and to k between the
projection and the attention, where XLA makes of `rms_norm` + `rope` a chain
of relayout copies and fusions each of the array's size (six forward and five
backward for q at 16 x 1,540 x 32 x 128: PERF.md section 6, PR 42).

    y[..., h, :] = rope(x[..., h, :] * rsqrt(mean(x[..., h, :]^2) + eps) * weight)

`x` [B, P, heads * head_dim] is the projection's result as the matmul leaves
it: a row's heads side by side, a head a whole number of 128-lane groups. A
grid step is `_ROWS` rows of one sequence (B and P stay two grid axes: P need
be no multiple of 8, and folding the two would be a copy; the last row tile is
partial). Head by head: the mean of squares is a lane reduction inside the
row, the weight a [1, head_dim] multiply, rotate-half a lane roll by half a
head (`pltpu.roll`) times a sine whose first half carries the minus sign. The
cosine and signed sine come as tables [B, P, head_dim] made outside from the
same angles as `networks/olmoe.py::rope`, so the kernel and the plain path
multiply by the same numbers. float32 throughout.

The result is [B, P, heads, head_dim] as XLA lays such an array out and as
`ops/pallas_attention.py::block_mask_attention` reads q: a head a sublane.
Where the heads fill whole sublane tiles (a multiple of 8: q's 32) the kernel
writes that itself — the array seen as rows [B, P * heads, head_dim], head h of
a tile's positions the rows h, h + heads, ..., one strided store of whole
vector registers (indexing the head axis instead, `ref[:, h, :]`, Mosaic
turns into a store a ROW: eight times the stores). Fewer heads (k's 4) would
be padded to 8 sublanes, twice the bytes: they are written side by side as
they came and reshaped outside, which the score kernel's own reshape of k
undoes. The backward kernel reads the cotangent in the same form and the rows
again, turns the rotation back, applies the norm's backward, writes the rows'
cotangent once, and leaves the weight's gradient as an [8, head_dim] partial
sum a grid step, added up outside. Nothing of the array's size is made on
either side but the kernel's own result.

`norm_rope_form` says where the pair runs: a TPU, heads of whole lane groups
and sequences of at least one row tile; `rms_norm` + `rope` elsewhere (the
caller's plain path).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stoix_tpu.observability import SCOPES, annotate
from stoix_tpu.ops.pallas_attention import _out_struct

_LANES, _SUBLANES = 128, 8
# Rows a grid step: 128 rows of 32 heads of 128 are 2 MiB in and 2 MiB out,
# twice each for the pipeline, inside the 16 MiB a kernel gets unasked (256
# rows with 32 heads unrolled were not).
_ROWS = 128
# A row tile up to this many bytes (32 heads of 128) runs in the VMEM a kernel
# gets unasked; a wider one (64 heads: 4 MiB in, out and, backward, the
# cotangent, each held twice) asks for twelve tiles' worth.
_UNASKED_TILE_BYTES = 2 * 1024 * 1024


def norm_rope_form(rows: int, head_dim: int) -> str:
    """The form the per-head norm and rotation take here for sequences of
    `rows` positions: `kernel` (the Pallas pair) or `plain` (`rms_norm` +
    `rope` as XLA compiles them). A pass over fewer rows a sequence than one
    tile (the rollout's block passes: 4) is a few MB and stays plain."""
    whole = head_dim % _LANES == 0 and rows >= _ROWS
    return "kernel" if jax.default_backend() == "tpu" and whole else "plain"


def _side_by_side(h: int, head_dim: int):
    """Head `h` of a row tile [rows, heads * head_dim]."""
    return (slice(None), slice(h * head_dim, (h + 1) * head_dim))


def _in_result(h: int, heads: int, head_dim: int):
    """Head `h` of a result tile: where the heads fill whole sublane tiles the
    tile is rows [rows * heads, head_dim] and the head every `heads`-th of
    them; else it lies as the input does."""
    if heads % _SUBLANES == 0:
        return (pl.ds(h, _ROWS, stride=heads), slice(None))
    return _side_by_side(h, head_dim)


def _result_shape(shape: Tuple[int, int, int], heads: int) -> Tuple[int, int, int]:
    """The result of rows `shape` [B, P, heads * head_dim], as `_in_result` indexes it."""
    batch, rows, width = shape
    return (batch, rows * heads, width // heads) if heads % _SUBLANES == 0 else shape


def _fwd_kernel(x_ref, w_ref, cos_ref, sin_ref, o_ref, *, heads: int, eps: float):
    head_dim = w_ref.shape[1]
    weight, cos, sin = w_ref[...], cos_ref[...], sin_ref[...]
    for h in range(heads):
        x = x_ref[_side_by_side(h, head_dim)]
        normed = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight
        # rotate-half; `sin` carries the half's sign
        o_ref[_in_result(h, heads, head_dim)] = (
            normed * cos + pltpu.roll(normed, head_dim // 2, 1) * sin
        )


def _bwd_kernel(
    dy_ref, x_ref, w_ref, cos_ref, sin_ref, dx_ref, dw_ref, *, heads: int, eps: float, rows: int
):
    head_dim = w_ref.shape[1]
    weight, cos, sin = w_ref[...], cos_ref[...], sin_ref[...]
    # The last tile's rows past the sequence's end hold whatever was there:
    # their results are dropped with the block's overhang, but they may not
    # join the weight's sum.
    at_row = pl.program_id(1) * _ROWS + jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)
    real = at_row < rows
    d_weight = jnp.zeros((_ROWS, head_dim), jnp.float32)
    for h in range(heads):
        dy, x = dy_ref[_in_result(h, heads, head_dim)], x_ref[_side_by_side(h, head_dim)]
        # y = n cos + roll(n) sin, and a roll by half a head is its own
        # transpose: dn = dy cos + roll(dy sin).
        d_normed = dy * cos + pltpu.roll(dy * sin, head_dim // 2, 1)
        inverse_rms = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
        scaled = x * inverse_rms
        d_weight = d_weight + jnp.where(real, d_normed * scaled, 0.0)
        d_scaled = d_normed * weight
        # n = x r w with r = rsqrt(mean(x^2) + eps): dx = r (g - x r mean(g x r)), g = dn w.
        inner = jnp.mean(d_scaled * scaled, axis=-1, keepdims=True)
        dx_ref[_side_by_side(h, head_dim)] = inverse_rms * (d_scaled - scaled * inner)
    dw_ref[...] = jnp.sum(d_weight.reshape(_ROWS // _SUBLANES, _SUBLANES, head_dim), axis=0)


def _call(kernel, name, spec, x, operands, kinds, outs):
    """The call both kernels share: a grid of (sequence, row tile); `kinds`
    names each operand's block and `outs` each result's."""
    heads, _, interpret = spec
    batch, rows, width = x.shape
    head_dim = width // heads
    tiles = pl.cdiv(rows, _ROWS)
    tile_bytes = _ROWS * width * 4
    shapes = {
        "rows": x.shape, "result": _result_shape(x.shape, heads),
        "partial": (batch, tiles, _SUBLANES, head_dim),
    }
    a_tile = lambda shape: pl.BlockSpec((None,) + shape[1:], lambda b, i: (b, i, 0))
    blocks = {
        "rows": a_tile((1, _ROWS, width)),
        "result": a_tile(_result_shape((1, _ROWS, width), heads)),
        "weight": pl.BlockSpec((1, head_dim), lambda b, i: (0, 0)),
        "table": a_tile((1, _ROWS, head_dim)),
        "partial": pl.BlockSpec((None, None, _SUBLANES, head_dim), lambda b, i: (b, i, 0, 0)),
    }
    return pl.pallas_call(
        kernel,
        grid=(batch, tiles),
        in_specs=[blocks[kind] for kind in kinds],
        out_specs=[blocks[kind] for kind in outs],
        out_shape=[_out_struct(shapes[kind], jnp.float32, *operands) for kind in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            **({"vmem_limit_bytes": 12 * tile_bytes} if tile_bytes > _UNASKED_TILE_BYTES else {}),
        ),
        name=name,
        interpret=interpret,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _norm_rope(x, weight, cos, sin, spec):
    heads, eps, _ = spec
    kernel = functools.partial(_fwd_kernel, heads=heads, eps=eps)
    kinds = ["rows", "weight", "table", "table"]
    out, = _call(kernel, "qk_norm_rope", spec, x, (x, weight[None], cos, sin), kinds, ["result"])
    return out.reshape(x.shape[:2] + (heads, x.shape[2] // heads))


def _norm_rope_fwd(x, weight, cos, sin, spec):
    return _norm_rope(x, weight, cos, sin, spec), (x, weight, cos, sin)


def _norm_rope_bwd(spec, residuals, d_out):
    x, weight, cos, sin = residuals
    heads, eps, _ = spec
    kernel = functools.partial(_bwd_kernel, heads=heads, eps=eps, rows=x.shape[1])
    kinds = ["result", "rows", "weight", "table", "table"]
    # The backward pass's ops carry the scope the forward's do, whatever name
    # stack the rule is traced under.
    with annotate(SCOPES["attention"]):
        d_out = d_out.reshape(_result_shape(x.shape, heads))
        dx, partial = _call(
            kernel, "qk_norm_rope_bwd", spec, x, (d_out, x, weight[None], cos, sin), kinds,
            ["rows", "partial"],
        )
        d_weight = jnp.sum(partial, axis=(0, 1, 2))
    return dx, d_weight, jnp.zeros_like(cos), jnp.zeros_like(sin)


_norm_rope.defvjp(_norm_rope_fwd, _norm_rope_bwd)


def _rotation_tables(angles: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """angles [..., head_dim] (a position's angles, the half repeated, as
    `networks/olmoe.py::rope` makes them) -> (cos, sin with the sign of
    rotate-half's first half): rotate(x) = x cos + roll(x, half) sin."""
    half = angles.shape[-1] // 2
    sign = jnp.where(jnp.arange(2 * half) < half, -1.0, 1.0)
    return jnp.cos(angles), jnp.sin(angles) * sign


def qk_norm_rope(
    x: jax.Array, weight: jax.Array, angles: jax.Array, *, heads: int, eps: float,
    interpret: bool = False,
) -> jax.Array:
    """x [B, P, heads * head_dim] float32 (a projection's result), `weight`
    [head_dim], `angles` [B, P, head_dim] -> [B, P, heads, head_dim]: every
    head normalised over its `head_dim`, times the weight, rotated. One Pallas
    kernel forward and one backward (module docstring); `interpret` runs the
    Pallas interpreter (a test asks for it)."""
    cos, sin = _rotation_tables(angles)
    return _norm_rope(x, weight, cos, sin, (heads, float(eps), interpret))
