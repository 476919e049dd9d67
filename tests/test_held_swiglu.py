"""The held experts' SwiGLU of a decode step as one Pallas kernel
(`ops/held_swiglu.py`) against `olmoe._held_swiglu`'s three `ragged_dot`s, in
the Pallas interpreter at widths that are odd multiples of 128; and the rule
that says where the kernel is taken (`olmoe.held_swiglu_form`), a pure
function of the backend and the chunk's shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu.networks import lfm2, olmoe, sdar
from stoix_tpu.ops import held_swiglu

ROWS, EXPERTS, HIDDEN, WIDTH = 32, 4, 384, 640  # 3 x 128 and 5 x 128

SIZES = {
    "uniform sizes": [8, 8, 8, 8],
    "an empty expert": [10, 0, 12, 10],
    "the first and the last expert empty": [0, 20, 12, 0],
    "every row on one expert": [0, 0, 32, 0],
    "rows past the last group": [5, 0, 9, 3],
    "one row": [0, 1, 0, 0],
    "no row": [0, 0, 0, 0],
}


def _operands():
    keys = jax.random.split(jax.random.PRNGKey(48), 4)
    return (
        jax.random.normal(keys[0], (ROWS, HIDDEN)),
        jax.random.normal(keys[1], (EXPERTS, HIDDEN, WIDTH)) * 0.05,
        jax.random.normal(keys[2], (EXPERTS, HIDDEN, WIDTH)) * 0.05,
        jax.random.normal(keys[3], (EXPERTS, WIDTH, HIDDEN)) * 0.05,
    )


@pytest.mark.parametrize("chunks", [1, 3], ids=["one chunk of hidden", "three chunks of hidden"])
@pytest.mark.parametrize("case", list(SIZES))
def test_the_kernel_is_the_three_ragged_dots_on_the_valid_rows(case, chunks, monkeypatch):
    # a block of all of `hidden`, or of a third: 128 rows of it for each of the 4 experts
    monkeypatch.setattr(held_swiglu, "_STEP_BYTES", 3 * EXPERTS * (HIDDEN // chunks) * 128 * 4)
    assert held_swiglu._chunk(HIDDEN, EXPERTS) == HIDDEN // chunks
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    valid = int(sizes.sum())
    operands = _operands()
    got = held_swiglu.held_swiglu_decode(*operands, sizes, interpret=True)
    assert got.shape == (ROWS, HIDDEN) and got.dtype == jnp.float32
    # Rows past the last group belong to no expert and are compared nowhere
    # (`_held_experts` masks them with `at.valid`); the kernel leaves zeros.
    np.testing.assert_array_equal(got[valid:], 0.0)
    if valid:
        want = olmoe._held_swiglu_ragged(*operands, sizes)[:valid]
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 1.0
        # float32 products on the CPU, both: the sums' order alone parts them
        np.testing.assert_allclose(got[:valid], want, atol=1e-5 * scale, rtol=0)


@pytest.mark.parametrize("hidden, count, chunk", [
    (2304, 8, 384), (2048, 8, 256), (2560, 8, 256), (2048, 16, 128), (384, 4, 384), (2048, 64, 128),
])
def test_a_blocks_rows_of_hidden_divide_it_and_keep_a_steps_fetch_small(hidden, count, chunk):
    """`gate`'s, `up`'s and `down`'s blocks of one grid step: whole lanes'
    worth of `hidden` that divide it, 5 MB together at most (held twice
    inside the 16 MiB a kernel gets unasked), never under a lane tile."""
    assert held_swiglu._chunk(hidden, count) == chunk
    assert hidden % chunk == 0 and chunk % held_swiglu.TILE == 0


def test_the_references_own_reading_of_the_program_finds_the_grouped_products():
    """What `benchmarks/references/ppo_*.py::stated_mismatches` asks of a
    decode step, read the way it reads it (`matmuls_of` walks into kernels):
    every product multiplies float32 at DEFAULT — nothing is rounded before
    the MXU rounds it — and `gate`'s and `up`'s are grouped over the held
    experts, a right operand `[E, chunk, 128]`."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import loader

    matmuls = loader.load_reference("ppo_olmoe", root).matmuls_of(
        lambda *operands: held_swiglu.held_swiglu_decode(*operands),
        *_operands(), jnp.asarray(SIZES["uniform sizes"], jnp.int32),
    )
    assert len(matmuls) == 3
    assert all(m["dtypes"] == ["float32"] and m["precision"] == "DEFAULT" for m in matmuls)
    grouped = [m["rhs"] for m in matmuls if len(m["rhs"]) == 3]
    assert grouped == [(EXPERTS, HIDDEN, 128)] * 2
    assert [m["rhs"] for m in matmuls if len(m["rhs"]) == 2] == [(EXPERTS * 128, HIDDEN)]


@pytest.mark.parametrize("shape, why", [
    ((20, 384, 640, 4), "rows that are no whole tiles"),
    ((32, 320, 640, 4), "a hidden size off the lanes"),
    ((32, 384, 600, 4), "a width off the lanes"),
    ((640, 2048, 768, 16), "more rows than vector memory holds beside the weight blocks"),
    ((64, 2048, 1024, 64), "weight blocks of all the experts larger than vector memory"),
])
def test_the_kernel_refuses_what_it_cannot_tile(shape, why):
    assert not held_swiglu.fits(*shape), why
    rows, hidden, width, count = shape
    zeros = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    with pytest.raises(ValueError, match="whole tiles"):
        jax.eval_shape(
            held_swiglu.held_swiglu_decode, zeros(rows, hidden), zeros(count, hidden, width),
            zeros(count, hidden, width), zeros(count, width, hidden),
            jax.ShapeDtypeStruct((count,), jnp.int32),
        )


# The decode chunks of the six held-expert cells (benchmarks/configs/,
# benchmarks/traffic/): tokens a rollout step | an evaluator step, top-k, the
# router's experts, held, hidden, width, the chunk's room in deviations.
CELLS = {
    "mellum2": ((16, 16), 8, 64, 8, 2304, 896, 5.0),
    "kanana2": ((128, 32), 6, 128, 16, 2048, 768, 5.0),
    "lfm2": ((128, 32), 4, 32, 8, 2048, 1792, 5.0),
    "ling3": ((64, 32), 8, 512, 8, 2560, 768, 5.0),
    "laguna": ((32, 16), 8, 256, 8, 2048, 512, 5.0),
    "sdar": ((512, 128), 8, 128, 16, 2048, 768, 0.0),
}


def _forms(cell):
    tokens, top_k, num_experts, held, hidden, width, sigmas = CELLS[cell]
    return {
        olmoe.held_swiglu_form(
            olmoe.held_chunk_rows(n, top_k, held, num_experts, sigmas), hidden, width, held
        )
        for n in tokens
    }


@pytest.mark.parametrize("cell", list(CELLS))
def test_on_the_cpu_every_cells_decode_keeps_the_ragged_dots(cell):
    assert jax.default_backend() == "cpu"
    assert _forms(cell) == {"ragged_dot"}


@pytest.mark.parametrize("cell", [c for c in CELLS if c != "mellum2"])
def test_on_a_tpu_the_five_other_cells_decode_keeps_the_ragged_dots(cell, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _forms(cell) == {"ragged_dot"}


def test_on_a_tpu_mellum2s_decode_takes_the_kernel(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _forms("mellum2") == {"kernel"}


@pytest.mark.parametrize("tokens, what", [
    (7168, "a minibatch of the update (2 sequences of 3,584 positions)"),
    (16 * 3072, "the prefill of 16 prompts"),
    (3072, "the prefill of one prompt"),
    (512, "a teacher-forced pass over one response"),
])
def test_on_a_tpu_mellum2s_large_chunks_keep_the_ragged_dots(tokens, what, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, top_k, num_experts, held, hidden, width, sigmas = CELLS["mellum2"]
    rows = olmoe.held_chunk_rows(tokens, top_k, held, num_experts, sigmas)
    assert rows > 4 * olmoe._HELD_DECODE_TILE, what
    assert olmoe.held_swiglu_form(rows, hidden, width, held) == "ragged_dot", what


@pytest.mark.parametrize("rows, form", [(64, "kernel"), (128, "kernel"), (192, "ragged_dot")])
def test_on_a_tpu_the_kernel_is_taken_as_far_as_it_was_measured(rows, form, monkeypatch):
    """`[8, 2048, 896]`: measured at 64 and at 128 rows (PERF.md section 6, PR
    48, call 3), and not beyond two decode tiles."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert olmoe.held_swiglu_form(rows, 2048, 896, 8) == form


def test_the_chunks_rows_are_what_moe_held_asks_for():
    """`held_chunk_rows` is `_moe_held`'s own sizing: the rollout's and the
    evaluator's chunks as PERF.md section 6 (PR 33) gives them."""
    assert olmoe.held_chunk_rows(128, 4, 8, 32, 5.0) == 192  # LFM2's rollout
    assert olmoe.held_chunk_rows(32, 4, 8, 32, 5.0) == 64  # its evaluator
    assert olmoe.held_chunk_rows(16, 8, 8, 64, 5.0) == 64  # Mellum2's, both
    assert olmoe.held_chunk_rows(2, 8, 8, 64, 5.0) == 16  # never more rows than pairs
    assert olmoe.held_chunk_rows(7168, 8, 8, 64, 5.0) == 9216  # whole 512-row tiles


@pytest.mark.parametrize("network", ["lfm2", "sdar"])
def test_a_network_says_the_form_its_decode_takes(network, monkeypatch):
    """What `ff_lm_ppo` and `ff_sdar_ppo` record in
    `stoix_tpu_held_swiglu_form{form}`: the network's own widths and its own
    chunk's room through the same rule."""
    if network == "lfm2":
        model = lfm2.Lfm2LM(
            vocab_size=64, hidden_size=2304, layer_types=("full_attention",), num_dense_layers=0,
            dense_width=0, num_heads=32, num_kv_heads=4, head_dim=128, num_experts=64,
            experts_held=8, experts_per_token=8, expert_width=896,
        )
        tokens = 16
    else:
        model = sdar.SdarLM(
            vocab_size=64, hidden_size=2304, num_heads=32, num_kv_heads=4, head_dim=128,
            num_experts=64, experts_held=8, experts_per_token=8, expert_width=896, block_length=4,
        )
        tokens = 8  # 32 pairs to a chunk of 16 rows: without room in deviations a tile is 8 rows
    assert model.held_swiglu_form(tokens) == "ragged_dot"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert model.held_swiglu_form(tokens) == "kernel"
    assert model.held_swiglu_form(4096) == "ragged_dot"


def test_the_forward_takes_the_kernel_and_the_backward_the_ragged_dots(monkeypatch):
    """`moe(..., held=)` at a small chunk with the rule steered onto the
    kernel (interpreted): the layer's result is the `ragged_dot` form's to
    the sums' order, and its gradient — `_held_experts`' backward recomputes each
    chunk through the `ragged_dot`s — is finite and the `ragged_dot` form's."""
    hidden, width, experts, held, tokens, top_k = 384, 640, 8, 4, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(keys[0], (tokens, hidden))
    router = jax.random.normal(keys[1], (hidden, experts)) * 0.1
    gate, up = (jax.random.normal(k, (held, hidden, width)) * 0.05 for k in keys[2:4])
    down = jax.random.normal(keys[4], (held, width, hidden)) * 0.05

    def layer(x, gate):
        return olmoe.moe(x, router, gate, up, down, top_k, held=(0, held), held_room_sigmas=5.0)[0]

    loss = lambda x, gate: jnp.sum(jnp.square(layer(x, gate)))
    want, want_grads = layer(x, gate), jax.grad(loss, argnums=(0, 1))(x, gate)

    taken = []
    monkeypatch.setattr(
        olmoe, "held_swiglu_form", lambda *shape: taken.append(shape) or "kernel"
    )
    monkeypatch.setattr(
        olmoe, "held_swiglu_decode",
        lambda *operands: held_swiglu.held_swiglu_decode(*operands, interpret=True),
    )
    got, got_grads = layer(x, gate), jax.grad(loss, argnums=(0, 1))(x, gate)
    assert taken and set(taken) == {(64, hidden, width, held)}
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=0)
    assert float(jnp.max(jnp.abs(got - want))) > 0.0  # another program, not the same one
    for got_grad, want_grad in zip(got_grads, want_grads):
        np.testing.assert_allclose(
            got_grad, want_grad, atol=1e-4 * float(jnp.max(jnp.abs(want_grad))), rtol=0
        )
