"""The TPU's compiler on the kernels of the main path at their real widths,
without a chip: libtpu compiles for a v5e that is described, not attached
(the `on-chip-measurement` guide, section 2). What Mosaic refuses — a slice
off the tiling, more VMEM than a kernel may take — it refuses here, at no
chip time; nothing runs, so results and times stay the chip's to give.

The topology is described inside a fixture, never while a module is imported:
one process at a time may load the TPU's library, and every worker imports
every test file."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from stoix_tpu.networks import lfm2
from stoix_tpu.ops import pallas_attention
from stoix_tpu.utils import config as config_lib


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache and
    # cannot be read back without one: keep it out.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


# The benchmark's block-diffusion cell: a minibatch of 16 sequences of [clean
# 516 ; 2 noisy copies of 512], 32 query heads on 4 key/value heads of 128.
CELL = dict(block_length=4, clean=516, copies=2)
SEQUENCES, POSITIONS, HEADS, KV_HEADS, HEAD_DIM = 16, 1540, 32, 4, 128


def _compiled(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip) for shape in shapes]
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_mosaic_takes_the_block_mask_kernels_at_the_cells_shape(one_chip, what):
    attend = lambda q, k, v: pallas_attention.block_mask_attention(q, k, v, **CELL)
    q = (SEQUENCES, POSITIONS, HEADS, HEAD_DIM)
    kv = (SEQUENCES, POSITIONS, KV_HEADS, HEAD_DIM)
    if what == "forward":
        text = _compiled(attend, one_chip, q, kv, kv).as_text()
        assert text.count("block_mask_attention_fwd") and "block_mask_attention_bwd" not in text
    else:
        loss = lambda q, k, v, w: jnp.sum(attend(q, k, v) * w)
        compiled = _compiled(
            jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv, (SEQUENCES, POSITIONS, HEADS * HEAD_DIM)
        )
        text = compiled.as_text()
        assert "block_mask_attention_fwd" in text and "block_mask_attention_bwd" in text
        # q, the result and their cotangents pass to and from the kernels as
        # they lie: nothing of their size is copied around them but what the
        # entry's own layouts ask for.
        assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 4 * SEQUENCES * POSITIONS * HEADS * HEAD_DIM


def test_q_and_k_pass_from_the_projections_to_the_block_mask_kernels_as_they_lie(one_chip, monkeypatch):
    """The gradient of `gqa_qkv` followed by `block_mask_attention` at the
    cell's shape, steered onto the TPU's branch of `norm_rope_form`: Mosaic
    takes `qk_norm_rope` and `qk_norm_rope_bwd` (32 | 4 heads of 128, a last
    row tile of 4 rows), and between the projections and the score kernels
    nothing of q's size is written but the pair's own results — no `copy`,
    `transpose` or fusion in any spelling of q's shape, and none of the
    rotation's halves. `rms_norm` + `rope` as XLA compiles them put six such
    ops before the forward kernel and five after the backward one (PERF.md
    section 6, PR 42)."""
    from stoix_tpu.networks import sdar

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hidden = 2048
    layer = {
        "wq": (hidden, HEADS * HEAD_DIM), "wk": (hidden, KV_HEADS * HEAD_DIM),
        "wv": (hidden, KV_HEADS * HEAD_DIM), "q_norm": (HEAD_DIM,), "k_norm": (HEAD_DIM,),
    }
    struct = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(layer, x, positions, w):
        q, k, v = sdar.gqa_qkv(layer, x, positions, HEADS, KV_HEADS, HEAD_DIM, 1e6, 1e-6)
        return jnp.sum(pallas_attention.block_mask_attention(q, k, v, **CELL) * w)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(
        jax.tree.map(struct, layer, is_leaf=lambda x: isinstance(x, tuple)),
        struct((SEQUENCES, POSITIONS, hidden)), struct((SEQUENCES, POSITIONS), jnp.int32),
        struct((SEQUENCES, POSITIONS, HEADS * HEAD_DIM)),
    ).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert re.search(r"qk_norm_rope\b(?!_bwd)", text) and "qk_norm_rope_bwd" in text
    # q's and k's pass each way, and the score kernels
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 6
    of_q_size = r"16,1540,32,128|16,1540,4,8,128|16,49280,128|3080,8,32,128|16,1540,32,64"
    moved = [
        line for line in text.splitlines()
        if re.search(rf"= f32\[({of_q_size})\]\{{[^}}]*\}} (copy|transpose|fusion)\(", line)
    ]
    assert not moved, moved


# The benchmark's Ling-3 cell: a minibatch of 8 sequences of 512 tokens, 32
# heads of 128, through a delta-rule layer's recurrence.
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_mosaic_takes_the_delta_rules_kernel_pair_at_the_cells_shape(one_chip, what):
    """`delta_rule_update_kernel` at [8, 512, 32, 128]: Mosaic takes the
    forward kernel and the pair; q, k, v, g and their cotangents pass to and
    from the kernels as they lie (no `copy`, `transpose` or `reshape` of an
    array of their size), and what the gradient keeps beside its operands
    and results is the chunks' starting states, 128 MiB."""
    from stoix_tpu.ops import delta_rule

    operand, beta = (8, 512, 32, 128), (8, 512, 32)
    rule = lambda q, k, v, g, b: delta_rule.delta_rule_update_kernel(q, k, v, g, b)[0]
    if what == "forward":
        compiled = _compiled(rule, one_chip, *[operand] * 4, beta)
        text = compiled.as_text()
        assert "delta_rule_update" in text and "delta_rule_update_bwd" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2**25  # the starting state, zeros
    else:
        loss = lambda q, k, v, g, b, w: jnp.sum(rule(q, k, v, g, b) * w)
        grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
        compiled = _compiled(grad, one_chip, *[operand] * 4, beta, operand)
        text = compiled.as_text()
        assert "delta_rule_update_bwd" in text and text.count("tpu_custom_call") == 2
        states = 8 * (512 // delta_rule.UPDATE_CHUNK) * 32 * 128 * 128 * 4
        assert states <= compiled.memory_analysis().temp_size_in_bytes < states + 2**26
    moved = [
        line for line in text.splitlines()
        if re.search(r"= f32\[8,512,(32,128|4096)\]\{[^}]*\} (copy|transpose|reshape)\(", line)
    ]
    assert not moved, moved


# The benchmark's LFM2 cell: 128 sequences decode 512 tokens through the
# published widths' first six layers (configs/network/lfm2_moe.yaml), one of
# them attention over a cache [512, 128, 8, 64]: head size 64 is half a lane
# row, so the cache's reads want the 128-wide batch axis minor.
LFM2_SEQUENCES, LFM2_TOKENS, LFM2_VOCAB = 128, 512, 16384


@pytest.mark.parametrize("together", [True, False], ids=["one_position", "a_position_a_sequence"])
def test_the_lfm2_decode_copies_no_cache_where_the_sequences_move_together(one_chip, together):
    """The decode scan of `Lfm2LM.step` at the cell's shape. With a position a
    sequence the row is scattered, the scatter keeps the cache as it is
    carried, and every branch of `_attend_cache` begins with a copy of the
    whole cache, keys and values, into the layout its reads want: 1.07 GB a
    step beside a decode that reads 0.27 GB at most (PERF.md section 6, PR
    35). With one position for all, one slab is written in place into a cache
    that lies as it is read, and nothing of the cache's size is copied."""
    network = config_lib.compose(config_lib.default_config_dir(), "network/lfm2_moe.yaml", [])
    actor = config_lib.instantiate(network.actor_network, vocab_size=LFM2_VOCAB)
    struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(struct, jax.eval_shape(
        lambda: actor.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), method="forward")
    ))
    tokens = jax.ShapeDtypeStruct((LFM2_TOKENS, LFM2_SEQUENCES), jnp.int32, sharding=one_chip)

    def decode(params, tokens):
        def one(carry, token):
            logits, _, carry, _ = actor.apply(params, carry, token, method="step")
            return carry, jnp.argmax(logits, axis=-1)

        carry = actor.init_carry(LFM2_SEQUENCES, LFM2_TOKENS, together=together)
        return jax.lax.scan(one, carry, tokens)[1]

    lowered = jax.jit(decode).trace(params, tokens).lower(lowering_platforms=("tpu",))
    text = lowered.compile().as_text()
    cache = rf"f32\[{LFM2_TOKENS},{LFM2_SEQUENCES},{actor.num_kv_heads},{actor.head_dim}\]"
    assert re.search(cache, text)  # the cache is in the program under this name
    copies = re.findall(rf"= {cache}\{{[^}}]*\}} copy\(", text)
    assert (not copies) if together else copies


# The benchmark's Kanana-2 cell: 128 sequences decode 512 tokens through five
# latent-attention layers at the published widths
# (configs/network/kanana2_moe.yaml), each against latent rows [128, 512, 576].


def test_the_latent_decode_copies_no_cache_and_goes_through_the_kernel(one_chip, monkeypatch):
    """The decode scan of the stack's `step` at the cell's shape, steered onto
    the TPU's branches (code that asks `jax.default_backend()` sees the CPU
    here): the new row is one slab written in place into rows that lie as the
    Pallas kernel reads them, sequence-major, and nothing of a cache's size is
    copied — XLA's own plan for the two batched products wrote a bfloat16
    copy of the cache in another layout every step (PERF.md section 6, PR 38)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    network = config_lib.compose(config_lib.default_config_dir(), "network/kanana2_moe.yaml", [])
    actor = config_lib.instantiate(network.actor_network, vocab_size=16032)
    struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(struct, jax.eval_shape(
        lambda: actor.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), method="forward")
    ))
    tokens = jax.ShapeDtypeStruct((512, 128), jnp.int32, sharding=one_chip)

    def decode(params, tokens):
        def one(carry, token):
            logits, _, carry, _ = actor.apply(params, carry, token, method="step")
            return carry, jnp.argmax(logits, axis=-1)

        return jax.lax.scan(one, actor.init_carry(128, 512, together=True), tokens)[1]

    lowered = jax.jit(decode).trace(params, tokens).lower(lowering_platforms=("tpu",))
    text = lowered.compile().as_text()
    cache = r"f32\[128,512,576\]"
    assert re.search(cache, text) and "latent_decode_attention" in text
    assert not re.findall(rf"= {cache}\{{[^}}]*\}} copy\(", text)
    assert set(re.findall(rf"{cache}\{{(\d,\d,\d)", text)) == {"2,1,0"}  # sequence-major, rows minor
    assert not re.search(r"bf16\[128,\d+,576\]", text)  # no lower-precision copy of the rows


# The three token cells' update attention: a minibatch of 16 sequences of 512
# tokens through `flash_attention` — 32 heads of 192 | 128 (Kanana-2's latent
# attention), 16 of 128 (OLMoE), 32 of 64 after the groups' repeat (LFM2) —
# and chip_smoke.py's shapes: the long bfloat16 one and ff_trans_ppo's window.
FLASH_SHAPES = {
    "kanana2": ((16, 512, 32, 192), (16, 512, 32, 128), jnp.float32),
    "olmoe": ((16, 512, 16, 128), (16, 512, 16, 128), jnp.float32),
    "lfm2": ((16, 512, 32, 64), (16, 512, 32, 64), jnp.float32),
    "long_bfloat16": ((4, 4096, 8, 64), (4, 4096, 8, 64), jnp.bfloat16),
    "padded_bfloat16": ((4, 4000, 8, 64), (4, 4000, 8, 64), jnp.bfloat16),
    "trans_ppo": ((64, 16, 4, 32), (64, 16, 4, 32), jnp.float32),
}


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("cell", list(FLASH_SHAPES))
def test_mosaic_takes_the_flash_kernels_at_the_cells_shapes(one_chip, cell, what):
    """The forward kernel under the name the benchmark finds it by, the
    backward kernel beside it in the gradient, and nothing of [queries, keys]
    size in either program."""
    qk, values, dtype = FLASH_SHAPES[cell]
    attend = lambda q, k, v: pallas_attention.flash_attention(q, k, v, causal=True)
    struct = lambda shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if what == "forward":
        fn, args = attend, (qk, qk, values)
    else:
        loss = lambda q, k, v, w: jnp.sum((attend(q, k, v) * w).astype(jnp.float32))
        fn, args = jax.grad(loss, argnums=(0, 1, 2)), (qk, qk, values, values)
    text = jax.jit(fn).trace(*map(struct, args)).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert re.search(r"flash_attention\b(?!_bwd)", text)
    assert ("flash_attention_bwd" in text) == (what == "gradient")
    batch, length, heads, _ = qk
    assert not re.search(rf"\[{batch},{heads},{length},{length}\]", text)


def _mixer_gradient(cell, one_chip):
    """The compiled gradient (parameters and input) of one attention layer at
    the cell's published widths — projections, norms, rotation, the kernels,
    the output projection — on 16 sequences of 512 tokens."""
    from stoix_tpu.networks import lfm2, mla

    if cell == "olmoe":
        network = config_lib.compose(config_lib.default_config_dir(), "network/olmoe.yaml", [])
        module = config_lib.instantiate(network.actor_network, vocab_size=50304)
        inputs = jax.ShapeDtypeStruct((16, 512), jnp.int32, sharding=one_chip)
        example, out = jnp.zeros((1, 2), jnp.int32), lambda result: result[0]
    else:
        name = {"kanana2": "kanana2_moe", "lfm2": "lfm2_moe"}[cell]
        c = config_lib.compose(config_lib.default_config_dir(), f"network/{name}.yaml", []).actor_network
        module = (
            mla.LatentAttention(
                c.hidden_size, c.num_heads, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
                c.v_head_dim, c.rope_theta, c.rms_eps,
            ) if cell == "kanana2" else lfm2.GroupedQueryAttention(
                c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim, c.rope_theta, c.rms_eps
            )
        )
        inputs = jax.ShapeDtypeStruct((16, 512, c.hidden_size), jnp.float32, sharding=one_chip)
        example, out = jnp.zeros((1, 2, c.hidden_size)), lambda result: result
    struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(struct, jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), example, method="forward")
    ))
    loss = lambda params, x: jnp.sum(out(module.apply(params, x, method="forward")) ** 2)
    argnums = 0 if cell == "olmoe" else (0, 1)
    lowered = jax.jit(jax.grad(loss, argnums=argnums)).trace(params, inputs).lower(
        lowering_platforms=("tpu",)
    )
    return lowered.compile().as_text()


@pytest.mark.parametrize("cell", ["kanana2", "lfm2", "olmoe"])
def test_the_update_attention_reads_its_operands_where_they_lie(one_chip, monkeypatch, cell):
    """A layer's gradient at the cell's widths, steered onto the TPU's branch
    of `best_attention`: both kernels are in it, no [16, heads, 512, 512]
    scores, and q, k, v, the result and their cotangents pass between the
    projections and the kernels as XLA lays them — positions minor — so no
    `copy` or `transpose` of an array of their size stands between. In the
    OLMoE block alone XLA keeps q and k features-minor for the norm over the
    whole projection that precedes the rotation, and copies those two, and dq
    and dk back: four copies of [16, 512, 2048] where the parent's
    `_fold_heads` made five and the plain backward wrote the scores."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _mixer_gradient(cell, one_chip)
    assert "flash_attention_bwd" in text and text.count("tpu_custom_call") >= 2
    heads = {"kanana2": 32, "lfm2": 32, "olmoe": 16}[cell]
    assert not re.search(rf"f32\[16,{heads},512,512\]", text)
    copies = [
        line for line in text.splitlines()
        if re.search(r"= f32\[16,(512,\d+(,\d+)?|\d+,\d+,512)\]\{[^}]*\} copy\(", line)
    ]
    assert len(copies) == (4 if cell == "olmoe" else 0)


# The benchmark's window-and-full cell (Laguna-XS.2): three window layers of
# 64 query heads against a ring of 512 rows and two full layers of 48 against a
# growing cache of 1,024, 8 key/value heads of 128, 32 sequences in the rollout
# and 16 in the evaluator.
GQA_DECODE_SHAPES = [(512, 32, 8), (512, 16, 8), (1024, 32, 6), (1024, 16, 6)]


@pytest.mark.parametrize("rows,sequences,group", GQA_DECODE_SHAPES)
def test_mosaic_takes_the_decode_kernel_at_the_cells_shapes(one_chip, monkeypatch, rows, sequences, group):
    """`attend_rows` steered onto the TPU's branch: one `gqa_decode_attention`
    call under the name the benchmark finds it by, the caches read where they
    lie (a sequence's block is whole tiles, so nothing of a cache's size is
    copied or laid out anew around the kernel)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    attend = lambda q, k, v: lfm2.attend_rows(q, k, v, jnp.int32(rows - 1))
    cache = (rows, sequences, 8, 128)
    text = _compiled(attend, one_chip, (sequences, 8, group, 128), cache, cache).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and "gqa_decode_attention" in calls[0]
    assert not re.findall(rf"= [a-z0-9]+\[{rows},{sequences},8,128\]\{{[^}}]*\}} (copy|transpose)\(", text)
    assert not re.search(rf"bf16\[{rows},{sequences},8,128\]", text)  # no lower-precision copy of the rows


# The benchmark's prefilled-prompt cell (Mellum2): 16 sequences decode through
# four routed layers whose 8 held experts of 64 are [2304, 896] (a chunk of 64
# rows); the Kanana-2 cell's 128 sequences through 16 of 128 at [2048, 768].
def _held_layer(one_chip, tokens, experts, held, hidden, width, top_k, what):
    from stoix_tpu.networks import olmoe

    def layer(x, router, gate, up, down):
        return olmoe.moe(
            x, router, gate, up, down, top_k, held=(0, held), renormalise=True, held_room_sigmas=5.0
        )[0]

    weights = ((held, hidden, width), (held, hidden, width), (held, width, hidden))
    fn = layer if what == "forward" else jax.grad(
        lambda *operands: jnp.sum(layer(*operands) ** 2), argnums=(0, 2, 3, 4)
    )
    return _compiled(fn, one_chip, (tokens, hidden), (hidden, experts), *weights).as_text()


def test_mellum2s_decode_step_streams_its_held_experts_through_the_kernel(one_chip, monkeypatch):
    """One decode step of `moe(..., held=(0, 8))` at `[8, 2304, 896]`, 16
    tokens, steered onto the TPU's branch of `held_swiglu_form`: the chunk's
    SwiGLU is ONE `held_swiglu_decode` call under the name the breakdown finds
    it by, no grouped matmul is left, and no bfloat16 copy of a weight is
    written (the rounding is in the kernel's registers)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _held_layer(one_chip, 16, 64, 8, 2304, 896, 8, "forward")
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and "held_swiglu_decode" in calls[0]
    assert "ragged-dot" not in text and "ragged_dot" not in text
    assert not re.search(r"bf16\[8,(2304,896|896,2304)\]", text)


def test_mellum2s_update_keeps_its_grouped_matmuls(one_chip, monkeypatch):
    """The gradient of the same layer over a minibatch of the update (7,168
    positions: chunks of 9,216 rows, whole 512-row tiles): `ragged-dot`s
    forward and backward, and no kernel — it has no VJP and needs none."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _held_layer(one_chip, 7168, 64, 8, 2304, 896, 8, "gradient")
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    # XLA's grouped matmul is itself a Mosaic call, `ragged-dot-none`: nine
    # of them (three forward, six backward) and the table of their groups.
    assert len(calls) >= 9 and all("ragged-dot" in line for line in calls)
    assert "held_swiglu_decode" not in text


def test_kanana2s_decode_step_is_the_program_it_was(one_chip, monkeypatch):
    """A decode step at Kanana-2's `[16, 2048, 768]`, 128 tokens: the text the
    TPU's compiler gives under the rule is the text it gives with the rule
    taken out (every chunk on the `ragged_dot`s), to the letter."""
    from stoix_tpu.networks import olmoe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # An instruction as the compiler wrote it, less the place in this file it was traced from.
    program = lambda text: [
        re.sub(r", metadata=\{[^}]*\}", "", line) for line in text.splitlines() if " = " in line
    ]
    under_the_rule = program(_held_layer(one_chip, 128, 128, 16, 2048, 768, 6, "forward"))
    monkeypatch.setattr(olmoe, "held_swiglu_form", lambda *shape: "ragged_dot")
    without = program(_held_layer(one_chip, 128, 128, 16, 2048, 768, 6, "forward"))
    assert under_the_rule == without and len(without) > 100
    without = "\n".join(without)
    assert "held_swiglu_decode" not in without and re.search(r"ragged[-_]dot", without)
