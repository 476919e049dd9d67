#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that stoix_tpu still starts on the chip.

    python3 chip_smoke.py            # on a machine with 1 or 4 TPU chips

ONE process drives the training main path once through the entry points a
user calls — `config_lib.compose(default_config_dir(), <default yaml>,
overrides)` -> the system's `run_experiment`, which is what each `main()` does
with argv — at the full width of the policies the repo benchmarks, with depth
cut to a few updates and weights made from the config's seed. Legs:

  kernels        every Pallas kernel compiled by Mosaic (interpret=False) and
                 compared with its plain-JAX reference at a stated tolerance,
                 forward and gradient; ring attention over all the chips; the
                 delta rule's update pair against the recurrence; q's and k's
                 norm-and-rotate pair against `rms_norm` + `rope`.
  trans_ppo      Anakin transformer PPO (identity_game): the flash-attention
                 kernel in the learner's forward pass, gradient steps taken.
  lm_ppo         Anakin PPO with the OLMoE token policy at a tiny preset
                 (token_task): grouped matmuls over sorted experts, the KV
                 cache in rollout and evaluator, flash attention in the update.
  lfm2_ppo       the same entry point with `network=lfm2_moe` at a tiny preset:
                 conv tails and a KV cache of head size 64 in one decode
                 carry, the row written at the one position of sequences that
                 move together, two 128-position prefixes to switch between.
  kanana2_ppo    the same entry point with `network=kanana2_moe` at a tiny preset
                 with the published head sizes (192 | 128): the absorbed
                 decode through the latent rows' Pallas kernel, a shared
                 expert beside the held ones, and the flash kernel pair,
                 forward and backward, in the update.
  ling3_ppo      the same entry point with `network=ling3_flash_moe` at a tiny
                 preset with the published head size (128): five delta-rule
                 layers — the matrix state rewritten a token through the
                 Pallas kernel `delta_rule_step` in rollout and evaluator,
                 the recurrence's kernel pair (`delta_rule_update`, state in
                 VMEM across chunks of 64) forward and backward in the update — to
                 one gated latent-attention layer, a group-limited router.
  sdar_ppo       Anakin PPO with the SDAR block-diffusion token policy at a
                 tiny preset (block_token_task): the held-experts loop of
                 grouped matmuls, block steps through the GQA cache in rollout
                 and evaluator, the [clean ; noisy copies] update with its
                 attention in the block-mask kernels (forward and backward)
                 behind q's and k's norm-and-rotate pair (the gauge
                 `stoix_tpu_qk_norm_rope{form=kernel}` = 1), then
                 `trunk_copies` against the plain masked products.
  ppo_pallas_gae Anakin ff_ppo with system.multistep_impl=pallas: the
                 recurrence kernel inside the learner.
  sebulba        Sebulba ff_ppo on the native C++ CartPole pool, 512 envs,
                 rollout 64. One chip: actor, learner, evaluator share device
                 0. Four chips: actors [0,1], learners [2,3], evaluator 0.
  anakin_ant     Anakin PPO on Ant, 2,048 envs per chip, rollout 16, default
                 256x256 torsos, two eval windows (the pipelined loop's second
                 dispatch reuses donated buffers). Ant is continuous-action,
                 so the entry point is ff_ppo_continuous — ff_ppo's learner
                 with the continuous head, the shape bench.py times. On
                 several chips also: arch.integrity.enabled (replicas that saw
                 different envs must still agree after the gradient pmean)
                 and a device-memory sample on every chip.
  anakin_ant_large  the same with the widest policy the repo benchmarks,
                 1024x1024 bfloat16 torsos (bench.py --large).

Every leg checks what came out by the repo's own means: finite return and
losses, a steady-state window and skipped_updates == 0 in LAST_RUN_STATS,
every learner-state leaf on TPU devices spanning all of them, zero Sebulba
evaluator errors / actor crashes, and a `pallas_call` in the traced program
wherever a kernel is claimed (read from the jaxpr, never inferred from the
backend's name). Any failed check or raised leg ends the run non-zero.

No fallback: this script sets no JAX_PLATFORMS and has no CPU mode. Unless
`jax.devices()[0].platform == "tpu"` it exits non-zero and prints no result.
It prints times only as set-up facts (compile seconds, persistent-cache hits)
and no number under a benchmark metric's name. The compile cache goes where
`JAX_COMPILATION_CACHE_DIR` says, else to `<checkout>/xla_cache`
(stoix_tpu/utils/compilecache.py); the directory and hit/miss counts are
printed, so a second run in the same place shows the warm start.

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import json
import math
import sys
import time
import traceback
from typing import Any, Callable, Dict, Iterator, List, Tuple

ENVS_PER_CHIP = 2048  # Anakin width per chip (bench.py's tracked shape)
ANAKIN_ROLLOUT = 16
SEBULBA_ENVS = 512
SEBULBA_ROLLOUT = 64
RING_SHARD_LEN = 256  # per-chip sequence shard: 2 x the 128-row kernel block
FLASH_LONG = (4, 4096, 8, 64)  # [B, S, H, D] bfloat16
FLASH_PADDED_LEN = 4000  # not a multiple of the 128-row block
FLASH_TRANS_PPO = (64, 16, 4, 32)  # ff_trans_ppo's window: S=16 padded to 128
FLASH_KANANA2 = (16, 512, 32, 192)  # the latent-attention cell's minibatch in float32 ...
FLASH_KANANA2_VALUES = 128  # ... whose values are narrower than its queries and keys

# Stated tolerances: max abs error on unit-normal inputs. The recurrence is
# bitwise. Attention outputs are compared with float32 full_attention at
# HIGHEST matmul precision; the kernels accumulate in float32 but the MXU
# multiplies in bfloat16 passes at default precision (~2^-8 relative per
# product), which — not float32 rounding — sets the bound. The flash
# kernels' gradients are compared with jax.grad of the same HIGHEST
# reference, ring attention's with jax.grad(full_attention) at the default
# precision.
TOL_RECURRENCE = 0.0
TOL_ATTN = 3e-2
TOL_GRAD = 5e-2
# float32 elementwise work on both sides: sums added up in another order
TOL_ELEMENTWISE = 1e-5


class CheckFailed(AssertionError):
    """A leg ran but what came out is wrong."""


def _require(condition: bool, message: str) -> None:
    if not condition:
        raise CheckFailed(message)


def _log(message: str) -> None:
    print(f"[chip_smoke] {message}", flush=True)


# ---------------------------------------------------------------------------
# Observation seams: read what the run did without changing what it does
# ---------------------------------------------------------------------------


def _placement(state: Any) -> Dict[str, Any]:
    """Where a learner-state pytree lives: platforms, the union and the
    per-leaf span of device ids, and which top-level fields are sharded."""
    import jax

    platforms, union, narrowest = set(), set(), None
    sharded_fields = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        ids = {d.id for d in leaf.sharding.device_set}
        platforms |= {d.platform for d in leaf.sharding.device_set}
        union |= ids
        narrowest = len(ids) if narrowest is None else min(narrowest, len(ids))
        if not leaf.sharding.is_fully_replicated:
            sharded_fields.add(str(getattr(path[0], "name", path[0])))
    return {
        "platforms": sorted(platforms),
        "device_ids": sorted(union),
        "narrowest_leaf_span": narrowest,
        "sharded_fields": sorted(sharded_fields),
    }


def _require_on_all_chips(placement: Dict[str, Any], n: int) -> None:
    _require(placement["platforms"] == ["tpu"], f"state not on TPU: {placement}")
    _require(
        len(placement["device_ids"]) == n and placement["narrowest_leaf_span"] == n,
        f"learner state does not span all {n} chip(s): {placement}",
    )


@contextlib.contextmanager
def _observe_learner_setup(
    module: Any, observed: Dict[str, Any], count_kernels: bool
) -> Iterator[None]:
    """Wrap the system module's `learner_setup` (looked up by its
    `run_experiment` at call time): the setup is returned untouched; its
    initial state's placement — and, where a kernel is claimed, the number of
    `pallas_call`s in the jaxpr of the very `learn` the runner compiles — are
    recorded. The jitted+shard_mapped learner's outputs keep the input specs,
    so the placement holds for the whole run."""
    original = module.learner_setup

    def observing(env, config, mesh, key):
        result = original(env, config, mesh, key)
        setup = result if hasattr(result, "learn") else result[0]
        observed["placement"] = _placement(setup.learner_state)
        if count_kernels:
            # (the jit's own trace: `make_jaxpr` would nest a jit that names compiler options)
            jaxpr = str(setup.learn.trace(setup.learner_state).jaxpr)
            observed["pallas_calls"] = jaxpr.count("pallas_call")
        return result

    module.learner_setup = observing
    try:
        yield
    finally:
        module.learner_setup = original


@contextlib.contextmanager
def _tee_train_metrics(observed: Dict[str, Any]) -> Iterator[None]:
    """Copy every TRAIN log event (the losses) on its way to the sinks."""
    import numpy as np

    from stoix_tpu.utils.logger import LogEvent, StoixLogger

    original = StoixLogger.log
    records: List[Dict[str, float]] = observed.setdefault("train", [])

    def log(self, metrics, t, t_eval, event):
        if event == LogEvent.TRAIN:
            records.append(
                {k: float(np.mean(np.asarray(v))) for k, v in metrics.items()}
            )
        return original(self, metrics, t, t_eval, event)

    StoixLogger.log = log
    try:
        yield
    finally:
        StoixLogger.log = original


def _require_finite_losses(observed: Dict[str, Any]) -> List[str]:
    records = observed.get("train") or []
    _require(bool(records), "no TRAIN metrics were logged")
    for record in records:
        bad = {k: v for k, v in record.items() if not math.isfinite(v)}
        _require(not bad, f"non-finite training metrics: {bad}")
    return sorted(records[-1])


def _counter_total(name: str) -> float:
    from stoix_tpu.observability import get_registry

    return sum(v for _, v in get_registry().counter(name).labels_and_values())


# ---------------------------------------------------------------------------
# System legs
# ---------------------------------------------------------------------------


def _anakin_leg(
    n: int,
    module_name: str,
    default_yaml: str,
    overrides: List[str],
    expect_kernel: bool = False,
    multichip_extras: bool = False,
) -> Dict[str, Any]:
    from stoix_tpu.observability import get_registry
    from stoix_tpu.systems import runner
    from stoix_tpu.utils import config as config_lib

    multichip_extras = multichip_extras and n > 1
    overrides = list(overrides) + [
        "arch.total_timesteps=~",
        "arch.num_evaluation=2",
        "arch.num_eval_episodes=8",
        "arch.absolute_metric=False",
        "logger.use_console=False",
    ]
    if multichip_extras:
        overrides += [
            "arch.integrity.enabled=True",
            # The device poller (observability/introspect.py) samples every
            # chip's allocator while the run holds its state.
            "logger.telemetry.enabled=True",
            "logger.telemetry.device_poll_interval_s=0.2",
        ]
    module = importlib.import_module(module_name)
    config = config_lib.compose(config_lib.default_config_dir(), default_yaml, overrides)
    observed: Dict[str, Any] = {}
    with _observe_learner_setup(module, observed, expect_kernel), _tee_train_metrics(observed):
        final_return = module.run_experiment(config)

    stats = runner.LAST_RUN_STATS
    _require(math.isfinite(final_return), f"final return not finite: {final_return}")
    loss_names = _require_finite_losses(observed)
    _require(stats["steady_state_sps"] > 0.0, "no steady-state window recorded")
    _require(stats["pipelined"], "the pipelined loop did not run")
    _require(
        stats["resilience"]["skipped_updates"] == 0,
        f"skipped updates: {stats['resilience']}",
    )
    _require_on_all_chips(observed["placement"], n)
    facts: Dict[str, Any] = {
        "final_return": round(final_return, 3),
        "losses": loss_names,
        "learner_compile_seconds": stats["compile"]["compile_s"],
        "learner_cache_hits": stats["compile"]["cache_hits"],
        "placement": observed["placement"],
    }
    if expect_kernel:
        _require(
            observed.get("pallas_calls", 0) > 0,
            "no pallas_call in the learner the runner compiled",
        )
        facts["pallas_calls_in_learner"] = observed["pallas_calls"]
    if n > 1:
        _require(
            "env_state" in observed["placement"]["sharded_fields"]
            and "params" not in observed["placement"]["sharded_fields"],
            f"expected env_state sharded and params replicated: {observed['placement']}",
        )
    if multichip_extras:
        integrity = stats["integrity"]
        # A replica mismatch raises StateCorruptionError out of the run; a
        # positive count therefore means "checked and agreed".
        _require(
            integrity["enabled"] and integrity["fingerprint_checks"] > 0,
            f"integrity sentinel did not check: {integrity}",
        )
        in_use = {}
        for label_key, value in (
            get_registry().gauge("stoix_tpu_device_memory_bytes").labels_and_values()
        ):
            labels = dict(label_key)
            if labels.get("kind") == "bytes_in_use" and labels.get("source") == "memory_stats":
                in_use[labels["device"]] = value
        _require(
            len(in_use) == n and all(v > 0 for v in in_use.values()),
            f"not every chip reported bytes_in_use > 0: {in_use}",
        )
        facts["fingerprint_checks"] = integrity["fingerprint_checks"]
        facts["chips_with_bytes_in_use"] = len(in_use)
    return facts


def leg_trans_ppo(n: int) -> Dict[str, Any]:
    return _anakin_leg(
        n,
        "stoix_tpu.systems.ppo.anakin.ff_trans_ppo",
        "default/anakin/default_ff_trans_ppo.yaml",
        ["env=identity_game", f"arch.total_num_envs={64 * n}", "arch.num_updates=4"],
        expect_kernel=True,
    )


def leg_lm_ppo(n: int) -> Dict[str, Any]:
    """The token policy at the tiny preset, data-parallel over the chips: the
    grouped-matmul kernels of `jax.lax.ragged_dot` in decode and update, the
    KV cache through rollout and evaluator, and (T = 128, the flash kernel's
    block) the Pallas attention in the teacher-forced pass."""
    tiny = [
        "hidden_size=128", "num_heads=4", "head_dim=32", "num_experts=8",
        "experts_per_token=2", "expert_width=64",
    ]
    return _anakin_leg(
        n,
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo",
        "default/anakin/default_ff_lm_ppo.yaml",
        [f"network.actor_network.{o}" for o in tiny] + [
            "env.kwargs.vocab_size=512", "env.kwargs.length=128", "system.rollout_length=128",
            f"arch.total_num_envs={16 * n}", "system.num_minibatches=4", "arch.num_updates=4",
            "arch.evaluation_greedy=True",
        ],
        expect_kernel=True,
    )


def _forms(gauge: str) -> Dict[str, float]:
    """A gauge labelled by `form`, as the run's traced programs left it."""
    from stoix_tpu.observability import get_registry

    return {
        dict(labels)["form"]: value
        for labels, value in get_registry().gauge(gauge).labels_and_values()
    }


def leg_lfm2_ppo(n: int) -> Dict[str, Any]:
    """The hybrid token policy through the same `ff_lm_ppo`, data-parallel
    over the chips: gated short convolutions beside grouped-query attention
    in one decode carry, the held experts' chunk loop, the flash kernel at
    the published head size of 64 (half a lane row: the size at which a
    scattered cache write made every decode step copy the cache, PERF.md
    section 6, PR 35). T = 256, so the cached attention switches between two
    prefixes; the run has to have taken the one-slab write."""
    tiny = [
        "hidden_size=128", "dense_width=256", "num_heads=4", "num_kv_heads=2", "head_dim=64",
        "expert_width=64",
    ]
    facts = _anakin_leg(
        n,
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo",
        "default/anakin/default_ff_lm_ppo.yaml",
        ["network=lfm2_moe"] + [f"network.actor_network.{o}" for o in tiny] + [
            "env.kwargs.vocab_size=512", "env.kwargs.length=256", "system.rollout_length=256",
            f"arch.total_num_envs={16 * n}", "system.num_minibatches=4", "arch.num_updates=4",
            "system.router_aux_loss_coef=0.0", "arch.evaluation_greedy=True",
        ],
        expect_kernel=True,
    )
    write = _forms("stoix_tpu_lm_cache_write")
    _require(write == {"slice": 1.0, "scatter": 0.0}, f"the cache write the run took: {write}")
    facts["cache_write"] = write
    return facts


def leg_kanana2_ppo(n: int) -> Dict[str, Any]:
    """The latent-attention token policy through the same `ff_lm_ppo`,
    data-parallel over the chips, at the published head sizes (queries and
    keys 128 + 64 rotated, values 128): the rollout and the evaluator decode
    absorbed, against one 192-wide latent row a position through
    `latent_decode_attention`; the update expands keys and values a head and
    takes the flash kernel pair, whose backward rule says which form it was
    traced in."""
    tiny = [
        "hidden_size=128", "dense_width=256", "num_heads=4", "num_kv_heads=4", "kv_lora_rank=128",
        "qk_nope_head_dim=128", "qk_rope_head_dim=64", "v_head_dim=128", "num_experts=32",
        "experts_held=4", "experts_per_token=3", "expert_width=64",
    ]
    facts = _anakin_leg(
        n,
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo",
        "default/anakin/default_ff_lm_ppo.yaml",
        ["network=kanana2_moe"] + [f"network.actor_network.{o}" for o in tiny] + [
            "env.kwargs.vocab_size=512", "env.kwargs.length=128", "system.rollout_length=128",
            f"arch.total_num_envs={16 * n}", "system.num_minibatches=4", "arch.num_updates=4",
            "system.router_aux_loss_coef=0.0", "arch.evaluation_greedy=True",
        ],
        expect_kernel=True,
    )
    for gauge, want in (
        ("stoix_tpu_mla_decode", {"absorbed": 1.0, "expanded": 0.0}),
        ("stoix_tpu_attention_backward", {"pallas": 1.0, "plain": 0.0}),
    ):
        facts[gauge] = _forms(gauge)
        _require(facts[gauge] == want, f"{gauge}: the run took {facts[gauge]}")
    return facts


def leg_ling3_ppo(n: int) -> Dict[str, Any]:
    """The delta-rule hybrid token policy through the same `ff_lm_ppo`,
    data-parallel over the chips, at the published head size (8 heads of 128:
    a state of whole tiles, one grid step of the decode kernel a sequence):
    rollout and evaluator rewrite five matrix states a token through
    `delta_rule_step_kernel` and decode the sixth layer absorbed; the update
    runs the delta rule's Pallas kernel pair (`delta_rule_update_kernel`: two
    chunks of 64 a sequence, the state in VMEM across them, its own backward
    pass) inside the rematerialised mixer and the flash kernel pair in the
    latent layer; the router
    chooses inside the 2 best of 4 groups."""
    tiny = [
        "hidden_size=128", "dense_width=256", "num_heads=8", "num_kv_heads=8", "head_dim=128",
        "kv_lora_rank=128", "qk_nope_head_dim=128", "qk_rope_head_dim=64", "v_head_dim=128",
        "num_experts=32", "experts_held=4", "experts_per_token=3", "expert_width=64", "n_group=4",
        "topk_group=2",
    ]
    facts = _anakin_leg(
        n,
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo",
        "default/anakin/default_ff_lm_ppo.yaml",
        ["network=ling3_flash_moe"] + [f"network.actor_network.{o}" for o in tiny] + [
            "env.kwargs.vocab_size=512", "env.kwargs.length=128", "system.rollout_length=128",
            f"arch.total_num_envs={16 * n}", "system.num_minibatches=4", "arch.num_updates=4",
            "system.router_aux_loss_coef=0.0", "arch.evaluation_greedy=True",
        ],
        expect_kernel=True,
    )
    for gauge, want in (
        ("stoix_tpu_delta_rule_update", {"kernel": 1.0, "chunked": 0.0, "scan": 0.0}),
        ("stoix_tpu_mla_decode", {"absorbed": 1.0, "expanded": 0.0}),
    ):
        facts[gauge] = _forms(gauge)
        _require(facts[gauge] == want, f"{gauge}: the run took {facts[gauge]}")
    from stoix_tpu.observability import get_registry

    carry = {
        dict(labels)["kind"]: value
        for labels, value in get_registry().gauge("stoix_tpu_lm_carry_bytes").labels_and_values()
    }
    _require(set(carry) == {"delta_state", "latent"}, f"the carry's kinds: {carry}")
    facts["carry_bytes"] = carry
    return facts


def leg_sdar_ppo(n: int) -> Dict[str, Any]:
    """The block-diffusion token policy at a tiny preset, data-parallel over
    the chips: `jax.lax.ragged_dot` over the held experts inside the chunk
    loop (forward and its hand-written backward), denoise and commit passes
    through the grouped-query cache, the teacher-forced pass under the block
    mask with its layers rematerialised — its attention through the Pallas
    kernels, forward and backward (heads of 128: whole lanes), which the
    learner the runner compiled has to hold, q and k handed to them by the
    norm-and-rotate kernel pair; then `trunk_copies` and its gradient as the
    chip runs them against the plain masked products behind `rms_norm` +
    `rope`."""
    tiny = [
        "hidden_size=128", "num_heads=4", "num_kv_heads=2", "head_dim=128", "num_experts=16",
        "experts_held=4", "experts_per_token=4", "expert_width=64", "num_layers=2",
    ]
    facts = _anakin_leg(
        n,
        "stoix_tpu.systems.ppo.anakin.ff_sdar_ppo",
        "default/anakin/default_ff_sdar_ppo.yaml",
        [f"network.actor_network.{o}" for o in tiny] + [
            "env.kwargs.vocab_size=512", "env.kwargs.length=128", "system.rollout_length=64",
            f"arch.total_num_envs={16 * n}", "system.num_minibatches=4", "arch.num_updates=4",
            "arch.evaluation_greedy=True",
        ],
        expect_kernel=True,
    )
    facts["stoix_tpu_qk_norm_rope"] = _forms("stoix_tpu_qk_norm_rope")
    _require(
        facts["stoix_tpu_qk_norm_rope"] == {"kernel": 1.0, "plain": 0.0},
        f"stoix_tpu_qk_norm_rope: the run took {facts['stoix_tpu_qk_norm_rope']}",
    )
    facts["trunk_copies_kernel_vs_plain_rms"] = _sdar_trunk_copies_against_plain()
    return facts


def _sdar_trunk_copies_against_plain() -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    from stoix_tpu.networks import sdar
    from stoix_tpu.systems.runner import LAST_RUN_STATS

    attention = LAST_RUN_STATS.get("update_attention", {})
    _require(attention.get("kernel") == 1, f"the run's update took the plain attention: {attention}")
    model = sdar.SdarLM(
        vocab_size=512, hidden_size=128, num_heads=4, num_kv_heads=2, head_dim=128,
        num_experts=16, experts_held=4, experts_per_token=4, expert_width=64, block_length=4,
    )
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    # (weights scaled up so that attention and the norms matter to the result)
    params = jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, model.init(keys[0]))
    clean = jax.random.randint(keys[1], (4, 4 + 256), 0, 511)
    noisy = jax.random.randint(keys[2], (4, 2, 256), 0, 512)

    def hidden_and_gradient():
        def loss(params):
            hidden, _ = model.trunk_copies(params, clean, noisy)
            return jnp.sum(jnp.sin(hidden)), hidden

        (_, hidden), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        layer = grads["params"]["layer_0"]
        return {"hidden": hidden, **{f"d_{name}": layer[name] for name in ("wq", "wk", "wv")}}

    _require(
        _has_pallas_call(model.trunk_copies, params, clean, noisy),
        "sdar trunk_copies: no pallas_call traced",
    )
    got = hidden_and_gradient()
    chosen, form = sdar.SdarLM.copies_attention, sdar.norm_rope_form
    sdar.SdarLM.copies_attention = lambda self, *a: {**chosen(self, *a), "kernel": 0}
    sdar.norm_rope_form = lambda *a: "plain"
    try:
        _require(
            not _has_pallas_call(model.trunk_copies, params, clean, noisy),
            "sdar trunk_copies: the plain path holds a pallas_call",
        )
        want = hidden_and_gradient()
    finally:
        sdar.SdarLM.copies_attention, sdar.norm_rope_form = chosen, form
    errors = {}
    for name in got:
        _require(bool(jnp.all(jnp.isfinite(got[name]))), f"sdar trunk_copies {name}: non-finite")
        rms = float(jnp.sqrt(jnp.mean((got[name] - want[name]) ** 2) / jnp.mean(want[name] ** 2)))
        errors[name] = float(f"{rms:.3e}")
        # Two roundings of one float32 computation at the MXU's default
        # precision (and a few tokens routed otherwise): a wrong mask, head
        # group or gradient reads of order 1.
        _require(rms <= TOL_GRAD, f"sdar trunk_copies {name}: relative rms {rms:.3e} > {TOL_GRAD}")
    return errors


def leg_ppo_pallas_gae(n: int) -> Dict[str, Any]:
    return _anakin_leg(
        n,
        "stoix_tpu.systems.ppo.anakin.ff_ppo",
        "default/anakin/default_ff_ppo.yaml",
        [
            f"arch.total_num_envs={ENVS_PER_CHIP * n}",
            f"system.rollout_length={ANAKIN_ROLLOUT}",
            "system.multistep_impl=pallas",
            "arch.num_updates=4",
        ],
        expect_kernel=True,
    )


def _ant_overrides(n: int) -> List[str]:
    return [
        "env=ant",
        f"arch.total_num_envs={ENVS_PER_CHIP * n}",
        f"system.rollout_length={ANAKIN_ROLLOUT}",
        "arch.num_updates=4",
    ]


def leg_anakin_ant(n: int) -> Dict[str, Any]:
    return _anakin_leg(
        n,
        "stoix_tpu.systems.ppo.anakin.ff_ppo_continuous",
        "default/anakin/default_ff_ppo_continuous.yaml",
        _ant_overrides(n),
        multichip_extras=True,
    )


def leg_anakin_ant_large(n: int) -> Dict[str, Any]:
    return _anakin_leg(
        n,
        "stoix_tpu.systems.ppo.anakin.ff_ppo_continuous",
        "default/anakin/default_ff_ppo_continuous.yaml",
        _ant_overrides(n)
        + [
            "network.actor_network.pre_torso.layer_sizes=[1024,1024]",
            "network.actor_network.pre_torso.compute_dtype=bfloat16",
            "network.critic_network.pre_torso.layer_sizes=[1024,1024]",
            "network.critic_network.pre_torso.compute_dtype=bfloat16",
        ],
    )


def leg_sebulba(n: int) -> Dict[str, Any]:
    from stoix_tpu.parallel import mesh as mesh_lib
    from stoix_tpu.sebulba import sources as sebulba_sources
    from stoix_tpu.systems.ppo.sebulba import ff_ppo as sebulba_ppo
    from stoix_tpu.utils import config as config_lib

    actors, learners = ([0], [0]) if n == 1 else ([0, 1], [2, 3])
    ids = lambda xs: str(xs).replace(" ", "")
    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/sebulba/default_ff_ppo.yaml",
        [
            "env=cartpole",
            "env.backend=cvec",
            f"arch.total_num_envs={SEBULBA_ENVS}",
            f"system.rollout_length={SEBULBA_ROLLOUT}",
            f"arch.actor.device_ids={ids(actors)}",
            f"arch.learner.device_ids={ids(learners)}",
            "arch.evaluator_device_id=0",
            "arch.num_updates=8",
            "arch.total_timesteps=~",
            "arch.num_evaluation=2",
            "arch.num_eval_episodes=8",
            "arch.absolute_metric=False",
            "logger.use_console=False",
        ],
    )
    observed: Dict[str, Any] = {"assembled": []}

    def observing_builder(*args: Any, **kwargs: Any) -> Callable:
        # `learn_step_builder` is run_experiment's own seam: build the stock
        # PPO update, and note where the state and the trajectory batch live
        # the first time it is stepped.
        inner = sebulba_ppo.get_learn_step(*args, **kwargs)

        def learn_step(state: Any, batch: Any) -> Any:
            if "state" not in observed:
                observed["state"] = _placement(state)
                observed["batch"] = _placement(batch)
            return inner(state, batch)

        return learn_step

    # The trajectory hand-off primitive, observed where the Sebulba learner's
    # batch source calls it (several learner devices only).
    assemble = mesh_lib.assemble_global_array

    @functools.wraps(assemble)
    def observing_assemble(*args: Any, **kwargs: Any) -> Any:
        out = assemble(*args, **kwargs)
        observed["assembled"].append(sorted(d.id for d in out.sharding.device_set))
        return out

    errors_before = _counter_total("stoix_tpu_sebulba_evaluator_errors_total")
    crashes_before = _counter_total("stoix_tpu_sebulba_actor_crashes_total")
    sebulba_sources.assemble_global_array = observing_assemble
    try:
        with _tee_train_metrics(observed):
            final_return = sebulba_ppo.run_experiment(
                config, learn_step_builder=observing_builder
            )
    finally:
        sebulba_sources.assemble_global_array = assemble

    stats = sebulba_ppo.LAST_RUN_STATS
    _require(math.isfinite(final_return), f"final return not finite: {final_return}")
    loss_names = _require_finite_losses(observed)
    _require(stats.get("steady_window_steps", 0) > 0, "no steady-state window recorded")
    _require(
        stats["resilience"]["skipped_updates"] == 0
        and stats["resilience"]["actor_restarts"] == 0,
        f"skipped updates or actor restarts: {stats['resilience']}",
    )
    _require(
        _counter_total("stoix_tpu_sebulba_evaluator_errors_total") == errors_before,
        "stoix_tpu_sebulba_evaluator_errors_total moved",
    )
    _require(
        _counter_total("stoix_tpu_sebulba_actor_crashes_total") == crashes_before,
        "stoix_tpu_sebulba_actor_crashes_total moved",
    )
    for what in ("state", "batch"):
        _require(
            observed[what]["platforms"] == ["tpu"]
            and observed[what]["device_ids"] == learners
            and observed[what]["narrowest_leaf_span"] == len(learners),
            f"learner {what} not on learner devices {learners} only: {observed[what]}",
        )
    if len(learners) > 1:
        _require(
            bool(observed["assembled"])
            and all(ids_ == learners for ids_ in observed["assembled"]),
            f"assemble_global_array output not on {learners} only: "
            f"{observed['assembled'][:3]}",
        )
    return {
        "final_return": round(final_return, 3),
        "losses": loss_names,
        "actors": actors,
        "learners": learners,
        "state_on": observed["state"]["device_ids"],
        "batch_on": observed["batch"]["device_ids"],
        "assemble_global_array_calls": len(observed["assembled"]),
    }


# ---------------------------------------------------------------------------
# Kernel leg: compiled (interpret=False) vs reference, forward and gradient
# ---------------------------------------------------------------------------


def _max_abs_err(got: Any, want: Any) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))


def _has_pallas_call(fn: Callable, *args: Any) -> bool:
    import jax

    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def _qkv(seed: int, shape: Tuple[int, ...], dtype: Any) -> Tuple[Any, Any, Any]:
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in keys)


def leg_kernels(n: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from stoix_tpu.ops import scan_kernels
    from stoix_tpu.ops.pallas_attention import flash_attention
    from stoix_tpu.ops.ring_attention import full_attention, make_ring_attention
    from stoix_tpu.parallel import create_mesh

    errors: Dict[str, float] = {}

    def reference(q, k, v, causal):
        # Plain float32 attention at full matmul precision.
        with jax.default_matmul_precision("highest"):
            f32 = lambda x: x.astype(jnp.float32)
            return full_attention(f32(q), f32(k), f32(v), causal=causal)

    def check(name: str, got: Any, want: Any, tol: float) -> None:
        _require(bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), f"{name}: non-finite")
        _require(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
        err = _max_abs_err(got, want)
        errors[name] = err
        _require(err <= tol, f"{name}: max abs error {err:.3e} > {tol:.1e}")

    # 1. The linear-recurrence kernel, bitwise against the scan it replaces.
    rng = np.random.default_rng(0)
    for t_len in (16, 128):
        w = jnp.asarray(rng.uniform(0.0, 1.0, (t_len, ENVS_PER_CHIP)), jnp.float32)
        d = jnp.asarray(rng.normal(size=(t_len, ENVS_PER_CHIP)), jnp.float32)
        init = jnp.asarray(rng.normal(size=(ENVS_PER_CHIP,)), jnp.float32)
        _require(
            _has_pallas_call(scan_kernels.pallas_linear_recurrence_reverse, w, d, init),
            "recurrence: no pallas_call traced",
        )
        check(
            f"recurrence_T{t_len}",
            scan_kernels.pallas_linear_recurrence_reverse(w, d, init),
            jax.jit(scan_kernels._scan_reverse)(w, d, init),
            TOL_RECURRENCE,
        )
    # bfloat16 inputs: widened around the float32-only kernel (a per-row walk
    # over packed bfloat16 rows is what Mosaic refuses), so the result is the
    # float32 scan of the widened inputs, narrowed — still bitwise.
    wb, db, initb = (x.astype(jnp.bfloat16) for x in (w, d, init))
    f32 = lambda x: x.astype(jnp.float32)
    check(
        "recurrence_T128_bf16",
        scan_kernels.pallas_linear_recurrence_reverse(wb, db, initb),
        jax.jit(scan_kernels._scan_reverse)(f32(wb), f32(db), f32(initb)).astype(jnp.bfloat16),
        TOL_RECURRENCE,
    )

    # 2. flash_attention forward: the long bfloat16 shape (causal, not, and a
    #    length that needs padding), ff_trans_ppo's own shape and the
    #    latent-attention cell's (values narrower than queries and keys).
    padded = (FLASH_LONG[0], FLASH_PADDED_LEN) + FLASH_LONG[2:]
    narrow = lambda qkv, d_v: qkv if d_v is None else (*qkv[:2], qkv[2][..., :d_v])
    for name, shape, d_v, dtype, causal in (
        ("flash_bf16_long", FLASH_LONG, None, jnp.bfloat16, False),
        ("flash_bf16_long_causal", FLASH_LONG, None, jnp.bfloat16, True),
        ("flash_bf16_padded_causal", padded, None, jnp.bfloat16, True),
        ("flash_f32_trans_ppo", FLASH_TRANS_PPO, None, jnp.float32, False),
        ("flash_f32_trans_ppo_causal", FLASH_TRANS_PPO, None, jnp.float32, True),
        ("flash_f32_kanana2_causal", FLASH_KANANA2, FLASH_KANANA2_VALUES, jnp.float32, True),
    ):
        q, k, v = narrow(_qkv(1, shape, dtype), d_v)
        attend = functools.partial(flash_attention, causal=causal)
        _require(_has_pallas_call(attend, q, k, v), f"{name}: no pallas_call traced")
        check(name, attend(q, k, v), reference(q, k, v, causal), TOL_ATTN)

    # 3. Its gradient — the backward kernel from the saved result and
    #    log-sum-exp — against the gradient of full_attention on the chip,
    #    under a non-uniform cotangent: ff_trans_ppo's window, the bfloat16
    #    shapes, and the latent-attention cell's minibatch in float32 (32
    #    heads of 192 | 128 over 512 positions).
    def grads(attend, q, k, v, weight):
        loss = lambda q_, k_, v_: jnp.sum(attend(q_, k_, v_).astype(jnp.float32) * weight)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    for name, shape, d_v, dtype, causal in (
        ("flash_grad_f32_trans_ppo_causal", FLASH_TRANS_PPO, None, jnp.float32, True),
        ("flash_grad_bf16_S512", (2, 512, 4, 64), None, jnp.bfloat16, False),
        # (one sequence of the long shape: the reference's gradient holds
        # five [B, 8, 4096, 4096] float32 arrays, 0.5 GB each at B = 1)
        ("flash_grad_bf16_long_causal", (1,) + FLASH_LONG[1:], None, jnp.bfloat16, True),
        ("flash_grad_f32_kanana2_causal", FLASH_KANANA2, FLASH_KANANA2_VALUES, jnp.float32, True),
    ):
        q, k, v = narrow(_qkv(2, shape, dtype), d_v)
        weight = jax.random.normal(jax.random.PRNGKey(3), v.shape, jnp.float32)
        flash = functools.partial(flash_attention, causal=causal)
        traced = jax.make_jaxpr(lambda *a: grads(flash, *a, weight))(q, k, v)
        _require(str(traced).count("pallas_call") == 2, f"{name}: not one kernel each way")
        got = grads(flash, q, k, v, weight)
        want = grads(functools.partial(reference, causal=causal), q, k, v, weight)
        for axis, g, r in zip("qkv", got, want):
            check(f"{name}_d{axis}", g, r, TOL_GRAD)

    # 4. flash_attention_chunk through ring attention over ALL the chips, with
    #    the shard length a multiple of 128 so the kernel branch is the one
    #    ring_attention takes by itself; forward and backward.
    mesh = create_mesh({"data": -1})
    seq_sharding = NamedSharding(mesh, P(None, "data"))
    shape = (2, RING_SHARD_LEN * n, 4, 64)
    for causal in (False, True):
        name = f"ring_x{n}_S{shape[1]}" + ("_causal" if causal else "")
        ring = make_ring_attention(mesh, axis="data", causal=causal)
        q, k, v = (jax.device_put(x, seq_sharding) for x in _qkv(4, shape, jnp.float32))
        _require(_has_pallas_call(ring, q, k, v), f"{name}: no pallas_call traced")
        out = ring(q, k, v)
        _require(
            len(out.sharding.device_set) == n, f"{name}: output not on all {n} chip(s)"
        )
        check(name, out, reference(q, k, v, causal), TOL_ATTN)
        weight = jax.random.normal(jax.random.PRNGKey(5), shape, jnp.float32)
        got = grads(ring, q, k, v, weight)
        want = grads(functools.partial(full_attention, causal=causal), q, k, v, weight)
        for axis, g, r in zip("qkv", got, want):
            check(f"{name}_grad_d{axis}", g, r, TOL_GRAD)

    # 5. The delta rule's update pair (ops/delta_rule.py) against the recurrence
    #    position by position at full precision: two sequences of two chunks,
    #    8 heads of 128, from a state; outputs, the state left, every gradient
    #    (each over its reference's largest entry: the products are one
    #    bfloat16 pass and the gradients span three decades).
    from stoix_tpu.ops import delta_rule

    keys = jax.random.split(jax.random.PRNGKey(6), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (2, 2 * delta_rule.UPDATE_CHUNK, 8, 128)
    q, k = (unit(jax.random.normal(x, shape)) for x in keys[:2])
    operands = (
        q * 128.0**-0.5, k, jax.random.normal(keys[2], shape),
        -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], shape) * 2.0 - 2.0),
        jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3])),
        0.1 * jax.random.normal(keys[5], (2, 8, 128, 128)),
    )
    weight = jax.random.normal(keys[6], shape)
    _require(
        _has_pallas_call(delta_rule.delta_rule_update_kernel, *operands),
        "delta rule: no pallas_call traced",
    )
    loss = lambda rule: lambda *a: jnp.sum(rule(*a)[0] * weight) + jnp.sum(rule(*a)[1] ** 2)
    both = lambda rule: jax.jit(lambda *a: (rule(*a), jax.grad(loss(rule), argnums=range(6))(*a)))
    (out, state), got = both(delta_rule.delta_rule_update_kernel)(*operands)
    with jax.default_matmul_precision("highest"):
        (want_out, want_state), want = both(delta_rule.delta_rule_scan)(*operands)
    in_scale = lambda x, ref: x / jnp.max(jnp.abs(ref))
    check("delta_rule_update", in_scale(out, want_out), in_scale(want_out, want_out), TOL_ATTN)
    check(
        "delta_rule_update_state", in_scale(state, want_state), in_scale(want_state, want_state),
        TOL_ATTN,
    )
    for name, g, r in zip(("q", "k", "v", "g", "beta", "state"), got, want):
        check(f"delta_rule_update_d{name}", in_scale(g, r), in_scale(r, r), TOL_GRAD)

    # 7. q's and k's per-head norm and rotation as one pass each way
    #    (`ops/qk_norm_rope.py`) against `rms_norm` + `rope` as XLA compiles
    #    them, both float32 elementwise: 32 heads written a head a sublane and
    #    4 side by side, a last row tile of 4 rows, positions that repeat as
    #    `trunk_copies` repeats them; the result and the gradients by the rows
    #    and by the norm's weight, each over its reference's largest entry.
    from stoix_tpu.networks.olmoe import rms_norm, rope, rope_angles
    from stoix_tpu.ops.qk_norm_rope import qk_norm_rope

    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    own = jnp.arange(132)
    positions = jnp.broadcast_to(jnp.concatenate([own, jnp.tile(own[4:], 2)]), (2, 388))
    for name, heads in (("q", 32), ("k", 4)):
        x = 0.7 * jax.random.normal(keys[0], (2, 388, heads * 128))
        gain = 1.0 + 0.1 * jax.random.normal(keys[1], (128,))
        weight = jax.random.normal(keys[2], (2, 388, heads, 128))
        plain = lambda x, gain: rope(
            rms_norm(x.reshape(2, 388, heads, 128), gain, 1e-6), positions, 1e6
        )
        kernel = lambda x, gain: qk_norm_rope(
            x, gain, rope_angles(positions, 128, 1e6), heads=heads, eps=1e-6
        )
        _require(_has_pallas_call(kernel, x, gain), "qk_norm_rope: no pallas_call traced")
        both = lambda fn: jax.jit(lambda x, gain: (
            fn(x, gain), jax.grad(lambda x, gain: jnp.sum(fn(x, gain) * weight), (0, 1))(x, gain)
        ))
        (out, got), (want_out, want) = both(kernel)(x, gain), both(plain)(x, gain)
        check(f"qk_norm_rope_{name}", in_scale(out, want_out), in_scale(want_out, want_out), TOL_ELEMENTWISE)
        for axis, g, r in zip(("rows", "weight"), got, want):
            check(f"qk_norm_rope_{name}_d{axis}", in_scale(g, r), in_scale(r, r), TOL_ELEMENTWISE)

    return {"max_abs_error": {k: float(f"{v:.3e}") for k, v in errors.items()}}


# ---------------------------------------------------------------------------


LEGS: List[Tuple[str, Callable[[int], Dict[str, Any]]]] = [
    ("kernels", leg_kernels),
    ("trans_ppo", leg_trans_ppo),
    ("lm_ppo", leg_lm_ppo),
    ("lfm2_ppo", leg_lfm2_ppo),
    ("kanana2_ppo", leg_kanana2_ppo),
    ("ling3_ppo", leg_ling3_ppo),
    ("sdar_ppo", leg_sdar_ppo),
    ("ppo_pallas_gae", leg_ppo_pallas_gae),
    ("sebulba", leg_sebulba),
    ("anakin_ant", leg_anakin_ant),
    ("anakin_ant_large", leg_anakin_ant_large),
]


def main() -> int:
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    _log(
        f"platform={device['platform']} device_kind={device['kind']} "
        f"device_count={device['count']}"
    )
    if device["platform"] != "tpu":
        print(
            f"chip_smoke.py needs a TPU and there is no fallback: JAX found "
            f"platform {device['platform']!r} ({device['kind']}). Nothing was run.",
            file=sys.stderr,
        )
        return 1
    n = device["count"]
    if n not in (1, 4):
        print(f"chip_smoke.py knows 1 chip or 4, found {n}", file=sys.stderr)
        return 1
    _log("device order: " + ", ".join(
        f"id={d.id} coords={getattr(d, 'coords', None)}" for d in devices
    ))

    from stoix_tpu.utils import compilecache

    _log(f"compile cache: {compilecache.configure()}")

    failed: List[str] = []
    for name, leg in LEGS:
        cache_before = compilecache.cache_stats()
        start = time.perf_counter()
        try:
            facts = leg(n)
            status = "PASS"
        except Exception:  # noqa: BLE001 — reported, run continues to show every leg, exit is non-zero
            traceback.print_exc()
            facts = {}
            status = "FAIL"
            failed.append(name)
        cache_after = compilecache.cache_stats()
        _log(
            f"leg {name}: {status} in {time.perf_counter() - start:.1f}s "
            f"(persistent cache: +{cache_after['hits'] - cache_before['hits']} hits, "
            f"+{cache_after['misses'] - cache_before['misses']} misses) "
            f"{json.dumps(facts, default=str)}"
        )
    totals = compilecache.cache_stats()
    _log(f"compile cache totals: hits={totals['hits']} misses={totals['misses']}")
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
