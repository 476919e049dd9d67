"""Driver for the latent-attention token policy (`ff_lm_ppo` with
`network=kanana2_moe`): the seams and the tick ARE drivers/anakin_lm.py's, so
that driver is loaded and run as it is, as drivers/anakin_lfm2.py does.

What differs is what the readers divide by: `ctx.shapes` comes from
harness/flops_mla.py — latent attention's projections, expansion, scores and
decode step, the shared expert, the held experts' rows — with the pairs a
token a layer that landed on the held experts as the run itself logged them.
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness import flops_mla, loader


def run(ctx: Any) -> None:
    from stoix_tpu.utils import config as config_lib

    loader.load_driver("anakin_lm", ctx.cell.root).run(ctx)

    # The config the run composed (the same overrides compose the same one).
    config = config_lib.compose(
        config_lib.default_config_dir(), ctx.cell.config["default_yaml"], ctx.overrides()
    )
    logged = lambda name: [rec[name] for _, rec in ctx.train if name in rec]
    mean = lambda values: sum(values) / len(values) if values else None
    ctx.shapes = flops_mla.mla_ppo_shapes(
        config, envs_per_chip=int(config.arch.total_num_envs) // ctx.cell.chips,
        updates_per_tick=int(ctx.shapes["updates_per_tick"]),
        held_pairs={
            "update": mean(logged("held_pairs_per_token")),
            "rollout": mean(logged("rollout_held_pairs_per_token")),
        },
    )
