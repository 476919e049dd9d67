"""Driver for the window-and-full attention token policy (`ff_lm_ppo` with
`network=laguna_xs2_moe`): the seams and the tick ARE drivers/anakin_lm.py's,
so that driver is loaded and run as it is, as drivers/anakin_ling3.py does.

What differs is what the readers divide by: `ctx.shapes` comes from
harness/flops_swa.py — projections a layer at ITS head count, the pairs of a
band and of a triangle, a decode step's live rows a layer kind, the shared
expert, the held experts' rows — with the pairs a token a layer that landed
on the held experts as the run itself logged them.
"""

from __future__ import annotations

from typing import Any

import jax

from benchmarks.harness import flops_swa, loader


def run(ctx: Any) -> None:
    from stoix_tpu.utils import config as config_lib

    loader.load_driver("anakin_lm", ctx.cell.root).run(ctx)

    # The config the run composed (the same overrides compose the same one).
    config = config_lib.compose(
        config_lib.default_config_dir(), ctx.cell.config["default_yaml"], ctx.overrides()
    )
    # The XLA options the run's learner was compiled with, if its yaml names
    # any (the key and the condition are ff_lm_ppo's): the reference compiles
    # its two stand-in programs with them too.
    on_tpu = jax.default_backend() == "tpu"
    ctx.networks["compiler_options"] = dict(
        (config.network.get("learner_compiler_options") if on_tpu else None) or {}
    )
    logged = lambda name: [rec[name] for _, rec in ctx.train if name in rec]
    mean = lambda values: sum(values) / len(values) if values else None
    ctx.shapes = flops_swa.swa_ppo_shapes(
        config, envs_per_chip=int(config.arch.total_num_envs) // ctx.cell.chips,
        updates_per_tick=int(ctx.shapes["updates_per_tick"]),
        held_pairs={
            "update": mean(logged("held_pairs_per_token")),
            "rollout": mean(logged("rollout_held_pairs_per_token")),
        },
    )
