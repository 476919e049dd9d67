"""Driver for the hybrid token policy (`ff_lm_ppo` with `network=lfm2_moe`):
the seams and the tick ARE drivers/anakin_lm.py's — `learner_setup` (state
placement; the forwarding recorder that keeps the newest output state and the
executable the runner compiled ahead of time), the configuration's
`networks_seam`, `StoixLogger.log`, one eval window a tick — so that driver
is loaded and run as it is, as drivers/anakin_sdar.py loads its recorder.

What differs is what the readers divide by: `ctx.shapes` comes from
harness/flops_lfm2.py — conv mixers, dense feed-forwards, the held experts'
rows — with the pairs a token a layer that landed on the held experts as the
run itself logged them.
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness import flops_lfm2, loader


def run(ctx: Any) -> None:
    from stoix_tpu.utils import config as config_lib

    loader.load_driver("anakin_lm", ctx.cell.root).run(ctx)

    # The config the run composed (the same overrides compose the same one).
    config = config_lib.compose(
        config_lib.default_config_dir(), ctx.cell.config["default_yaml"], ctx.overrides()
    )
    logged = lambda name: [rec[name] for _, rec in ctx.train if name in rec]
    mean = lambda values: sum(values) / len(values) if values else None
    ctx.shapes = flops_lfm2.lfm2_ppo_shapes(
        config, envs_per_chip=int(config.arch.total_num_envs) // ctx.cell.chips,
        updates_per_tick=int(ctx.shapes["updates_per_tick"]),
        held_pairs={
            "update": mean(logged("held_pairs_per_token")),
            "rollout": mean(logged("rollout_held_pairs_per_token")),
        },
    )
