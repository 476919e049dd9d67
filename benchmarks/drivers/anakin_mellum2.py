"""Driver for the token policy that generates from a prefilled prompt
(`ff_lm_ppo` with `network=mellum2_moe` and `env.kwargs.prompt_length`): the
seams and the tick ARE drivers/anakin_lm.py's, so that driver is loaded and
run as it is, as drivers/anakin_laguna.py does.

What differs is what the run leaves for the reference and the readers.
drivers/anakin_lm.py asks the program for its entry points at `max_len` =
`system.rollout_length`; a sequence here is prompt and response, so this
driver asks again at their sum (with the network objects the program built,
seen through the configuration's `networks_seam` once more, around that
driver's own recorder) and hands over `prefill` beside `forward` and `step`.
`ctx.shapes` comes from harness/flops_mellum2.py — decode means over the
positions P .. P + G - 1, the update's pairs over P + G, the head on G, the
prefill's forward — with the pairs a token a layer that landed on the held
experts as the run itself logged them, prefill, rollout and update.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict

from benchmarks.harness import flops_mellum2, loader


def run(ctx: Any) -> None:
    from stoix_tpu.utils import config as config_lib

    spec = ctx.cell.config
    module = importlib.import_module(spec["system_module"])
    nets_module_name, nets_attr = spec["networks_seam"].split(":")
    nets_module = importlib.import_module(nets_module_name)
    build_networks = getattr(nets_module, nets_attr)
    seen: Dict[str, Any] = {}

    def recording_build_networks(*args: Any, **kwargs: Any) -> Any:
        seen["networks"] = build_networks(*args, **kwargs)
        return seen["networks"]

    setattr(nets_module, nets_attr, recording_build_networks)
    try:
        loader.load_driver("anakin_lm", ctx.cell.root).run(ctx)
    finally:
        setattr(nets_module, nets_attr, build_networks)

    # The config the run composed (the same overrides compose the same one).
    config = config_lib.compose(
        config_lib.default_config_dir(), spec["default_yaml"], ctx.overrides()
    )
    prompt, response = int(config.env.kwargs.prompt_length), int(config.system.rollout_length)
    functions = module.network_functions(*seen["networks"], prompt + response)
    ctx.networks.update(
        forward=functions.forward, step=functions.step, init_cache=functions.init_cache,
        prefill=functions.prefill,
    )
    logged = lambda name: [rec[name] for _, rec in ctx.train if name in rec]
    mean = lambda values: sum(values) / len(values) if values else None
    ctx.shapes = flops_mellum2.mellum2_ppo_shapes(
        config, envs_per_chip=int(config.arch.total_num_envs) // ctx.cell.chips,
        updates_per_tick=int(ctx.shapes["updates_per_tick"]),
        held_pairs={
            "update": mean(logged("held_pairs_per_token")),
            "rollout": mean(logged("rollout_held_pairs_per_token")),
            "prefill": mean(logged("prefill_held_pairs_per_token")),
        },
    )
