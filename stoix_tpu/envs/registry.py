"""Environment factory — the `make_env.py` equivalent.

The reference dispatches over 14 external suites (reference
stoix/utils/make_env.py:420-433 `ENV_MAKERS`); this registry dispatches over the
first-party suites plus optional external ones when present, and applies the
canonical wrapper stack (reference make_env.py:29-61).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

from stoix_tpu.envs import (
    block_token_task,
    breakout_pixel,
    classic,
    debug,
    doorkey,
    game2048,
    locomotion,
    minatar,
    snake,
    token_task,
)
from stoix_tpu.envs.core import Environment
from stoix_tpu.envs.wrappers import (
    EpisodeStepLimit,
    FlattenObservationWrapper,
    RecordEpisodeMetrics,
    apply_core_wrappers,
)

# scenario name -> constructor(**env_kwargs)
ENV_REGISTRY: Dict[str, Callable[..., Environment]] = {
    "CartPole-v1": classic.CartPole,
    "Pendulum-v1": classic.Pendulum,
    "Acrobot-v1": classic.Acrobot,
    "MountainCar-v0": classic.MountainCar,
    "MountainCarContinuous-v0": classic.MountainCarContinuous,
    "Catch-bsuite": classic.Catch,
    "Ant": locomotion.Ant,
    "Hopper": locomotion.Hopper,
    "Walker2d": locomotion.Walker2d,
    "HalfCheetah": locomotion.HalfCheetah,
    "Breakout-minatar": minatar.Breakout,
    "Breakout-atari": breakout_pixel.BreakoutPixel,
    "Asterix-minatar": minatar.Asterix,
    "Freeway-minatar": minatar.Freeway,
    "SpaceInvaders-minatar": minatar.SpaceInvaders,
    "Snake-v1": snake.Snake,
    "Game2048-v1": game2048.Game2048,
    "DoorKey-v0": doorkey.DoorKey,
    "IdentityGame": debug.IdentityGame,
    "SequenceGame": debug.SequenceGame,
    "TokenTask": token_task.TokenTask,
    "BlockTokenTask": block_token_task.BlockTokenTask,
}


def register(name: str, ctor: Callable[..., Environment]) -> None:
    ENV_REGISTRY[name] = ctor


def make_single(scenario: str, suite: Optional[str] = None, **env_kwargs: Any) -> Environment:
    """Construct a raw (unwrapped, unbatched) environment.

    `suite` selects an external-suite adapter (gymnax/brax/jumanji, lazy
    imports — see stoix_tpu/envs/suites.py); first-party scenarios resolve
    through ENV_REGISTRY regardless of the suite tag so configs can spell
    `env_name: classic` etc. explicitly.
    """
    from stoix_tpu.envs import suites

    # An explicit external-suite tag wins over the first-party registry —
    # e.g. env_name: gymnax + CartPole-v1 must build the gymnax adapter, not
    # the first-party CartPole that happens to share the scenario name.
    if suite in suites.SUITE_MAKERS:
        return suites.SUITE_MAKERS[suite](scenario, **env_kwargs)
    if scenario in ENV_REGISTRY:
        return ENV_REGISTRY[scenario](**env_kwargs)
    raise ValueError(
        f"Unknown environment '{scenario}' (suite={suite!r}). First-party: "
        f"{sorted(ENV_REGISTRY)}; external suites: {sorted(suites.SUITE_MAKERS)}"
    )


def make(config: Any) -> Tuple[Environment, Environment]:
    """Build (train_env, eval_env) from a config with an `env` section.

    Expected config fields (mirrors reference configs/env/**):
        env.scenario.name        — registry key
        env.kwargs               — ctor kwargs (optional)
        env.wrapper              — dict(max_episode_steps, use_optimistic_reset,
                                   reset_ratio, use_cached_auto_reset,
                                   flatten_observation) (optional)
        arch.total_num_envs      — global env count (split across data shards upstream)
    """
    env_cfg = config.env
    kwargs = dict(getattr(env_cfg, "kwargs", {}) or {})
    scenario = env_cfg.scenario.name if hasattr(env_cfg.scenario, "name") else env_cfg.scenario
    suite = getattr(env_cfg, "env_name", None)
    wrapper_cfg = dict(getattr(env_cfg, "wrapper", {}) or {})

    # Kinetix keeps distinct train/eval level sources (reference
    # make_env.py:240-245 builds separate reset functions); every other suite
    # constructs the two envs identically.
    if suite == "kinetix":
        train_env = make_single(scenario, suite=suite, role="train", **kwargs)
        eval_env = make_single(scenario, suite=suite, role="eval", **kwargs)
    else:
        train_env = make_single(scenario, suite=suite, **kwargs)
        eval_env = make_single(scenario, suite=suite, **kwargs)

    if wrapper_cfg.get("flatten_observation", False):
        train_env = FlattenObservationWrapper(train_env)
        eval_env = FlattenObservationWrapper(eval_env)

    num_envs = int(config.arch.total_num_envs)
    train_env = apply_core_wrappers(
        train_env,
        num_envs=num_envs,
        max_episode_steps=wrapper_cfg.get("max_episode_steps"),
        use_optimistic_reset=bool(wrapper_cfg.get("use_optimistic_reset", False)),
        reset_ratio=int(wrapper_cfg.get("reset_ratio", 16)),
        use_cached_auto_reset=bool(wrapper_cfg.get("use_cached_auto_reset", False)),
    )
    # Eval env: metrics + step limit only; episodes must genuinely end (no
    # auto-reset) because the evaluator's while_loop keys off timestep.last()
    # (reference stoix/evaluator.py:152).
    if wrapper_cfg.get("max_episode_steps"):
        eval_env = EpisodeStepLimit(eval_env, wrapper_cfg["max_episode_steps"])
    eval_env = RecordEpisodeMetrics(eval_env)
    return train_env, eval_env
