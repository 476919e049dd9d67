"""Anakin DQN-Reg (reference stoix/systems/q_learning/ff_dqn_reg.py, 574 LoC):
DQN with a regularization term that directly penalizes Q(s,a)
(loss = reg * Q(s,a) + 0.5 td^2 — Co-Reyes et al., Evolving RL Algorithms)."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from stoix_tpu.base_types import Transition
from stoix_tpu.ops import pick_along_last
from stoix_tpu.systems.q_learning.q_family import run_q_experiment
from stoix_tpu.utils import config as config_lib


def dqn_reg_loss(online_params: Any, target_params: Any, batch: Transition, q_apply, config):
    q_tm1 = q_apply(online_params, batch.obs, 0.0).preferences
    q_t = q_apply(target_params, batch.next_obs, 0.0).preferences
    d_t = float(config.system.gamma) * (1.0 - batch.done.astype(jnp.float32))
    qa_tm1 = pick_along_last(q_tm1, batch.action)
    target = jax.lax.stop_gradient(batch.reward + d_t * jnp.max(q_t, axis=-1))
    td = target - qa_tm1
    reg = float(config.system.get("regularizer_coeff", 0.1))
    loss = jnp.mean(reg * qa_tm1 + 0.5 * td**2)
    return loss, {"q_loss": loss, "mean_q": jnp.mean(q_tm1)}


def run_experiment(config: Any) -> float:
    return run_q_experiment(config, dqn_reg_loss)


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_dqn_reg.yaml", sys.argv[1:]
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
