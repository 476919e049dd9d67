"""Operations and bytes the PPO update's matmuls need, from shapes alone.

What is counted: the forward and backward matrix multiplications of the
actor's and the critic's MLPs over every sample of every minibatch of every
epoch of one update. A Dense layer [in, out] on B samples costs 2*B*in*out
FLOPs forward and twice that backward (gradients with respect to the weights
and to the inputs), 6*B*in*out in all; the first layer's input gradient is
computed by XLA only if something needs it, and it is counted anyway (27 or 4
inputs against 256: under 2% of the total). Elementwise work, the losses, the
optimizer and the rollout are NOT counted: this is the least the matmuls
need, the numerator of a roofline share, not the update's whole cost.

Bytes: each layer reads its input activations and writes its outputs forward,
and reads both again backward, at the activation dtype's width; weights are
read three times (forward, both backward products) and are negligible here.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence


def dense_layers(in_dim: int, hidden: Sequence[int], head_outs: Sequence[int]) -> list:
    """[(in, out), ...] of an MLP torso followed by its head's Dense layers
    (each head layer reads the torso's last width)."""
    layers, width = [], int(in_dim)
    for size in hidden:
        layers.append((width, int(size)))
        width = int(size)
    layers.extend((width, int(out)) for out in head_outs)
    return layers


def mlp_train_cost(samples: int, layers: Sequence[tuple], dtype_bytes: int = 4) -> Dict[str, float]:
    flops = sum(6.0 * samples * i * o for i, o in layers)
    act_bytes = sum(2.0 * samples * (i + o) * dtype_bytes for i, o in layers)
    weight_bytes = sum(3.0 * i * o * dtype_bytes for i, o in layers)
    return {"flops": flops, "bytes": act_bytes + weight_bytes}


def ppo_update_cost(
    envs_per_chip: int, rollout_length: int, epochs: int,
    actor_layers: Sequence[tuple], critic_layers: Sequence[tuple], dtype_bytes: int = 4,
) -> Dict[str, Any]:
    """One PPO update on one chip: every epoch passes every one of the
    envs*rollout samples through both networks once, forward and backward."""
    samples = int(envs_per_chip) * int(rollout_length) * int(epochs)
    actor = mlp_train_cost(samples, actor_layers, dtype_bytes)
    critic = mlp_train_cost(samples, critic_layers, dtype_bytes)
    return {
        "samples": samples,
        "flops": actor["flops"] + critic["flops"],
        "bytes": actor["bytes"] + critic["bytes"],
    }


def ppo_shapes(
    config: Any, head: str, obs_dim: int, envs_per_chip: int, updates_per_tick: int
) -> Dict[str, Any]:
    """What the system's composed config resolved to, and from it
    `update_cost`: the matmuls of one PPO update on one chip (`head` is the
    configuration's reference action head: a tanh-Normal head has two Dense
    layers, the others one). The per-layer readers take `update_cost` and
    `updates_per_tick` from here whatever function a driver made them with."""
    action_dim = int(config.system.action_dim)
    head_outs = [action_dim] * (2 if head == "tanh_normal" else 1)
    shapes = {
        "envs_per_chip": int(envs_per_chip),
        "rollout_length": int(config.system.rollout_length),
        "epochs": int(config.system.epochs),
        "num_minibatches": int(config.system.num_minibatches),
        "updates_per_tick": int(updates_per_tick),
        "actor_layers": dense_layers(
            obs_dim, list(config.network.actor_network.pre_torso.layer_sizes), head_outs
        ),
        "critic_layers": dense_layers(
            obs_dim, list(config.network.critic_network.pre_torso.layer_sizes), [1]
        ),
    }
    shapes["update_cost"] = ppo_update_cost(
        shapes["envs_per_chip"], shapes["rollout_length"], shapes["epochs"],
        shapes["actor_layers"], shapes["critic_layers"],
    )
    return shapes
