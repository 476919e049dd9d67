"""flops.py's arithmetic and the peaks table."""

import pytest

import _paths  # noqa: F401
from benchmarks.harness import flops, peaks


def test_dense_layers_of_the_ant_actor_and_critic():
    assert flops.dense_layers(27, [256, 256], [8, 8]) == [(27, 256), (256, 256), (256, 8), (256, 8)]
    assert flops.dense_layers(27, [256, 256], [1]) == [(27, 256), (256, 256), (256, 1)]


def test_mlp_train_cost_is_six_flops_a_weight_a_sample():
    cost = flops.mlp_train_cost(10, [(4, 8), (8, 2)], dtype_bytes=4)
    assert cost["flops"] == 6 * 10 * (4 * 8 + 8 * 2)
    assert cost["bytes"] == 2 * 10 * (12 + 10) * 4 + 3 * (32 + 16) * 4


def test_ppo_update_cost_counts_every_epoch_once():
    actor = flops.dense_layers(27, [256, 256], [8, 8])
    critic = flops.dense_layers(27, [256, 256], [1])
    cost = flops.ppo_update_cost(2048, 16, 4, actor, critic)
    assert cost["samples"] == 2048 * 16 * 4
    weights = sum(i * o for i, o in actor) + sum(i * o for i, o in critic)
    assert cost["flops"] == 6.0 * cost["samples"] * weights


def test_v5e_peaks_and_which_bound_binds():
    table = peaks.peaks_for("TPU v5 lite")
    assert table["bf16_flops_per_s"] == 197e12 and table["hbm_bytes_per_s"] == 819e9
    compute = peaks.least_seconds(197e12, 1.0, "TPU v5 lite")
    assert compute["binds"] == "compute" and compute["seconds"] == pytest.approx(1.0)
    memory = peaks.least_seconds(1.0, 819e9 * 2, "TPU v5 lite")
    assert memory["binds"] == "memory" and memory["seconds"] == pytest.approx(2.0)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "_source", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for(kind)
