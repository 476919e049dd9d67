"""Test configuration: force an 8-device virtual CPU platform BEFORE jax import
so every test can exercise real multi-device sharding (mesh axes, shard_map,
collectives) without TPU hardware. This is the fake-device harness the reference
lacks (SURVEY.md §4 'Multi-node/multi-device without a cluster: not tested')."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def shared_identity_checkpoint(tmp_path_factory):
    """ONE tiny trained ff_ppo identity_game checkpoint for the whole
    session (tier-1 budget: every e2e module training its own copy costs
    ~7s each — serve, loop, ... all restore from this one store instead).
    Yields (store_dir, train_root_dir). Tests must treat the store as
    READ-ONLY; anything that writes new steps (hot-swap publishes, loop
    learners) copies it into its own tmp dir first."""
    import os
    import shutil

    from stoix_tpu.systems.ppo.anakin import ff_ppo
    from stoix_tpu.utils import config as config_lib

    uid = "shared-id-ckpt"
    root = tmp_path_factory.mktemp("shared_identity_ckpt")
    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/anakin/default_ff_ppo.yaml",
        [
            "env=identity_game",
            "arch.total_num_envs=16",
            "arch.total_timesteps=1024",
            "arch.num_evaluation=1",
            "arch.num_eval_episodes=8",
            "arch.absolute_metric=False",
            "system.rollout_length=8",
            "system.num_minibatches=2",
            "logger.use_console=False",
            f"logger.base_exp_path={root}/results",
            "logger.checkpointing.save_model=True",
            f"logger.checkpointing.save_args.checkpoint_uid={uid}",
        ],
    )
    cwd = os.getcwd()
    os.chdir(root)
    try:
        ff_ppo.run_experiment(config)
    finally:
        os.chdir(cwd)
    store = os.path.join(str(root), "checkpoints", uid, "ff_ppo")
    assert os.path.isdir(store)
    yield store, str(root)
    shutil.rmtree(str(root), ignore_errors=True)
