"""The OLMoE and SDAR learners are the programs they were before the router
took a scoring function, a selection bias and a scale as arguments and
before `ff_lm_ppo` asked its network for the carry (PR 33): the StableHLO of
each `learner_fn` at its tiny preset, source locations taken off, hashes to
what the parent commit's did (recorded there with this very function; the
check PRs 29 and 30 made by hand). A change that is MEANT to alter one of
these programs records its new digest here and says so in CHANGES.md."""

import hashlib
import importlib
import re

import jax
import pytest

from stoix_tpu import envs
from stoix_tpu.utils import config as config_lib

from test_lm_ppo import TINY as OLMOE_TINY
from test_sdar_ppo import TINY as SDAR_TINY

LEARNERS = {
    "olmoe": (
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo", "default/anakin/default_ff_lm_ppo.yaml",
        OLMOE_TINY, "e9c8cdb597985fc46051771308411fdd477df5f002fd72ba64180c4874fc1c2b",
    ),
    "olmoe_2layers": (
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo", "default/anakin/default_ff_lm_ppo.yaml",
        OLMOE_TINY + ["network.actor_network.num_layers=2"], "4c1be9f6a79401ca6477ed2c9cb6cc0b1b8020cd7e52cda9fb4411949b61ec01",
    ),
    "sdar": (
        "stoix_tpu.systems.ppo.anakin.ff_sdar_ppo", "default/anakin/default_ff_sdar_ppo.yaml",
        SDAR_TINY + ["arch.total_num_envs=32"], "9b82252633639bbe9f59820e1de6647fe70a3e5852c51d88dd6d336345fe4e10",
    ),
}


def learner_digest(module_name, default_yaml, overrides):
    """sha256 of the learner's StableHLO text without its `loc(...)`s."""
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    module = importlib.import_module(module_name)
    config = config_lib.compose(config_lib.default_config_dir(), default_yaml, list(overrides))
    mesh = MeshRoles.from_config(config).learn_mesh()
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, _ = envs.make(config)
    setup = module.learner_setup(env, config, mesh, jax.random.PRNGKey(0))
    text = setup.learn.lower(setup.learner_state).as_text()
    text = re.sub(r"\s*loc\([^\n]*\)", "", text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(LEARNERS))
def test_the_learner_is_the_program_the_parent_commit_lowered(devices, name):
    module_name, default_yaml, overrides, want = LEARNERS[name]
    assert learner_digest(module_name, default_yaml, overrides) == want


if __name__ == "__main__":  # prints the digests of the tree it runs in
    for name, (module_name, default_yaml, overrides, _) in sorted(LEARNERS.items()):
        print(name, learner_digest(module_name, default_yaml, overrides))
