"""The token-policy learners are the programs they were: the StableHLO of
each `learner_fn` at its tiny preset, source locations taken off, hashes to
the digest recorded here with this very function (the check PRs 29 and 30
made by hand). A change that is MEANT to alter one of these programs records
its new digest here and says so in CHANGES.md.

`sdar` is the parent commit's program since before PR 33 (the router's new
arguments and the network-declared carry left it as it was). `olmoe` and
`olmoe_2layers` were re-recorded in PR 35, which made `ff_lm_ppo` ask for the
carry of ONE position (`length []`: the key/value row written as one slab);
`lfm2` was first recorded there, after the same change. `kanana2` is the
parent commit's program of PR 40 (computed on `git archive` of it: the
head-wise gate, the group-limited choice and the delta mixer's keys left it
as it was); `ling3` was first recorded in PR 40 and re-recorded in PR 41,
ALONE: the delta mixer's update no longer runs the chunked recurrence in
four rematerialised groups of heads (`_in_head_groups` went with the Pallas
kernel pair that made it unnecessary on the chip; on the CPU the mixer now
calls `delta_rule_chunked` once), and the other five are to the digit what
they were."""

import hashlib
import importlib
import re

import jax
import pytest

from stoix_tpu import envs
from stoix_tpu.utils import config as config_lib

from test_kanana2_ppo import TINY as KANANA2_TINY
from test_lfm2_ppo import TINY as LFM2_TINY
from test_ling3_ppo import TINY as LING3_TINY
from test_lm_ppo import TINY as OLMOE_TINY
from test_sdar_ppo import TINY as SDAR_TINY

LEARNERS = {
    "olmoe": (
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo", "default/anakin/default_ff_lm_ppo.yaml",
        OLMOE_TINY, "5460d1cc8a6475ff7f2aa7957f99f68b131da9b736bcffd644ef791a223217aa",
    ),
    "olmoe_2layers": (
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo", "default/anakin/default_ff_lm_ppo.yaml",
        OLMOE_TINY + ["network.actor_network.num_layers=2"], "19a79201f07047178d73fdd81dccf5aef9508f235e25db24981d70aaba38159f",
    ),
    "lfm2": (
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo", "default/anakin/default_ff_lm_ppo.yaml",
        LFM2_TINY, "b822d1be53da6e9c2300ac46f52984cd49c8bbdf2442fef1986d98f1950c7b29",
    ),
    "kanana2": (
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo", "default/anakin/default_ff_lm_ppo.yaml",
        KANANA2_TINY, "16b86da1380421516c624d64c58d133794e56c455d4d4a05073093fd84d6eadc",
    ),
    "ling3": (
        "stoix_tpu.systems.ppo.anakin.ff_lm_ppo", "default/anakin/default_ff_lm_ppo.yaml",
        LING3_TINY, "7b450a12541031ff826da493afa5612606ce8212a3d30ba975d493d1f7c69db5",
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
