"""`stoix_tpu.utils.checkpointing` without its library (docs/DESIGN.md §2.2,
ISSUE 37): everything in the module that is not a store being built or read
runs in a process in which `import orbax` RAISES — the guard that a later
edit does not put the library back on the module's top, where every run with
checkpointing off would pay for it again. One child process runs every case
and prints one JSON line; what a saving run does is tests/test_checkpointing.py's.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r'''
import json, os, sys, tempfile

sys.modules["orbax"] = None  # `import orbax[.checkpoint]` raises ImportError from here on
sys.path.insert(0, sys.argv[1])

import jax
import numpy as np

from stoix_tpu.utils import checkpointing
from stoix_tpu.utils import config as config_lib

out = {}


def case(name):
    def run(fn):
        try:
            out[name] = {"ok": fn()}
        except Exception as exc:  # noqa: BLE001 — the parent reads what went wrong
            out[name] = {"raised": f"{type(exc).__name__}: {exc}"}
    return run


@case("importers")
def _():
    # Every module that imports checkpointing for its pure helpers or the class.
    import stoix_tpu.loop.runner, stoix_tpu.population.elastic, stoix_tpu.resilience.fleet
    import stoix_tpu.serve.checkpoint, stoix_tpu.systems.runner
    return True


@case("_path_key")
def _():
    tree = {"params": {"w": np.zeros(2)}, "steps": (np.zeros(1), np.ones(1))}
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [list(checkpointing._path_key(path)) for path, _ in leaves]


@case("place_host_leaves")
def _():
    template = {"a": jax.numpy.zeros((2, 3)), "b": np.zeros(4, np.int32)}
    raw = {("a",): np.full((2, 3), 7.0, np.float32), ("b",): np.arange(4, dtype=np.int32)}
    tree, matched, reinit, keys = checkpointing.place_host_leaves(raw, template, step=3)
    return [
        matched, reinit, keys, isinstance(tree["a"], jax.Array),
        np.asarray(tree["a"]).tolist(), np.asarray(tree["b"]).tolist(),
    ]


@case("saved_digest_record")
def _():
    with tempfile.TemporaryDirectory() as store:
        empty = checkpointing.saved_digest_record(store)
        with open(os.path.join(store, checkpointing.DIGEST_SIDECAR), "w") as f:
            json.dump({"steps": {"12": {"params/w": "ab"}}}, f)
        record = checkpointing.saved_digest_record(store)
    return [empty, {str(k): v for k, v in record.items()}]


@case("checkpointer_from_config")
def _():
    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_ppo.yaml", []
    )
    assert not config.logger.checkpointing.save_model  # the shipped default
    return checkpointing.checkpointer_from_config(config, "ff_ppo") is None


@case("Checkpointer")
def _():
    # The block is real: what does build a store needs the library.
    with tempfile.TemporaryDirectory() as store:
        checkpointing.Checkpointer("model", rel_dir=store, checkpoint_uid="uid")


out["orbax_in_sys_modules"] = sorted(m for m in sys.modules if m.startswith("orbax."))
print(json.dumps(out), flush=True)
'''

EXPECTED = {
    "importers": {"ok": True},
    "_path_key": {"ok": [["params", "w"], ["steps", "0"], ["steps", "1"]]},
    "place_host_leaves": {
        "ok": [2, [], [], True, [[7.0, 7.0, 7.0], [7.0, 7.0, 7.0]], [0, 1, 2, 3]]
    },
    "saved_digest_record": {"ok": [{}, {"12": {"params/w": "ab"}}]},
    "checkpointer_from_config": {"ok": True},
}


@pytest.fixture(scope="module")
def child():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-c", CHILD, ROOT], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_pure_part_runs_where_importing_orbax_raises(child, name):
    assert child[name] == EXPECTED[name]


def test_building_a_store_is_what_needs_the_library(child):
    assert child["Checkpointer"]["raised"].startswith(("ImportError", "ModuleNotFoundError"))
    assert child["orbax_in_sys_modules"] == []
