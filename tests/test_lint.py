"""Per-rule fixture tests for the stoix_tpu.analysis static-analysis gate.

Structure (ISSUE 5 satellite): every registered rule — the migrated
F401/HYG/STX001-004 and the new JAX-aware STX005-009 — gets at least one
snippet that MUST flag and one near-miss that MUST NOT, replayed straight
from the rule's own `flag_snippets`/`clean_snippets` (so the fixtures ship
with the rule module and the docs stay honest). Targeted tests below pin the
trickier semantics per rule; the CLI tests prove the end-to-end contract
(exit 1 + rule id + line for a seeded violation; byte-identical shim).

The repo-wide clean gate lives in tests/test_analysis_clean.py.
"""

import json
import os
import subprocess
import sys

import pytest

from stoix_tpu.analysis import get_rule, get_rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ids(rule):
    return rule.id


# ---------------------------------------------------------------------------
# Registry-driven fixture replay: one flagging + one near-miss snippet per rule.


@pytest.mark.parametrize("rule", get_rules(), ids=_ids)
def test_rule_has_fixture_snippets(rule):
    if rule.check_file is None and not rule.flag_snippets:
        pytest.skip(f"{rule.id} is tree-scoped (dedicated tests below)")
    assert rule.flag_snippets, f"{rule.id} ships no must-flag fixture snippet"
    assert rule.clean_snippets, f"{rule.id} ships no near-miss fixture snippet"


@pytest.mark.parametrize("rule", get_rules(), ids=_ids)
def test_flag_snippets_flag(rule):
    if rule.check_file is None and not rule.flag_snippets:
        pytest.skip(f"{rule.id} is tree-scoped")
    for i, snippet in enumerate(rule.flag_snippets):
        findings = rule.run_on_source(snippet)
        assert any(f.rule in rule.finding_ids for f in findings), (
            f"{rule.id} flag_snippets[{i}] produced no {rule.id} finding: "
            f"{[(f.rule, f.line, f.message) for f in findings]}"
        )


@pytest.mark.parametrize("rule", get_rules(), ids=_ids)
def test_clean_snippets_stay_clean(rule):
    if rule.check_file is None and not rule.flag_snippets:
        pytest.skip(f"{rule.id} is tree-scoped")
    for i, snippet in enumerate(rule.clean_snippets):
        findings = [f for f in rule.run_on_source(snippet) if f.rule in rule.finding_ids]
        assert not findings, (
            f"{rule.id} clean_snippets[{i}] (a near-miss) flagged: "
            f"{[(f.rule, f.line, f.message) for f in findings]}"
        )


# ---------------------------------------------------------------------------
# Migrated-rule semantics (STX001-004), unchanged from the flat lint.py.


def test_stx001_catches_attribute_qualified_checkpointer_wait():
    rule = get_rule("STX001")
    source = (
        "def run():\n"
        "    self.checkpointer.wait()\n"
        "    setup.ckpt.wait()\n"
        "    lock.wait()\n"  # not a checkpointer: must NOT trip the gate
    )
    findings = rule.run_on_source(source, rel="stoix_tpu/systems/fake_system.py")
    assert len(findings) == 2, findings
    assert all("STX001" in f.message for f in findings)
    # Sebulba files own their sync points; out of scope.
    assert rule.run_on_source(source, rel="stoix_tpu/systems/ppo/sebulba/x.py") == []


def test_stx002_scope_and_allowlist():
    rule = get_rule("STX002")
    assert rule.run_on_source('print("x")\n', rel="stoix_tpu/utils/logger.py") == []
    assert rule.run_on_source('print("x")\n', rel="stoix_tpu/sweep.py") == []
    assert rule.run_on_source('print("x")\n', rel="scripts/whatever.py") == []
    assert len(rule.run_on_source('print("x")\n', rel="stoix_tpu/envs/foo.py")) == 1


def test_stx003_scope_and_allowlist():
    rule = get_rule("STX003")
    swallowed = "try:\n    x()\nexcept Exception:\n    pass\n"
    assert rule.run_on_source(swallowed, rel="stoix_tpu/resilience/faultinject.py") == []
    assert rule.run_on_source(swallowed, rel="tests/test_whatever.py") == []
    assert len(rule.run_on_source(swallowed, rel="stoix_tpu/envs/foo.py")) == 1


def test_stx004_keyed_and_bounded_forms_pass():
    rule = get_rule("STX004")
    # dict.get(key) — the canonical near-miss named in the issue.
    assert rule.run_on_source("v = d.get('key')\n") == []
    assert rule.run_on_source("q.get()\n", rel="tests/test_whatever.py") == []
    assert rule.run_on_source("q.get()\n", rel="scripts/tool.py") == []
    assert len(rule.run_on_source("q.get()\n")) == 1


# ---------------------------------------------------------------------------
# STX005 — PRNG discipline specifics.


def test_stx005_resplit_key_is_clean():
    # The issue's named near-miss: a re-split key is NOT reuse.
    rule = get_rule("STX005")
    source = (
        "import jax\n\n\ndef f(key):\n"
        "    key, sub = jax.random.split(key)\n"
        "    a = jax.random.normal(sub, (2,))\n"
        "    key, sub = jax.random.split(key)\n"
        "    b = jax.random.normal(sub, (2,))\n"
        "    return a + b\n"
    )
    assert rule.run_on_source(source) == []


def test_stx005_loop_carried_reuse_flags():
    rule = get_rule("STX005")
    source = (
        "import jax\n\n\ndef f(key, n):\n"
        "    out = []\n"
        "    for _ in range(n):\n"
        "        out.append(jax.random.normal(key, (2,)))\n"
        "    return out\n"
    )
    findings = rule.run_on_source(source)
    assert findings and all(f.rule == "STX005" for f in findings)


def test_stx005_reuse_reports_both_lines():
    rule = get_rule("STX005")
    source = (
        "import jax\n\n\ndef f(key):\n"
        "    a = jax.random.normal(key, (2,))\n"
        "    b = jax.random.uniform(key, (2,))\n"
        "    return a + b\n"
    )
    (finding,) = rule.run_on_source(source)
    assert finding.line == 6 and "line 5" in finding.message


def test_stx005_resplit_in_both_if_arms_is_clean():
    # Both arms rebind the key — the merged state must be reset, not the
    # pre-branch consumption record.
    rule = get_rule("STX005")
    source = (
        "import jax\n\n\ndef f(key, flag):\n"
        "    a = jax.random.normal(key, (2,))\n"
        "    if flag:\n"
        "        key, _ = jax.random.split(key)\n"
        "    else:\n"
        "        key, _ = jax.random.split(key)\n"
        "    b = jax.random.normal(key, (2,))\n"
        "    return a + b\n"
    )
    assert rule.run_on_source(source) == []


def test_noqa_rule_requires_reason_for_new_rule_codes():
    rule = get_rule("NOQA")
    (finding,) = rule.run_on_source("x = 1  # noqa: STX007\n")
    assert finding.line == 1 and "STX007" in finding.message
    assert rule.run_on_source("x = 1  # noqa: STX007 — single-host-only op\n") == []


def test_stx005_noqa_with_rule_id_suppresses():
    rule = get_rule("STX005")
    source = (
        "import jax\n\n\ndef f(key):\n"
        "    a = jax.random.normal(key, (2,))\n"
        "    b = jax.random.uniform(key, (2,))  # noqa: STX005 — intentional common-random-numbers\n"
        "    return a + b\n"
    )
    assert rule.run_on_source(source) == []


# ---------------------------------------------------------------------------
# STX006 — jit-reachability specifics.


def test_stx006_factory_returned_learner_is_reachable():
    # The get_learner_fn -> learner_fn -> shard_map idiom: a .item() buried
    # in the returned learner must be found.
    rule = get_rule("STX006")
    source = (
        "import jax\nfrom jax import shard_map\n\n\n"
        "def get_learner_fn(config):\n"
        "    def learner_fn(state):\n"
        "        return state.loss.item()\n"
        "    return learner_fn\n\n\n"
        "def setup(mesh, specs, config):\n"
        "    learn_per_shard = get_learner_fn(config)\n"
        "    return shard_map(learn_per_shard, mesh=mesh, in_specs=specs, out_specs=specs)\n"
    )
    findings = rule.run_on_source(source)
    assert [f.line for f in findings] == [7], findings


def test_stx005_np_random_is_not_key_consumption():
    # np.random draws take distribution PARAMS, not PRNG keys; reusing `mu`
    # across two np.random calls must not read as key reuse.
    rule = get_rule("STX005")
    source = (
        "import numpy as np\n\n\ndef f(mu, sigma):\n"
        "    a = np.random.normal(mu, sigma)\n"
        "    b = np.random.normal(mu, sigma)\n"
        "    return a + b\n"
    )
    assert rule.run_on_source(source) == []


def test_stx006_static_shape_cast_is_clean():
    # int(x.shape[0]) on a traced value is the standard static-shape idiom.
    rule = get_rule("STX006")
    source = (
        "import jax\n\n\n@jax.jit\ndef f(x):\n"
        "    n = int(x.shape[0])\n"
        "    return x.reshape(n, -1)\n"
    )
    assert rule.run_on_source(source) == []


def test_stx006_host_only_helper_is_not_flagged():
    rule = get_rule("STX006")
    source = (
        "import jax\nimport numpy as np\n\n\n"
        "def fetch_metrics(tree):\n"
        "    return {k: float(np.asarray(v).item()) for k, v in tree.items()}\n\n\n"
        "@jax.jit\ndef learn(state):\n"
        "    return state\n"
    )
    assert rule.run_on_source(source) == []


# ---------------------------------------------------------------------------
# STX007 — the acceptance-criterion scenario: a misspelled axis_name in a
# COPY of a real Anakin system file is caught, the original is clean.


def test_stx007_catches_misspelled_axis_in_anakin_copy():
    rule = get_rule("STX007")
    with open(os.path.join(REPO, "stoix_tpu", "systems", "ppo", "anakin", "ff_ppo.py")) as f:
        source = f.read()
    assert rule.run_on_source(source, rel="stoix_tpu/systems/ppo/anakin/_copy.py") == []
    target = 'jax.lax.pmean(actor_grads, axis_name="data")'
    assert target in source
    bad = source.replace(target, 'jax.lax.pmean(actor_grads, axis_name="dataa")', 1)
    findings = rule.run_on_source(bad, rel="stoix_tpu/systems/ppo/anakin/_copy.py")
    assert len(findings) == 1 and "'dataa'" in findings[0].message
    assert findings[0].line == source[: source.index(target)].count("\n") + 1


def test_stx007_matching_axis_name_is_clean():
    # The issue's named near-miss: a matching axis name must not flag.
    rule = get_rule("STX007")
    source = (
        "import jax\n\n\ndef make(step):\n"
        '    batched = jax.vmap(step, axis_name="inner")\n'
        "    def learner(x):\n"
        '        return jax.lax.pmean(x, axis_name="inner")\n'
        "    return learner, batched\n"
    )
    assert rule.run_on_source(source) == []


def test_stx007_checks_axis_names_tuples():
    rule = get_rule("STX007")
    source = (
        "from stoix_tpu.ops import running_statistics\n\n\ndef f(stats, batch):\n"
        "    return running_statistics.update(stats, batch, "
        'axis_names=("batch", "dtaa"))\n'
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and "'dtaa'" in findings[0].message


# ---------------------------------------------------------------------------
# STX008 — donation specifics.


def test_stx008_decorated_partial_jit_donation():
    rule = get_rule("STX008")
    source = (
        "import jax\nfrom functools import partial\n\n\n"
        "@partial(jax.jit, donate_argnums=(0,))\n"
        "def step(state, batch):\n"
        "    return state\n\n\n"
        "def run(state, batch):\n"
        "    new = step(state, batch)\n"
        "    return new, state.loss\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and findings[0].line == 12


def test_stx008_dynamic_donate_kwargs_kill_switch_resolves():
    # PR 5's documented blind spot, closed this PR: the **donate kill-switch
    # pattern resolves through the dict-literal assignment, taking the
    # DONATING branch (donation-on must be safe; off is the degraded mode).
    rule = get_rule("STX008")
    source = (
        "import jax, os\n\n"
        "donate = {} if os.environ.get('NO_DONATE') else {'donate_argnums': (0,)}\n"
        "step = jax.jit(update, **donate)\n\n\n"
        "def run(state):\n"
        "    out = step(state)\n"
        "    return out, state\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and findings[0].line == 9, findings


def test_stx008_donate_argnames_maps_to_positional_callsite():
    # donate_argnames resolves through the wrapped signature, so a POSITIONAL
    # read-after-donate is caught; the rebind idiom stays clean.
    rule = get_rule("STX008")
    source = (
        "import jax\n\n\ndef update(state, batch):\n"
        "    return state\n\n\n"
        'step = jax.jit(update, donate_argnames=("state",))\n\n\n'
        "def run(state, batch):\n"
        "    out = step(state, batch)\n"
        "    return out, state.loss\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and findings[0].line == 13, findings


def test_stx008_keyword_callsite_of_donated_position_is_tracked():
    # donate_argnums cross-maps to the parameter NAME, so passing the donated
    # argument by keyword is tracked too.
    rule = get_rule("STX008")
    source = (
        "import jax\n\n\ndef update(state, batch):\n"
        "    return state\n\n\n"
        "step = jax.jit(update, donate_argnums=(0,))\n\n\n"
        "def run(state, batch):\n"
        "    out = step(state=state, batch=batch)\n"
        "    return out, state.loss\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and findings[0].line == 13, findings


# ---------------------------------------------------------------------------
# STX010 — the acceptance-criterion scenario: an axis renamed in ONE P(...)
# of a copy of the real Anakin PPO file is caught at the exact line, and the
# unmodified copy stays clean (mirrors the STX007 misspelled-axis test).


def test_stx010_catches_seeded_misshard_in_ff_ppo_copy():
    rule = get_rule("STX010")
    with open(os.path.join(REPO, "stoix_tpu", "systems", "ppo", "anakin", "ff_ppo.py")) as f:
        source = f.read()
    rel = "stoix_tpu/systems/ppo/anakin/_misshard_copy.py"
    assert rule.run_on_source(source, rel=rel) == []
    target = 'key=P("data"),'
    assert target in source
    bad = source.replace(target, 'key=P("dtaa"),', 1)
    findings = rule.run_on_source(bad, rel=rel)
    assert len(findings) == 1 and findings[0].rule == "STX010"
    assert "'dtaa'" in findings[0].message
    assert findings[0].line == source[: source.index(target)].count("\n") + 1
    assert findings[0].path == rel.replace("/", os.sep)


def test_stx010_mesh_local_resolution_beats_universe():
    # "model" exists in the repo universe, but NOT on the mesh this spec
    # statically flows with — the mesh-local check STX007 cannot do.
    rule = get_rule("STX010")
    source = (
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n\n\n"
        "def place(devices, params):\n"
        '    learner_mesh = Mesh(np.array(devices), ("data",))\n'
        '    return NamedSharding(learner_mesh, P("model"))\n'
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and "learner_mesh" in findings[0].message


def test_stx010_spec_arity_vs_literal_shape_rank():
    rule = get_rule("STX010")
    source = (
        "import jax\nfrom jax.sharding import NamedSharding, PartitionSpec as P\n\n\n"
        "def assemble(mesh, shards):\n"
        "    return jax.make_array_from_single_device_arrays(\n"
        '        (8,), NamedSharding(mesh, P("data", None)), shards\n'
        "    )\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and "rank 1" in findings[0].message


def test_stx010_parameter_mesh_does_not_resolve_to_other_scopes_binding():
    # A `mesh` PARAMETER is the caller's mesh — it must not resolve to a
    # same-named local binding in ANOTHER function (universe fallback, where
    # "model" is valid), or the 37-file sharding refactor lints wrong code.
    rule = get_rule("STX010")
    source = (
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n\n\n"
        "def build_learner(devices):\n"
        '    mesh = Mesh(np.array(devices), ("data",))\n'
        "    return mesh\n\n\n"
        "def place(mesh, params):\n"
        '    return NamedSharding(mesh, P("model"))\n'
    )
    assert rule.run_on_source(source) == []


def test_stx010_rebound_mesh_name_falls_back_to_universe():
    # A same-scope rebind through a helper (`mesh = widen(mesh)`) makes the
    # name's axes unknowable — the stale ctor binding must NOT win (universe
    # fallback, where "model" is valid).
    rule = get_rule("STX010")
    source = (
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n\n\n"
        "def place(devs, widen):\n"
        '    mesh = Mesh(devs, ("data",))\n'
        "    mesh = widen(mesh)\n"
        '    return NamedSharding(mesh, P("model"))\n'
    )
    assert rule.run_on_source(source) == []


def test_stx010_other_scope_nonctor_binding_poisons_mesh_name():
    # `mesh` bound by a ctor in ONE function and by an opaque helper call in
    # ANOTHER: the second function's use must not resolve to the first
    # function's axes (universe fallback), or valid code fails the gate.
    rule = get_rule("STX010")
    source = (
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n\n\n"
        "def build_data(devices):\n"
        '    mesh = Mesh(np.array(devices), ("data",))\n'
        '    return NamedSharding(mesh, P("data"))\n\n\n'
        "def place(devices, make_model_mesh):\n"
        "    mesh = make_model_mesh(devices)\n"
        '    return NamedSharding(mesh, P("model"))\n'
    )
    assert rule.run_on_source(source) == []


def test_stx010_parameter_spec_does_not_resolve_to_other_scopes_binding():
    # A `spec` PARAMETER is the caller's spec — it must not resolve to a
    # same-named local P(...) in ANOTHER function (opaque leaf), exactly the
    # discipline mesh names already get.
    rule = get_rule("STX010")
    source = (
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n\n\n"
        "def model_spec():\n"
        '    spec = P("model")\n'
        "    return spec\n\n\n"
        "def place(devices, spec):\n"
        '    m = Mesh(np.array(devices), ("data",))\n'
        "    return NamedSharding(m, spec)\n"
    )
    assert rule.run_on_source(source) == []


def test_stx010_rebound_spec_name_is_ambiguous():
    # A same-scope rebind through a helper (`spec = widen(spec)`) — and a
    # second P(...) literal binding of the same name — make the name's value
    # unknowable: the stale literal must NOT win (opaque leaf, no finding).
    rule = get_rule("STX010")
    source = (
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n\n\n"
        "def place(devs, widen):\n"
        '    spec = P("model")\n'
        "    spec = widen(spec)\n"
        '    return NamedSharding(Mesh(devs, ("data",)), spec)\n\n\n'
        "def elsewhere(devs):\n"
        '    spec = P("data")\n'
        '    return NamedSharding(Mesh(devs, ("data",)), spec)\n'
    )
    assert rule.run_on_source(source) == []


def test_stx010_single_spec_binding_still_resolves():
    # The guard is rebind-poisoning, not a lobotomy: a name bound ONCE to a
    # P(...) literal still resolves and still catches the misshard.
    rule = get_rule("STX010")
    source = (
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n\n\n"
        "def place(devs):\n"
        '    spec = P("model")\n'
        '    return NamedSharding(Mesh(devs, ("data",)), spec)\n'
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and "'model'" in findings[0].message


def test_stx010_variable_axis_slots_are_axis_generic():
    # parallel/topology-style library code passes axes as variables: skipped
    # per slot, never guessed.
    rule = get_rule("STX010")
    source = (
        "from jax.sharding import NamedSharding, PartitionSpec as P\n\n\n"
        "def seq_sharding(mesh, axis):\n"
        "    return NamedSharding(mesh, P(None, axis))\n"
    )
    assert rule.run_on_source(source) == []


# ---------------------------------------------------------------------------
# STX011 — shard_map contract specifics.


def test_stx011_partial_bound_args_drop_out_of_arity():
    # functools.partial binds positionals: 1 spec into partial(f, cfg) where
    # f takes (cfg, batch) is satisfiable and must NOT flag.
    rule = get_rule("STX011")
    source = (
        "from functools import partial\n"
        "from jax.sharding import PartitionSpec as P\n"
        "from jax import shard_map\n\n\n"
        "def per_shard(cfg, batch):\n"
        "    return batch\n\n\n"
        "def build(mesh, cfg):\n"
        "    return shard_map(partial(per_shard, cfg), mesh=mesh,\n"
        '                     in_specs=(P("data"),), out_specs=P("data"))\n'
    )
    assert rule.run_on_source(source) == []


def test_stx011_literal_axis_names_tuple_is_not_a_wildcard():
    # An all-literal axis_names=("model",) tuple contributes its literals but
    # must NOT wildcard-suppress the check for OTHER axes: "data" is sharded
    # in, never reduced, and claimed replicated -> flags.
    rule = get_rule("STX011")
    source = (
        "from jax.sharding import PartitionSpec as P\n"
        "from jax import shard_map\n"
        "from stoix_tpu.resilience import guards\n\n\n"
        "def per_shard(batch):\n"
        '    out, _ = guards.guard_update("skip", new=batch, old=batch,\n'
        '                                 axis_names=("model",))\n'
        "    return out\n\n\n"
        "def build(mesh):\n"
        "    return shard_map(per_shard, mesh=mesh,\n"
        '                     in_specs=(P("data"),), out_specs=P())\n'
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and "'data'" in findings[0].message


def test_stx011_variable_axis_name_suppresses_replication_check():
    # A collective whose axis rides a VARIABLE may reduce over any axis:
    # axis-generic library code (ring_attention) must not false-positive.
    rule = get_rule("STX011")
    source = (
        "import jax\nfrom jax.sharding import PartitionSpec as P\n"
        "from jax import shard_map\n\n\n"
        "def make(axis):\n"
        "    def per_shard(batch):\n"
        "        return jax.lax.pmean(batch, axis_name=axis)\n\n"
        "    def build(mesh):\n"
        "        return shard_map(per_shard, mesh=mesh,\n"
        '                         in_specs=(P("data"),), out_specs=P())\n'
        "    return build\n"
    )
    assert rule.run_on_source(source) == []


# ---------------------------------------------------------------------------
# STX012 — recompile-hazard specifics.


def test_stx012_static_argnames_cross_maps_to_positional_callsite():
    # static_argnames resolves to positions through the wrapped signature, so
    # a loop variable passed POSITIONALLY at that slot is still caught.
    rule = get_rule("STX012")
    source = (
        "import jax\n\n\ndef update(state, width):\n"
        "    return state\n\n\n"
        'step = jax.jit(update, static_argnames=("width",))\n\n\n'
        "def run(state, n):\n"
        "    for i in range(n):\n"
        "        state = step(state, i)\n"
        "    return state\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and findings[0].line == 13
    assert "loop variable" in findings[0].message


def test_stx012_jit_in_setup_called_in_loop_is_clean():
    rule = get_rule("STX012")
    source = (
        "import jax\n\n\ndef run(update, state, n):\n"
        "    step = jax.jit(update)\n"
        "    for _ in range(n):\n"
        "        state = step(state)\n"
        "    return state\n"
    )
    assert rule.run_on_source(source) == []


def test_stx012_out_of_range_static_argnums_names_the_bound():
    rule = get_rule("STX012")
    source = (
        "import jax\n\n\ndef update(state):\n"
        "    return state\n\n\nstep = jax.jit(update, static_argnums=(2,))\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and "out of range" in findings[0].message


# ---------------------------------------------------------------------------
# STX013 — host-divergence specifics.


def test_stx013_rebind_from_untainted_expression_clears_taint():
    rule = get_rule("STX013")
    source = (
        "import jax\nimport time\n\nstep = jax.jit(update)\n\n\n"
        "def run(state):\n"
        "    t = time.time()\n"
        "    t = 0.0\n"
        "    return step(state, t)\n"
    )
    assert rule.run_on_source(source) == []


def test_stx013_module_scope_taint_reaches_function_scope_sink():
    rule = get_rule("STX013")
    source = (
        "import jax\nimport os\n\nstep = jax.jit(update)\n"
        'DEBUG_SCALE = float(os.environ.get("SCALE", "1.0"))\n\n\n'
        "def run(state):\n"
        "    return step(state, DEBUG_SCALE)\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and "os.environ" in findings[0].message
    assert findings[0].line == 9


def test_stx013_parameter_shadows_module_taint():
    # A function parameter named like a tainted module global is a FRESH
    # caller-supplied value — must not inherit the module-scope taint.
    rule = get_rule("STX013")
    source = (
        "import jax\nimport time\n\nstep = jax.jit(update)\n"
        "T0 = time.perf_counter()\n\n\n"
        "def run(state, T0):\n"
        "    return step(state, T0)\n"
    )
    assert rule.run_on_source(source) == []


def test_stx012_vararg_absorbs_static_positions():
    # static_argnums may index into *args — no out-of-range claim.
    rule = get_rule("STX012")
    source = (
        "import jax\n\n\ndef update(state, *scales):\n"
        "    return state\n\n\nstep = jax.jit(update, static_argnums=(2,))\n"
    )
    assert rule.run_on_source(source) == []


def test_stx013_else_branch_rebind_does_not_launder_if_branch_taint():
    # Branch states join as a union: the config-toggle pattern (env var
    # reaching a jitted call on the debug path only) must still flag.
    rule = get_rule("STX013")
    source = (
        "import jax\nimport os\n\nstep = jax.jit(update)\n\n\n"
        "def run(state, debug):\n"
        "    if debug:\n"
        '        scale = float(os.environ.get("S", "1"))\n'
        "    else:\n"
        "        scale = 1.0\n"
        "    return step(state, scale)\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and findings[0].line == 12, findings


def test_stx013_with_open_binding_carries_taint():
    # `with open(p) as f:` is the dominant filesystem-read idiom; reads of
    # `f` must carry the taint to the sink.
    rule = get_rule("STX013")
    source = (
        "import jax\n\nstep = jax.jit(update)\n\n\n"
        "def run(state, path):\n"
        "    with open(path) as f:\n"
        "        cfg = f.read()\n"
        "    return step(state, float(cfg))\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and "open()" in findings[0].message, findings


def test_stx012_while_counter_and_body_derived_are_loop_varying():
    rule = get_rule("STX012")
    source = (
        "import jax\n\nstep = jax.jit(update, static_argnums=(1,))\n\n\n"
        "def run(state, n):\n"
        "    i = 0\n"
        "    while i < n:\n"
        "        state = step(state, i)\n"
        "        i += 1\n"
        "    return state\n\n\n"
        "def run2(state, n):\n"
        "    for i in range(n):\n"
        "        width = i * 2\n"
        "        state = step(state, width)\n"
        "    return state\n"
    )
    findings = rule.run_on_source(source)
    assert [f.line for f in findings] == [9, 17], findings


def test_stx012_loop_invariant_constant_at_static_position_is_clean():
    # A name assigned a loop-INVARIANT value inside the body compiles exactly
    # once — flagging it would fail correct code; a value derived from it AND
    # the counter is still caught (transitive fixpoint).
    rule = get_rule("STX012")
    source = (
        "import jax\n\nstep = jax.jit(update, static_argnums=(1,))\n\n\n"
        "def run(state, n):\n"
        "    for _ in range(n):\n"
        "        width = 64\n"
        "        state = step(state, width)\n"
        "    return state\n\n\n"
        "def run2(state, n):\n"
        "    for i in range(n):\n"
        "        base = 64\n"
        "        width = base + i\n"
        "        state = step(state, width)\n"
        "    return state\n\n\n"
        "def run3(state, n):\n"
        "    for i in range(n):\n"
        "        w, block = i, 64\n"
        "        state = step(state, block)\n"
        "    return state\n"
    )
    findings = rule.run_on_source(source)
    # run3: tuple-unpack pairs element-wise — `block` is loop-invariant even
    # though its unpack sibling `w` derives from the counter.
    assert [f.line for f in findings] == [17], findings


def test_stx013_jax_random_import_alias_is_not_stdlib_random():
    # `from jax import random` binds KEYED jax.random to the bare name the
    # stdlib heuristic matches — the rule's documented exemption must hold.
    rule = get_rule("STX013")
    source = (
        "import jax\nfrom jax import random\n\nstep = jax.jit(update)\n\n\n"
        "def run(state, key):\n"
        "    key, sub = random.split(key)\n"
        "    return step(state, sub)\n"
    )
    assert rule.run_on_source(source, rel="stoix_tpu/systems/x.py") == []
    # Without the jax import, the SAME source is stdlib random: flagged.
    bad = source.replace("from jax import random", "import random")
    findings = rule.run_on_source(bad, rel="stoix_tpu/systems/x.py")
    assert len(findings) == 1 and "random.split()" in findings[0].message


def test_stx013_seeded_default_rng_is_deterministic():
    rule = get_rule("STX013")
    source = (
        "import jax\nimport numpy as np\n\nstep = jax.jit(update)\n\n\n"
        "def run(state, config):\n"
        "    rng = np.random.default_rng(int(config.arch.seed))\n"
        "    return step(state, rng.normal())\n"
    )
    assert rule.run_on_source(source, rel="stoix_tpu/systems/x.py") == []
    # An UNSEEDED generator draws per-host entropy: still flagged.
    bad = source.replace("default_rng(int(config.arch.seed))", "default_rng()")
    findings = rule.run_on_source(bad, rel="stoix_tpu/systems/x.py")
    assert len(findings) == 1 and "default_rng" in findings[0].message


def test_stx013_collective_helper_is_a_sink():
    rule = get_rule("STX013")
    source = (
        "import time\n\nfrom stoix_tpu.parallel import fetch_global\n\n\n"
        "def snapshot(tree):\n"
        "    stamp = time.time()\n"
        "    return fetch_global(tree, stamp)\n"
    )
    findings = rule.run_on_source(source)
    assert len(findings) == 1 and "time.time()" in findings[0].message


# ---------------------------------------------------------------------------
# STX009 — config↔code cross-check on a synthetic repo.


def _make_stx9_repo(tmp_path, code: str, yaml_text: str):
    (tmp_path / "stoix_tpu" / "configs" / "system").mkdir(parents=True)
    (tmp_path / "stoix_tpu" / "systems").mkdir(parents=True)
    (tmp_path / "stoix_tpu" / "configs" / "system" / "probe.yaml").write_text(yaml_text)
    code_path = tmp_path / "stoix_tpu" / "systems" / "probe_system.py"
    code_path.write_text(code)
    import ast

    from stoix_tpu.analysis import FileContext, TreeContext

    ctx = FileContext(
        repo=str(tmp_path),
        path=str(code_path),
        rel=os.path.join("stoix_tpu", "systems", "probe_system.py"),
        source=code,
        lines=code.splitlines(),
        tree=ast.parse(code),
    )
    return TreeContext(repo=str(tmp_path), files=[ctx])


def test_stx009_flags_typoed_read_and_dead_key(tmp_path):
    rule = get_rule("STX009")
    tree_ctx = _make_stx9_repo(
        tmp_path,
        code=(
            "def run_experiment(config):\n"
            "    lr = config.system.actor_lr\n"
            "    typo = config.system.gama\n"
            "    return lr, typo\n"
        ),
        yaml_text="actor_lr: 3.0e-4\ngamma: 0.99\nnever_read_knob: 7\n",
    )
    findings = rule.check_tree(rule, tree_ctx)
    unknown = [f for f in findings if "system.gama" in f.message]
    dead = [f for f in findings if "never_read_knob" in f.message]
    assert len(unknown) == 1 and unknown[0].line == 3
    assert unknown[0].path.endswith("probe_system.py")
    # gamma IS dead here (never read) — but only never_read_knob and gamma
    # may be reported, never the read actor_lr.
    assert dead and not any("actor_lr" in f.message for f in findings)


def test_stx009_computed_fields_and_tolerant_reads_are_known(tmp_path):
    rule = get_rule("STX009")
    tree_ctx = _make_stx9_repo(
        tmp_path,
        code=(
            "def run_experiment(config):\n"
            "    config.system.action_dim = 6\n"
            "    a = config.system.action_dim\n"  # computed field: not a typo
            "    b = config.system.get('warmup', 0)\n"  # tolerant: never unknown
            "    c = config.system.gamma\n"
            "    pf = (config.get('system') or {}).get('nested') or {}\n"
            "    d = pf.get('knob', 1.0)\n"  # dict-style subtree composition
            "    return a, b, c, d\n"
        ),
        yaml_text="gamma: 0.99\nnested:\n  knob: 2.0\n",
    )
    findings = rule.check_tree(rule, tree_ctx)
    assert findings == [], [(f.path, f.line, f.message) for f in findings]


# ---------------------------------------------------------------------------
# CLI contract: exit codes, rule naming, JSON shape, shim equivalence.


def _run_cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "stoix_tpu.analysis", *args],
        capture_output=True,
        text=True,
        cwd=cwd,
    )


def test_cli_seeded_violation_exits_1_naming_rule_and_line(tmp_path):
    # Acceptance: seeding a documented violation snippet into a scratch file
    # makes the CLI exit 1 naming the correct rule id and line.
    rule = get_rule("STX005")
    scratch = os.path.join(REPO, "stoix_tpu", "_stx_fixture_scratch_probe.py")
    with open(scratch, "w") as f:
        f.write(rule.flag_snippets[0])
    try:
        proc = _run_cli(
            ["--select", "STX005", "stoix_tpu/_stx_fixture_scratch_probe.py"]
        )
    finally:
        os.remove(scratch)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "STX005" in proc.stdout
    assert "_stx_fixture_scratch_probe.py:6" in proc.stdout


def test_cli_json_format_shape():
    rule = get_rule("STX006")
    scratch = os.path.join(REPO, "stoix_tpu", "_stx_fixture_scratch_probe.py")
    with open(scratch, "w") as f:
        f.write(rule.flag_snippets[0])
    try:
        proc = _run_cli(
            [
                "--select",
                "STX006",
                "--format",
                "json",
                "stoix_tpu/_stx_fixture_scratch_probe.py",
            ]
        )
    finally:
        os.remove(scratch)
    assert proc.returncode == 1
    findings = json.loads(proc.stdout)
    assert isinstance(findings, list) and findings
    for f in findings:
        assert set(f) == {"rule", "path", "line", "message", "severity"}
    assert findings[0]["rule"] == "STX006"
    assert isinstance(findings[0]["line"], int)


def test_cli_github_format_annotation_lines():
    # One ::error workflow-command per finding, anchored to the PR diff.
    rule = get_rule("STX005")
    scratch = os.path.join(REPO, "stoix_tpu", "_stx_fixture_scratch_probe.py")
    with open(scratch, "w") as f:
        f.write(rule.flag_snippets[0])
    try:
        proc = _run_cli(
            [
                "--select",
                "STX005",
                "--format",
                "github",
                "stoix_tpu/_stx_fixture_scratch_probe.py",
            ]
        )
    finally:
        os.remove(scratch)
    assert proc.returncode == 1
    annotations = [l for l in proc.stdout.splitlines() if l.startswith("::")]
    assert annotations, proc.stdout
    assert annotations[0].startswith(
        "::error file=stoix_tpu/_stx_fixture_scratch_probe.py,line="
    )
    assert "title=STX005" in annotations[0]
    # The summary line rides along for the action log; not an annotation.
    assert proc.stdout.splitlines()[-1].startswith("[lint] ")


def test_cli_changed_only_scans_untracked_violation():
    # An UNTRACKED scratch violation is part of the git-changed set, so
    # --changed-only must find it; tree-scoped rules are skipped (a partial
    # file set would fabricate dead config keys), which --select sidesteps.
    rule = get_rule("STX005")
    scratch = os.path.join(REPO, "stoix_tpu", "_stx_fixture_scratch_probe.py")
    with open(scratch, "w") as f:
        f.write(rule.flag_snippets[0])
    try:
        proc = _run_cli(["--select", "STX005", "--changed-only"])
    finally:
        os.remove(scratch)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "_stx_fixture_scratch_probe.py" in proc.stdout


def test_cli_changed_only_rejects_explicit_paths():
    proc = _run_cli(["--changed-only", "stoix_tpu/analysis"])
    assert proc.returncode == 2
    assert "mutually exclusive" in proc.stderr


def test_cli_changed_only_with_selected_tree_rule_exits_2(monkeypatch, capsys):
    # --select STX009 --changed-only would silently never run the one rule
    # the user asked for (tree-scoped rules are skipped on a partial file
    # set) — a permanent green no-op in CI. Must refuse, like the explicit
    # paths conflict.
    from stoix_tpu.analysis import __main__ as cli
    from stoix_tpu.analysis import core

    monkeypatch.setattr(
        core, "changed_paths", lambda: [os.path.join("stoix_tpu", "launcher.py")]
    )
    rc = cli.main(["--changed-only", "--select", "STX009", "--format", "json"])
    out = capsys.readouterr()
    assert rc == 2
    assert "STX009" in out.err and "tree-scoped" in out.err


@pytest.mark.slow
def test_cli_changed_only_clean_tree_falls_back_to_full_scan(monkeypatch, capsys):
    # Slow lane (tier-1 budget, PR 19): a full-repo analysis scan (~6s);
    # the changed-only fast path and its refusals stay not-slow above.
    # The CI/prolog case: the bad change is already COMMITTED, so the
    # changed set is empty — a vacuous 0-file pass would be a fake gate.
    from stoix_tpu.analysis import __main__ as cli
    from stoix_tpu.analysis import core

    monkeypatch.setattr(core, "changed_paths", lambda: [])
    rc = cli.main(["--changed-only", "--select", "STX010", "--format", "json"])
    out = capsys.readouterr()
    assert rc == 0
    assert "clean work tree, running the full scan" in out.err
    assert json.loads(out.out) == []


def test_cli_select_unknown_rule_exits_2():
    proc = _run_cli(["--select", "STX999", "scripts"])
    assert proc.returncode == 2


def test_cli_ignore_unknown_rule_exits_2():
    # A typo'd --ignore must not silently waive nothing.
    proc = _run_cli(["--ignore", "STX999", "scripts"])
    assert proc.returncode == 2


@pytest.mark.slow
def test_shim_output_is_byte_identical():
    # Slow lane (tier-1 budget, PR 19): two analysis subprocesses (~10s);
    # the shim's exit-code parity is also covered by
    # test_analysis_clean.py's not-slow module-CLI gate.
    # scripts/lint.py must keep every existing invocation working: same
    # stdout, same exit code as the module CLI (here on a small subtree).
    args = ["stoix_tpu/analysis", "--skip-external"]
    via_module = _run_cli(args)
    via_shim = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"), *args],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert via_shim.returncode == via_module.returncode
    assert via_shim.stdout == via_module.stdout


def test_list_rules_catalog():
    proc = _run_cli(["--list-rules"])
    assert proc.returncode == 0
    for rule_id in ("F401", "STX001", "STX005", "STX009"):
        assert rule_id in proc.stdout


# ---------------------------------------------------------------------------
# launcher.py --preflight-only runs the analysis gate (satellite): the report
# grows a static-analysis section, exit semantics unchanged otherwise.


@pytest.mark.slow
def test_launcher_preflight_includes_static_analysis_section(monkeypatch, capsys):
    # Slow lane (tier-1 budget, PR 19): the preflight report embeds a
    # full-repo analysis scan (~28s); the preflight report shape itself is
    # pinned not-slow in test_threadmodel.py's empty-model preflight test.
    from stoix_tpu import launcher
    from stoix_tpu.resilience import preflight

    def fake_run_preflight(configs=None, settings=None):
        report = preflight.PreflightReport()
        report.add("backend_probe", "pass", "stubbed — no subprocess in unit test")
        return report

    monkeypatch.setattr(preflight, "run_preflight", fake_run_preflight)
    rc = launcher.run_preflight_only([])
    out = capsys.readouterr().out
    assert rc == 0
    assert "static-analysis" in out and "[PASS]" in out


def test_launcher_preflight_fails_on_lint_finding(monkeypatch, capsys):
    from stoix_tpu import analysis, launcher
    from stoix_tpu.resilience import preflight

    def fake_run_preflight(configs=None, settings=None):
        report = preflight.PreflightReport()
        report.add("backend_probe", "pass", "stubbed")
        return report

    def fake_run_paths(paths=None, select=None, ignore=None, repo=None, with_tree_rules=True):
        finding = analysis.Finding(
            "STX007", "stoix_tpu/systems/x.py", 42, "collective axis name 'dataa' ... (STX007)"
        )
        return [finding], 1

    monkeypatch.setattr(preflight, "run_preflight", fake_run_preflight)
    monkeypatch.setattr(analysis, "run_paths", fake_run_paths)
    rc = launcher.run_preflight_only([])
    out = capsys.readouterr().out
    assert rc == 1
    assert "static-analysis" in out and "STX007" in out


def test_launcher_preflight_changed_only_passes_git_selection(monkeypatch, capsys):
    # --changed-only routes the git-diff selection into the lint stage (tree
    # rules off) and the report names the narrowed scope.
    from stoix_tpu import analysis, launcher
    from stoix_tpu.resilience import preflight

    def fake_run_preflight(configs=None, settings=None):
        report = preflight.PreflightReport()
        report.add("backend_probe", "pass", "stubbed")
        return report

    seen = {}

    def fake_run_paths(paths=None, select=None, ignore=None, repo=None, with_tree_rules=True):
        seen["paths"] = paths
        seen["with_tree_rules"] = with_tree_rules
        return [], len(paths or [])

    monkeypatch.setattr(preflight, "run_preflight", fake_run_preflight)
    monkeypatch.setattr(analysis, "run_paths", fake_run_paths)
    monkeypatch.setattr(analysis, "changed_paths", lambda: ["stoix_tpu/launcher.py"])
    rc = launcher.run_preflight_only([], changed_only=True)
    out = capsys.readouterr().out
    assert rc == 0
    assert seen["paths"] == ["stoix_tpu/launcher.py"]
    assert seen["with_tree_rules"] is False
    assert "changed files clean" in out


def test_launcher_changed_only_without_preflight_only_is_rejected():
    # Silently ignoring --changed-only would fake a lint gate on --submit.
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "stoix_tpu.launcher",
            "--systems",
            "stoix_tpu.systems.ppo.anakin.ff_ppo",
            "--envs",
            "cartpole",
            "--changed-only",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert proc.returncode == 2
    assert "--changed-only requires --preflight-only" in proc.stderr
