"""MeshRoles — ONE description of which devices play which role.

Before this module, three subsystems each invented their own device
bookkeeping:

  * Anakin (`systems/runner.py`) built a global mesh straight from
    `arch.mesh` and implicitly ran every role (act / learn / evaluate) on
    every device;
  * Sebulba (two host loops then; `sebulba/runner.py` now) indexed
    `jax.devices()` with `arch.actor.device_ids` /
    `arch.learner.device_ids` / `arch.evaluator_device_id` and hand-rolled
    the learner mesh;
  * serve (`serve/server.py`) silently used whatever jax's default device
    was.

`MeshRoles` replaces all three: it is constructed ONCE from `arch.mesh` +
`arch.roles` (with back-compat derivation from the legacy Sebulba keys and
the architecture name when `arch.roles` is absent), validated as a whole
(ids in range, act/learn either colocated or disjoint — never a partial
overlap), and consumed by the Anakin runner, the Sebulba device split, the
replay service's data axis (via the learn mesh), the serve path, and the
population runner (`stoix_tpu/population`, whose learn role owns the
("pop", "data") mesh).

Config shape (docs/DESIGN.md §2.11):

    arch:
      mesh: {data: -1}          # axes of the LEARN role's mesh
      roles: ~                  # ~ = derive from architecture_name + legacy
                                # keys; or an explicit mapping:
      # roles:
      #   act:      {device_ids: [0]}
      #   learn:    {device_ids: [1, 2, 3], mesh: {data: -1}}
      #   evaluate: {device_ids: [0]}
      #   serve:    {device_ids: [0]}

Role semantics: `act` and `learn` are the PRIMARY roles — they must either
be colocated (identical device sets, the Anakin/population shape) or
disjoint (the Sebulba split); a partial overlap is always a config bug.
`evaluate` and `serve` are rider roles that may alias any device.

The resolution half (`resolve_assignments`) is deliberately jax-free so
`resilience/preflight.py` can validate a split against the PROBED device
count without touching jax in the parent process; `MeshRoles` materializes
actual `jax.Device` objects and meshes lazily.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

ROLE_ACT = "act"
ROLE_LEARN = "learn"
ROLE_EVALUATE = "evaluate"
ROLE_SERVE = "serve"
ROLE_NAMES = (ROLE_ACT, ROLE_LEARN, ROLE_EVALUATE, ROLE_SERVE)

# Primary roles partition the compute; rider roles may alias any device.
PRIMARY_ROLES = (ROLE_ACT, ROLE_LEARN)


class MeshRolesError(ValueError):
    """A role assignment that cannot be satisfied; carries ALL findings."""

    def __init__(self, findings: Sequence[str]):
        self.findings = list(findings)
        super().__init__(
            "mesh-role assignment invalid:\n  - " + "\n  - ".join(self.findings)
        )


class RoleAssignment(NamedTuple):
    """One role's share of the job: which device ids it owns (None = all
    devices in the job) and which mesh axes its programs run over."""

    role: str
    device_ids: Optional[Tuple[int, ...]]  # None = every device
    axes: Dict[str, int]

    def resolved_ids(self, device_count: int) -> Tuple[int, ...]:
        if self.device_ids is None:
            return tuple(range(device_count))
        return self.device_ids


def _as_id_tuple(raw: Any) -> Optional[Tuple[int, ...]]:
    if raw is None:
        return None
    return tuple(int(i) for i in raw)


def resolve_assignments(
    config: Any, device_count: Optional[int] = None
) -> Dict[str, RoleAssignment]:
    """Resolve `arch.roles` (or the legacy per-architecture keys) into role
    assignments, validating the partition invariants. Pure host logic — no
    jax import — so preflight can run it against the probed device count.

    Raises MeshRolesError listing EVERY finding at once (the preflight
    discipline: one run reports the whole config's problems).
    """
    arch = config.get("arch") or {}
    mesh_axes = dict(arch.get("mesh") or {"data": -1})
    arch_name = str(arch.get("architecture_name", "anakin"))
    explicit = arch.get("roles") or None

    findings: List[str] = []
    assignments: Dict[str, RoleAssignment] = {}

    if explicit:
        for role, spec in dict(explicit).items():
            if role not in ROLE_NAMES:
                findings.append(
                    f"arch.roles names unknown role '{role}' "
                    f"(known: {', '.join(ROLE_NAMES)})"
                )
                continue
            spec = spec or {}
            axes = dict(spec.get("mesh") or {})
            if role == ROLE_LEARN and not axes:
                axes = dict(mesh_axes)
            assignments[role] = RoleAssignment(
                role, _as_id_tuple(spec.get("device_ids")), axes
            )
        if ROLE_LEARN not in assignments:
            findings.append("arch.roles must assign the 'learn' role")
    elif arch_name == "sebulba":
        actor_ids = _as_id_tuple((arch.get("actor") or {}).get("device_ids")) or ()
        learner_ids = _as_id_tuple((arch.get("learner") or {}).get("device_ids")) or ()
        eval_id = int(arch.get("evaluator_device_id", 0))
        if not actor_ids or not learner_ids:
            findings.append(
                "arch.actor.device_ids and arch.learner.device_ids must both "
                "be non-empty"
            )
        assignments[ROLE_ACT] = RoleAssignment(ROLE_ACT, actor_ids, {})
        assignments[ROLE_LEARN] = RoleAssignment(
            ROLE_LEARN, learner_ids, {"data": -1}
        )
        assignments[ROLE_EVALUATE] = RoleAssignment(
            ROLE_EVALUATE, (eval_id,), {"data": 1}
        )
    elif arch_name == "serve":
        # Serving owns one device by default (the pre-MeshRoles behavior:
        # jax's default device, which is device 0).
        assignments[ROLE_SERVE] = RoleAssignment(ROLE_SERVE, (0,), {})
    else:
        # Anakin / population: every role colocated on the whole mesh.
        for role in (ROLE_ACT, ROLE_LEARN, ROLE_EVALUATE):
            assignments[role] = RoleAssignment(role, None, dict(mesh_axes))

    # --- invariants, against the probed count when one is known -------------
    if device_count is not None:
        bad = sorted(
            {
                i
                for a in assignments.values()
                if a.device_ids is not None
                for i in a.device_ids
                if not 0 <= i < device_count
            }
        )
        if bad:
            by_role = {
                a.role: list(a.device_ids)
                for a in assignments.values()
                if a.device_ids is not None
            }
            findings.append(
                f"device ids {bad} out of range for the {device_count} probed "
                f"devices (roles: {by_role})"
            )

    act = assignments.get(ROLE_ACT)
    learn = assignments.get(ROLE_LEARN)
    if act is not None and learn is not None:
        # device_ids=None means "every device": resolvable against a known
        # device count, and against an explicit peer it is the full range —
        # so the only unresolvable pairing is one-None with no count.
        act_ids = learn_ids = None
        if act.device_ids is not None and learn.device_ids is not None:
            act_ids, learn_ids = set(act.device_ids), set(learn.device_ids)
        elif device_count is not None:
            act_ids = set(act.resolved_ids(device_count))
            learn_ids = set(learn.resolved_ids(device_count))
        if act_ids is not None and act_ids != learn_ids and act_ids & learn_ids:
            findings.append(
                f"act and learn roles partially overlap on device ids "
                f"{sorted(act_ids & learn_ids)} — primary roles must be "
                "either colocated (identical sets) or disjoint"
            )

    for a in assignments.values():
        sizes = list(a.axes.values())
        if sizes.count(-1) > 1:
            findings.append(
                f"role '{a.role}': at most one mesh axis may be -1, got {a.axes}"
            )

    if findings:
        raise MeshRolesError(findings)
    return assignments


def elastic_mesh_axes(
    axes: Optional[Dict[str, int]], device_count: int
) -> Dict[str, int]:
    """Re-derive a mesh axis spec for a DIFFERENT device count (the elastic
    relaunch path, docs/DESIGN.md §2.14). Pure host logic — no jax — so the
    supervising launcher can compute the survivor topology before spawning.

    A `-1` axis already absorbs whatever count the child probes, so the spec
    passes through untouched. When every axis is pinned, the `data` axis is
    rescaled to fit (the population shape: `{pop: P, data: -1→fixed}`); a
    count the fixed axes cannot divide is refused rather than silently
    truncated — the caller must shrink the other axes (e.g. the population)
    first.
    """
    if device_count < 1:
        raise MeshRolesError(
            [f"cannot derive a mesh for {device_count} devices"]
        )
    axes = dict(axes or {"data": -1})
    if any(size == -1 for size in axes.values()):
        return axes
    fixed = 1
    for name, size in axes.items():
        if name != "data":
            fixed *= int(size)
    if "data" not in axes:
        raise MeshRolesError(
            [
                f"mesh axes {axes} have no -1 axis and no 'data' axis to "
                f"rescale for {device_count} devices"
            ]
        )
    if fixed < 1 or device_count % fixed != 0:
        raise MeshRolesError(
            [
                f"mesh axes {axes} cannot be rescaled to {device_count} "
                f"devices: the non-data axes multiply to {fixed}, which does "
                f"not divide {device_count}"
            ]
        )
    rescaled = dict(axes)
    rescaled["data"] = device_count // fixed
    return rescaled


class MeshRoles:
    """Materialized role → devices/mesh mapping for this process's job.

    The single device-bookkeeping object consumed by the Anakin runner
    (learn mesh), the Sebulba split (act/learn/evaluate devices + learn
    mesh), the replay service (the learn mesh's data axis), serve (the serve
    device), and the population runner (("pop", "data") learn mesh).
    """

    def __init__(self, assignments: Dict[str, RoleAssignment], devices: Sequence[Any]):
        self._assignments = dict(assignments)
        self._devices = list(devices)

    @classmethod
    def from_config(cls, config: Any, devices: Optional[Sequence[Any]] = None) -> "MeshRoles":
        if devices is None:
            import jax

            devices = jax.devices()
        devices = list(devices)
        return cls(resolve_assignments(config, device_count=len(devices)), devices)

    # -- queries --------------------------------------------------------------
    @property
    def roles(self) -> Tuple[str, ...]:
        return tuple(self._assignments)

    def has_role(self, role: str) -> bool:
        return role in self._assignments

    def assignment(self, role: str) -> RoleAssignment:
        if role not in self._assignments:
            raise MeshRolesError(
                [f"role '{role}' is not assigned (assigned: {', '.join(self.roles)})"]
            )
        return self._assignments[role]

    def role_device_ids(self, role: str) -> Tuple[int, ...]:
        return self.assignment(role).resolved_ids(len(self._devices))

    def role_devices(self, role: str) -> List[Any]:
        return [self._devices[i] for i in self.role_device_ids(role)]

    def device(self, role: str) -> Any:
        return self.role_devices(role)[0]

    def role_mesh(self, role: str):
        """The role's mesh: its axes over its devices. Roles declared without
        axes get a pure data-parallel mesh over their devices."""
        from stoix_tpu.parallel.mesh import create_mesh

        a = self.assignment(role)
        axes = dict(a.axes) or {"data": -1}
        return create_mesh(axes, devices=self.role_devices(role))

    def learn_mesh(self):
        return self.role_mesh(ROLE_LEARN)

    def colocated(self, role_a: str, role_b: str) -> bool:
        return set(self.role_device_ids(role_a)) == set(self.role_device_ids(role_b))

    def describe(self) -> str:
        parts = []
        for role, a in self._assignments.items():
            ids = a.resolved_ids(len(self._devices))
            axes = f" axes={a.axes}" if a.axes else ""
            parts.append(f"{role}=[{','.join(map(str, ids))}]{axes}")
        return " ".join(parts)
