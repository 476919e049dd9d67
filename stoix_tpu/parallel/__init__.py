from stoix_tpu.parallel.gossip import (
    GossipError,
    GossipPlan,
    GossipSettings,
    build_gossip_plan,
    mixing_matrix,
)
from stoix_tpu.parallel.distributed import (
    is_coordinator,
    maybe_initialize_distributed,
    process_allgather,
)
from stoix_tpu.parallel.mesh import (
    assemble_global_array,
    fetch_global,
    fetch_global_async,
    materialize,
    axis_size,
    create_mesh,
    data_sharding,
    replicate,
    replicated_sharding,
    shard_leading_axis,
)
from stoix_tpu.parallel.roles import (
    MeshRoles,
    MeshRolesError,
    RoleAssignment,
    resolve_assignments,
)

__all__ = [
    "GossipError",
    "GossipPlan",
    "GossipSettings",
    "build_gossip_plan",
    "mixing_matrix",
    "is_coordinator",
    "maybe_initialize_distributed",
    "process_allgather",
    "MeshRoles",
    "MeshRolesError",
    "RoleAssignment",
    "resolve_assignments",
    "assemble_global_array",
    "fetch_global",
    "fetch_global_async",
    "materialize",
    "axis_size",
    "create_mesh",
    "data_sharding",
    "replicate",
    "replicated_sharding",
    "shard_leading_axis",
]
