"""Multi-host initialisation and host-side coordination.

The reference explicitly does not support multi-host (the reference's
stoix/systems/ppo/sebulba/ff_ppo.py:808-810 asserts local == global devices;
its README.md:57).
Here multi-host is first-class: call `maybe_initialize_distributed()` before
any JAX computation; the global mesh then spans all processes and collectives
ride ICI within a slice / DCN across slices automatically via shardings.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np


def maybe_initialize_distributed(config: Optional[Any] = None) -> None:
    """Initialise jax.distributed when running under a multi-process launcher.

    Controlled by (in priority order) config.arch.distributed fields or the
    standard env vars (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
    JAX_PROCESS_ID, or a cloud-TPU environment where jax.distributed can
    auto-detect). No-op for single-process runs.

    A HALF-configured launch — num_processes > 1 declared (config or env)
    but no coordinator address anywhere — raises ConfigValidationError
    instead of silently falling back to single-process: the old behavior let
    a "pod" run train 1/N of the batch with every collective a local no-op
    and NO error anywhere, which is the worst possible failure mode (wrong
    numbers, green dashboards).
    """
    dist_cfg = None
    if config is not None:
        dist_cfg = getattr(getattr(config, "arch", None), "distributed", None)

    coordinator = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if dist_cfg and dist_cfg.get("coordinator_address"):
        coordinator = dist_cfg["coordinator_address"]

    if coordinator is None:
        declared = None
        source = None
        if dist_cfg and dist_cfg.get("num_processes") not in (None, "~"):
            declared, source = dist_cfg.get("num_processes"), "arch.distributed.num_processes"
        elif os.environ.get("JAX_NUM_PROCESSES"):
            declared, source = os.environ["JAX_NUM_PROCESSES"], "JAX_NUM_PROCESSES"
        if declared is not None and int(declared) > 1:
            from stoix_tpu.resilience.errors import ConfigValidationError

            raise ConfigValidationError(
                [
                    f"{source}={declared} declares a multi-process launch but "
                    f"no coordinator address is set (JAX_COORDINATOR_ADDRESS "
                    f"or arch.distributed.coordinator_address): refusing to "
                    f"silently run single-process — this 'pod' would train "
                    f"1/{int(declared)} of the batch with every cross-host "
                    f"collective a local no-op and no error anywhere"
                ]
            )
        return  # single process (or an environment where auto-detect is unsafe)

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(
            (dist_cfg or {}).get("num_processes", os.environ.get("JAX_NUM_PROCESSES", 1))
        ),
        process_id=int(
            (dist_cfg or {}).get(
                "process_id",
                os.environ.get("JAX_PROCESS_ID", os.environ.get("SLURM_PROCID", 0)),
            )
        ),
    )


def is_coordinator() -> bool:
    """True on process 0 — gate logging/checkpointing/eval-printing on this."""
    return jax.process_index() == 0


def process_allgather(x: Any) -> Any:
    """Gather host-local values across processes (fully-replicated result).

    Equivalent to jax.experimental.multihost_utils.process_allgather; used for
    cross-host metric aggregation in the host loop.
    """
    if jax.process_count() == 1:
        return jax.tree.map(np.asarray, x)
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(x)
