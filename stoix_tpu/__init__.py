"""stoix_tpu — a TPU-native distributed single-agent RL framework.

A ground-up rebuild of the capabilities of EdanToledo/Stoix, designed for
jax.jit + shard_map over a global TPU mesh instead of single-host pmap.
"""

import time

# The moment this package was first imported, on the `perf_counter` clock: where
# set-up's phase `process_boot` ends and `launch` begins
# (observability/trace.py::SetupClock). The first statement, and the only import.
IMPORTED_AT = time.perf_counter()

__version__ = "0.1.0"
