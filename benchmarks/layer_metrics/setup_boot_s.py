"""Set-up: seconds from the OS's start of the process to the import of the
program's package — the interpreter, the launcher's own imports and, in this
benchmark, `import jax` and the chip's start-up in the device gate, which come
first here — from the program's set-up gauge
`stoix_tpu_setup_phase_seconds{phase=process_boot}`
(`stoix_tpu/observability/trace.py::SetupClock`). Moves `setup_s`."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.setup_phase_seconds(ctx, ("process_boot",))
