"""chip_smoke.py has no fallback: without a TPU it refuses, quickly, by name.

What the script proves it proves on the chip (through the chip tool); tier-1
only pins the refusal — a run without the chip must never look like a run
with it."""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu_quickly_naming_the_platform():
    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    elapsed = time.monotonic() - start
    assert proc.returncode != 0, proc.stdout
    assert elapsed < 30.0, f"the refusal took {elapsed:.0f}s"
    # It says what it found, first, and prints no result object.
    assert proc.stdout.splitlines()[0].startswith("[chip_smoke] platform=cpu "), proc.stdout
    assert "platform 'cpu'" in proc.stderr, proc.stderr
    assert '"ok"' not in proc.stdout
