"""chip_smoke.py's contract off the chip: with no accelerator it must fail,
not pass slowly on the CPU."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_on_the_cpu_backend():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    # It stopped at the device check: no task ran, no result line.
    assert "status=" not in proc.stdout
    assert '"ok"' not in proc.stdout
