"""Test config: run everything on an 8-device virtual CPU mesh.

Mirrors SURVEY.md section 4's test-pyramid plan: pmap/pjit semantics are
exercised on CPU with ``--xla_force_host_platform_device_count`` so multi-chip
sharding is validated without TPU hardware. Both environment variables are
read when the backend starts, so they are set here, at conftest import time,
before anything touches JAX.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic tests: no persistent compile cache, so no test runs an executable
# a previous run left in artifacts/ (the cache's own tests start children
# with their own environment).
os.environ["OLS_COMPILE_CACHE"] = "0"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests"
    )
    config.addinivalue_line(
        "markers",
        "chaos: long randomized fault-injection sweeps (run with -m chaos); "
        "the seeded deterministic chaos smoke test stays in tier-1",
    )
