"""Test configuration.

Force JAX onto a virtual 8-device CPU platform before anything imports jax:
multi-chip sharding logic is exercised on a host-only mesh (the driver
separately dry-runs the multichip path; a real TPU is reserved for
benchmark/run.py and chip_smoke.py).
"""

import os
import sys

# unconditionally: the suite is the CPU rehearsal and must never take a chip
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# the suite (and the script children it spawns, through the env) compiles
# hundreds of throwaway CPU programs: keep them out of the persistent
# cache ops/jaxcfg.py places inside the checkout
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running stress tests, excluded from the tier-1 "
        "`-m 'not slow'` run",
    )
