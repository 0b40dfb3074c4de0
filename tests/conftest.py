"""Test harness config: run JAX on a virtual 8-device CPU mesh.

The suite runs on the CPU wherever it is started: sharding correctness
is validated on a forced 8-device CPU platform (the driver separately
dry-runs the multi-chip path via __graft_entry__.dryrun_multichip), and
the chip is checked by chip_smoke.py, not by tests.  The platform is
pinned through jax.config so a stray JAX_PLATFORMS cannot move the suite
onto an accelerator; XLA_FLAGS is read at backend-init time, which
happens after conftest import, so the forced device count can go
through the environment.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: opt-in long-running reproduction loops (flake rehit "
        "recipes, soak tests) — excluded from tier-1 via -m 'not "
        "slow'")
