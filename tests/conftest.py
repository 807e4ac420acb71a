"""Test configuration: force an 8-device virtual CPU platform so the
multi-chip sharding paths (shard_map over a Mesh) are exercised without TPU
hardware, and enable f64 for the parity math.

Note: a pytest plugin imports jax before this conftest runs, so the env-var
route is too late for jax.config defaults — but the XLA backend itself is
not initialized until first use, so jax.config.update and XLA_FLAGS still
take effect here."""

import os

import jax

# grpc's C++ threads write INFO lines (GOAWAY notices and the like)
# straight to fd 2, bypassing pytest capture; under `2>&1` they splice
# into the progress dot-lines and corrupt the tier-1 DOTS_PASSED count.
# Only ERROR-severity output is worth that interleaving.
os.environ.setdefault("GRPC_VERBOSITY", "ERROR")

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# Tests keep JAX's persistent compilation cache off, in this process
# and in the servers they spawn (the entry points place it otherwise).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soaks excluded from tier-1 (deselected by -m 'not slow')",
    )
    config.addinivalue_line(
        "markers",
        "perf: micro-benchmark assertions (loose budgets; run in tier-1 "
        "to keep instrumentation overhead honest)",
    )
