"""Test bootstrap: force an 8-device virtual CPU mesh before jax imports.

Multi-chip TPU hardware is not available in CI; sharding tests run over
XLA's forced host-platform device count, which exercises the same
GSPMD-partitioned programs the real mesh would run.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The suite must not depend on what an earlier run left on disk: JAX's
# persistent compile cache (solver/tpu.py resolves it to <repo>/.jax_cache)
# stays out of it.  The cache-placement tests opt back in per subprocess.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def small_catalog():
    from karpenter_tpu.models.catalog import generate_catalog

    return generate_catalog(full=False)


@pytest.fixture(scope="session")
def full_catalog():
    from karpenter_tpu.models.catalog import generate_catalog

    return generate_catalog(full=True)
