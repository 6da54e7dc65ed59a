"""Test configuration: pin the CPU platform with 8 virtual devices BEFORE
any backend touch, so multi-client mesh sharding is exercised without TPU
hardware (SURVEY.md §4 implication: mesh-simulated backend stands in for
multi-node). ``provision_virtual_devices`` sets ``jax_platforms=cpu`` and
``jax_num_cpu_devices=8`` through the config API for this process, so the
suite runs on the CPU even on a machine that holds a chip; the
environment variables say the same to the child processes tests spawn."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from neuroimagedisttraining_tpu.parallel.mesh import provision_virtual_devices  # noqa: E402
from neuroimagedisttraining_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

provision_virtual_devices(8)

# Persistent XLA compilation cache: the suite is compile-bound (~100 jitted
# engine programs); warm-cache reruns skip nearly all of it. Same rule as
# every entry point (utils/compile_cache.py).
enable_compile_cache()


@pytest.fixture(scope="session")
def synthetic_cohort():
    from neuroimagedisttraining_tpu.data.synthetic import generate_synthetic_abcd

    return generate_synthetic_abcd(num_subjects=96, shape=(12, 14, 12),
                                   num_sites=4, seed=0)


@pytest.fixture(scope="session")
def synthetic_cohort8():
    """8-site cohort: one real client per device on the 8-device mesh
    (ring-gossip plans require no padding clients)."""
    from neuroimagedisttraining_tpu.data.synthetic import (
        generate_synthetic_abcd,
    )

    return generate_synthetic_abcd(num_subjects=96, shape=(12, 14, 12),
                                   num_sites=8, seed=1)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
