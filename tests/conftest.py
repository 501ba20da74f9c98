"""Test harness for tpudl.

The reference runs its whole "distributed" suite on local[*] Spark
(SURVEY.md §4: driver+executors in one JVM). Our equivalent trick: an
8-device simulated CPU mesh via XLA host-platform device multiplexing,
so every collective/sharding path is exercised without TPU pods.

These env vars must be set before jax initializes a backend, hence the
top-of-conftest placement.
"""

import os

# The suite is DEFINED on the simulated CPU mesh: pin the platform in the
# environment, so the subprocesses the tests spawn inherit it too (on a
# TPU host a child that reached for the chip would take it, or hang on
# one this process holds).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep TF (used only as a model loader in ingest tests) off any accelerator
# and quiet.
os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def mesh8():
    import jax

    from tpudl import mesh as M

    assert jax.device_count() >= 8, "conftest failed to fake 8 devices"
    return M.build_mesh(n_data=8)


@pytest.fixture(scope="session")
def mesh4x2():
    from tpudl import mesh as M

    return M.build_mesh(n_data=4, n_model=2)
