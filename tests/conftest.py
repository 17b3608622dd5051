"""Test fixture: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): HPX tests multi-locality
behavior with real processes on localhost; we test multi-chip behavior with
XLA's host-platform virtual devices. bench.py and chip_smoke.py use the real
TPU; tests use CPU so they run anywhere and exercise the same sharding code.

Env vars MUST be set before jax is imported anywhere.
"""

import os

# force, don't setdefault: tier-1 never takes a chip, whatever the
# caller's environment names
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh1d(devices):
    from jax.sharding import Mesh
    import numpy as np
    return Mesh(np.array(devices), ("x",))


@pytest.fixture(scope="session")
def mesh2d(devices):
    from jax.sharding import Mesh
    import numpy as np
    return Mesh(np.array(devices).reshape(4, 2), ("x", "y"))


@pytest.fixture(autouse=True)
def _reset_test_counters():
    from hpx_tpu import testing
    testing.reset_errors()
    yield
    assert testing.report_errors() == 0, "HPX_TEST failures recorded"
