"""Test harness config: the suite is CPU-only by design.

Unit tests validate bit-exactness and sharding semantics on a virtual
8-device CPU mesh (fast, deterministic, no chip contention); the chip is
exercised by ``chip_smoke.py``.  ``JAX_PLATFORMS=cpu`` goes into the
environment before the first ``import jax`` so that every child process a
test starts (daemons, bench children) inherits it.
"""

import contextlib
import os

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

from kaspa_tpu.utils import jax_setup

jax_setup.setup()


@pytest.fixture
def on_mesh_devices():
    """``with on_mesh_devices("schnorr"):`` — the wrapped work must have
    gone through the sharded device dispatch for every named kernel and
    none of it down the host degraded lane.  The host lane is bit-identical
    by design, so without this a mesh path that never reaches a device
    passes every identity comparison."""
    from kaspa_tpu.observability.core import REGISTRY

    @contextlib.contextmanager
    def guard(*kernels):
        before = REGISTRY.snapshot()["counters"]
        yield
        after = REGISTRY.snapshot()["counters"]
        assert after["secp_degraded_jobs"] == before["secp_degraded_jobs"], "mesh work fell to the host lane"
        for k in kernels:
            assert after["mesh_dispatches"].get(k, 0) > before["mesh_dispatches"].get(k, 0), (
                f"no sharded {k} dispatch happened"
            )

    return guard
