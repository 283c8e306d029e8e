import os

# Force an 8-device host platform (before the jax import below initialises
# the backend) so the distributed-execution tests (tests/test_distributed.py)
# exercise a real (data, model) mesh in tier-1.  Measured overhead on the
# rest of the suite is nil — single-device computations still run on device
# 0.  An explicit device-count in the caller's XLA_FLAGS wins.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def engine_backend():
    """The engine backend under test, selected by the XPIKE_BACKEND env var.

    CI's backend-matrix job runs the engine/serving tests once per backend
    (reference | integer | pallas); locally it defaults to "reference".
    Tests that exercise *the selected* substrate (rather than comparing
    substrates) should take this fixture instead of hard-coding a name.
    """
    name = os.environ.get("XPIKE_BACKEND", "reference")
    from repro.engine import BACKENDS

    assert name in BACKENDS, f"XPIKE_BACKEND={name!r} not in {sorted(BACKENDS)}"
    return name
