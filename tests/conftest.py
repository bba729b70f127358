"""Test fixtures: the tests run on the CPU, never on a chip.

They run with ``JAX_PLATFORMS=cpu`` and 8 virtual CPU devices
(``--xla_force_host_platform_device_count=8``, a 2x2x2 mesh), both set
here before JAX is imported, so a test never takes a TPU that another
process holds. Pallas kernels run in interpret mode here; what only the
chip can show (kernel compiles at real widths) is AOT-compiled against a
described v5e topology in ``tests/test_tpu_compile.py``. The chip itself
is reached through the builder's chip tool with ``python chip_smoke.py``
or ``python chip_smoke.py --chips 4``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _devices():
    devs = jax.devices()
    assert devs[0].platform == "cpu", f"tests must run on the CPU: {devs}"
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
