"""Test harness: force an 8-device virtual CPU mesh.

All distributed kernels run under the Pallas TPU interpreter on CPU devices
(remote DMA + semaphores are simulated faithfully), so the full 8-way
distributed test suite runs on a CPU-only box — the simulation story the
reference lacks (SURVEY.md §4).

IMPORTANT — interpreter buffer-size ceiling: on a single-core host, the
Pallas TPU interpreter deadlocks when a kernel that blocks on cross-device
semaphores also allocates any per-device buffer >= 16KB (the interpreter's
per-device threads park inside io_callbacks awaiting buffer transfers that
the CPU client's lone async thread — busy running a blocked callback — can
never service; verified empirically: <=12KB always passes, >=16KB always
hangs). Keep every input/output/scratch buffer in distributed-kernel tests
<= 12KB per device. Compiled TPU execution has no such limit.
"""

import os
import re

_flags = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", ""),
)
os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# Pin the platform in-process too (backends initialize lazily, so this
# takes effect even where JAX_PLATFORMS was read before this file ran).
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from triton_distributed_tpu.runtime.mesh import make_mesh

    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"
    return make_mesh({"tp": 8})


@pytest.fixture(scope="session")
def mesh4x2():
    from triton_distributed_tpu.runtime.mesh import make_mesh

    return make_mesh({"ep": 4, "tp": 2}, set_default=False)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
