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
<= 12KB per device. Compiled TPU execution has no such limit. The ceiling
binds program code that tests execute under the interpreter as much as the
tests' own arrays: ``obs.comm_ledger.selfcheck`` sizes its collectives
under it, or every test that reaches it hangs.
"""

import faulthandler
import hashlib
import os
import re
import sys
import tempfile
import threading

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


# One limit for every test. A hang here sits in a C++ wait that never
# returns to the interpreter (an interpreted Pallas collective inside
# jax.block_until_ready), so no Python-level alarm can end it: a timer
# thread writes every thread's stack and ends the process. Under xdist the
# test is reported failed ("worker crashed") and a new worker takes over.
# xdist 3.8's loadfile scheduler hands the rest of the file out again WITH
# the test that crashed, so the dying process leaves the test's name where
# the next worker finds it and fails it at once instead of hanging again.
TEST_LIMIT_S = 300.0


def _over_limit_marker(nodeid: str) -> str | None:
    """Where a worker that dies at the limit leaves the test's name for
    the worker that is handed it again; None without xdist, where the
    process that dies is the whole run and nothing is run again."""
    run = os.environ.get("PYTEST_XDIST_TESTRUNUID")
    if run is None:
        return None
    name = hashlib.sha1(f"{run}:{nodeid}".encode()).hexdigest()
    return os.path.join(tempfile.gettempdir(), f"tdt-over-limit-{name}")


@pytest.fixture(autouse=True)
def _test_limit(request):
    nodeid = request.node.nodeid
    marker = _over_limit_marker(nodeid)
    if marker is not None and os.path.exists(marker):
        os.unlink(marker)
        pytest.fail(f"over the {TEST_LIMIT_S:.0f} s limit in the worker "
                    f"that ran it first; not run again")

    def expire():
        # Past pytest's capture, to the stderr the run was started with.
        capman = request.config.pluginmanager.getplugin("capturemanager")
        if capman is not None:
            capman.suspend_global_capture(in_=True)
        sys.stderr.write(
            f"\n{nodeid}: over the {TEST_LIMIT_S:.0f} s limit of "
            f"tests/conftest.py; ending this process. Stacks:\n")
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        sys.stderr.flush()
        if marker is not None:
            open(marker, "w").close()
        os._exit(1)

    timer = threading.Timer(TEST_LIMIT_S, expire)
    timer.daemon = True
    timer.start()
    yield
    timer.cancel()


@pytest.fixture(scope="session")
def test_limit_s():
    """The limit, for a test that sets a shorter one of its own on what it
    starts (tests/test_tutorials.py's subprocesses)."""
    return TEST_LIMIT_S


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
