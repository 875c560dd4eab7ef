"""Test harness: force an 8-device virtual CPU mesh.

All distributed kernels run under the Pallas TPU interpreter on CPU devices
(remote DMA + semaphores are simulated faithfully), so the full 8-way
distributed test suite runs on a CPU-only box — the simulation story the
reference lacks (SURVEY.md §4).

Of the three SERVED one-chip kernels a CPU run interprets one by default:
``paged_attention``, under ``BatchEngine(paged_attn="fused")`` alone
(``PLAIN_PATH`` below is its plain form, for suites of host logic).
``ssm_state_update`` and ``grouped_gemm_skip`` take their plain ``jax.numpy``
equal under ``interpret=None`` where there is no TPU
(``runtime/platform.plain_off_tpu``); a test asks for the interpreted kernel
with ``interpret=True`` (the kernel's entry, or ``Engine(..., interpret=True)``
for a whole step) and for Mosaic's with ``interpret=False``
(tests/test_chip_compile.py).

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
# The margin since PR 44: the slowest test of a whole run under six workers
# takes 115 s on the builder's eight cores (the parent's took 250 s there and
# 290 s on the driver's machine, within 4% of this limit), so the limit is
# 2.6 times the slowest test. It is not lowered: a lower limit on a busier
# machine is a new way to fail. A test that nears 150 s belongs in
# ROADMAP.md D20.
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


# The plain path, for a suite whose SUBJECT IS HOST LOGIC (the scheduler, the
# fleet, the router, journals, observers, spans, budgets, drafts): what its
# engines are built with, ``BatchEngine(engine, ..., **PLAIN_PATH)``. A step
# through the fused kernel is 0.3 s under the interpreter, through the gather
# form 1 ms, and nothing such a suite asserts is decided by a step's attention
# arithmetic; that the two serve the same tokens is held ONCE, by
# tests/test_paged_attention.py::test_batch_engine_fused_matches_gather_and
# _golden. A suite keeps on "fused" the cases whose subject reaches the kernel
# and says which at its helper. The day ``paged_attn`` leaves the constructor
# (ROADMAP D6) this line becomes ``{}`` and no test changes.
PLAIN_PATH = {"paged_attn": "gather"}


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



def pair_planes(k, v, row_ndim: int = 2):
    """K rows and V rows ``(..., lines, *row)`` -> the pool's ONE arena of
    paired planes ``(..., 2, lines, *row)`` (``serving.kv_pool``: plane 0
    the keys, plane 1 the values), for a test that makes its own pool.
    ``row_ndim``: the axes of a row after its line, 2 for ``(Hkv, dh)``, 1
    for a scale arena's ``(Hkv,)``, 3 for a ring's ``(line, Hkv, dh)``
    behind its blocks."""
    import jax.numpy as jnp

    return jnp.stack([k, v], axis=-(row_ndim + 2))
