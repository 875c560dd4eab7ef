"""Device-side kernel telemetry: in-kernel probe records.

Opt-in instrumentation for the distributed Pallas kernels. When a kernel is
built with ``probes=True`` it gains one extra *per-rank* int32 output buffer
(SMEM-resident, fixed shape) plus a one-cell SMEM ordinal scratch, and its
body records per-grid-step event ordinals, phase counters, and byte counters
into that buffer. When probes are off (the default) nothing is threaded
through at all — the kernel body sees ``probe=NULL`` whose methods are
trace-time no-ops, so the disabled jaxpr (and therefore the compiled
artifact) is byte-identical to a build that never heard of probes. A probing
run is an explicitly separate compile.

Record format (all int32)::

    buf.shape == (1 + n_steps, N_FIELDS)
    buf[0]  = header: [MAGIC, VERSION, n_steps, rank, world, 0, 0, 0]
    buf[1+step] = [ordinal, dma_issues, dma_waits, sem_spin_iters,
                   local_bytes, remote_bytes, wait_bytes, kflops]

- ``ordinal``: 1-based execution ordinal of the grid step on this rank
  (sequential-grid kernels; absolute-row kernels such as paged attention
  document the caveat at their call site).
- ``dma_issues`` / ``dma_waits``: counts of DMA starts / completion waits
  (local copies, remote puts, receive-arrival and send-drain waits).
- ``sem_spin_iters``: semaphore-wait iterations that are pure choreography
  (barrier signals awaited), as opposed to data-arrival waits.
- ``local_bytes`` / ``remote_bytes``: bytes moved by DMAs *issued* this step
  (remote = over ICI). ``wait_bytes``: bytes whose completion was *awaited*
  this step — the decoder's stall weight.
- ``kflops``: compute issued this step, in units of 1024 flops (``max(1,
  flops >> 10)`` keeps small test shapes visible without overflowing int32).

TPU Pallas exposes no device cycle counter, so records carry no timestamps;
the host decoder (``obs/kprobe.py``) assigns deterministic modeled durations
from the byte/iteration counters and the perf-model hardware profile, which
is exactly what makes the pipeline reproducible in interpret mode on CPU.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.runtime import compat as _compat  # noqa: F401

# -- record layout -----------------------------------------------------------

MAGIC = 0x6B7072  # "kpr"
VERSION = 1
N_FIELDS = 8

# per-step fields
F_ORD = 0
F_DMA_ISSUE = 1
F_DMA_WAIT = 2
F_SEM_SPIN = 3
F_LOCAL_BYTES = 4
F_REMOTE_BYTES = 5
F_WAIT_BYTES = 6
F_KFLOPS = 7

# header row (row 0)
H_MAGIC = 0
H_VERSION = 1
H_STEPS = 2
H_RANK = 3
H_WORLD = 4

FIELD_NAMES = ("ordinal", "dma_issue", "dma_wait", "sem_spin",
               "local_bytes", "remote_bytes", "wait_bytes", "kflops")


def _ref_bytes(ref) -> int:
    """Static byte count of a ref/view (shapes are trace-time constants)."""
    return int(math.prod(ref.shape)) * int(np.dtype(ref.dtype).itemsize)


def _is_static(v) -> bool:
    return isinstance(v, (int, np.integer))


# -- device-side recorders ---------------------------------------------------


class Probe:
    """Live recorder bound to one kernel invocation's probe buffer.

    Constructed inside the probed kernel wrapper from the two extra refs the
    build threads through (the SMEM probe output and the SMEM ordinal
    scratch). Kernel bodies call :meth:`enter` once per grid step, then the
    phase recorders; all stores are scalar SMEM stores (SMEM takes scalar
    stores only).
    """

    enabled = True

    def __init__(self, buf_ref, ord_ref, *, n_steps: int):
        self._buf = buf_ref
        self._ord = ord_ref
        self._n_steps = int(n_steps)
        self._row = None

    def _bump(self, field: int, amount):
        self._buf[self._row, field] = self._buf[self._row, field] + amount

    def enter(self, step, rank, world, *, fresh=True):
        """Open the record for grid step ``step`` (0-based; static int or
        traced scalar). Zeroes the step row (Pallas outputs start
        uninitialized), writes the header + zeroes the ordinal counter at
        step 0, then stamps this step's execution ordinal. ``fresh`` (a
        bool, static or traced) False re-enters a row that is already open
        — a loop whose first two iterations book into one record — and
        keeps its counts and ordinal."""
        def _init():
            self._buf[0, H_MAGIC] = MAGIC
            self._buf[0, H_VERSION] = VERSION
            self._buf[0, H_STEPS] = self._n_steps
            self._buf[0, H_RANK] = rank
            self._buf[0, H_WORLD] = world
            for f in range(5, N_FIELDS):
                self._buf[0, f] = 0
            self._ord[0] = 0

        if _is_static(step):
            if int(step) == 0:
                _init()
        else:
            pl.when(step == 0)(_init)

        row = step + 1
        self._row = row

        def _open():
            for f in range(N_FIELDS):
                self._buf[row, f] = 0
            self._ord[0] = self._ord[0] + 1
            self._buf[row, F_ORD] = self._ord[0]

        if fresh is True:
            _open()
        else:
            pl.when(fresh)(_open)

    def dma_issue(self, ref, *, remote: bool = False):
        """A DMA start whose source/payload is ``ref`` (remote = ICI put)."""
        nbytes = _ref_bytes(ref)
        self._bump(F_DMA_ISSUE, 1)
        self._bump(F_REMOTE_BYTES if remote else F_LOCAL_BYTES, nbytes)

    def dma_wait(self, ref):
        """A completion wait for a DMA moving ``ref``-many bytes."""
        self._bump(F_DMA_WAIT, 1)
        self._bump(F_WAIT_BYTES, _ref_bytes(ref))

    def sem_spin(self, iters: int):
        """``iters`` pure-choreography semaphore-wait iterations (barriers)."""
        self._bump(F_SEM_SPIN, int(iters))

    def compute(self, flops: int):
        """``flops`` of compute issued this step (recorded as kflops)."""
        self._bump(F_KFLOPS, max(1, int(flops) >> 10))


class NullProbe:
    """Trace-time no-op stand-in: the default ``probe=`` value. Every method
    emits nothing, so a probe-off build's jaxpr is identical to one predating
    the probe layer entirely."""

    enabled = False

    def enter(self, step, rank, world, *, fresh=True):
        pass

    def dma_issue(self, ref, *, remote: bool = False):
        pass

    def dma_wait(self, ref):
        pass

    def sem_spin(self, iters: int):
        pass

    def compute(self, flops: int):
        pass


NULL = NullProbe()


# -- pallas-call build helpers ----------------------------------------------


def n_rows(n_steps: int) -> int:
    return 1 + max(1, int(n_steps))


def out_shape(n_steps: int) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct for the probe output appended to a kernel's
    ``out_shape`` list (always the LAST output)."""
    return jax.ShapeDtypeStruct((n_rows(n_steps), N_FIELDS), jnp.int32)


def out_spec() -> pl.BlockSpec:
    """Whole-buffer SMEM spec for the probe output (scalar stores only;
    persists across sequential grid steps like any unblocked output)."""
    return pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM)


def ord_scratch():
    """The one-cell SMEM ordinal counter appended to ``scratch_shapes``
    (always the LAST scratch)."""
    return pltpu.SMEM((1,), jnp.int32)


def host_stub_buffer(n_steps: int = 1, *, rank: int = 0, world: int = 1):
    """Host-built probe buffer for degenerate paths that never launch the
    kernel (``world == 1`` fallbacks): a valid header over all-zero rows, so
    decoders need no special case."""
    buf = np.zeros((n_rows(n_steps), N_FIELDS), np.int32)
    buf[0, H_MAGIC] = MAGIC
    buf[0, H_VERSION] = VERSION
    buf[0, H_STEPS] = max(1, int(n_steps))
    buf[0, H_RANK] = int(rank)
    buf[0, H_WORLD] = int(world)
    return jnp.asarray(buf)


# -- comm-safety analyzer variants ------------------------------------------
#
# Every instrumented kernel re-registers as "<base>+probe": the base body
# wrapped to receive the two probe refs appended at the END of the arg list
# and handed a live Probe via the ``probe=`` keyword. The analyzer then
# proves the probed choreography is exactly as clean as the base one —
# probe buffers are rank-local SMEM with no semaphore traffic, so any
# violation would be a real instrumentation bug.

from triton_distributed_tpu.analysis import registry as _comm  # noqa: E402

# base registration name -> the kwarg names whose product is n_steps when the
# spec carries a grid (empty grid -> single-step kernel).
PROBE_BASES = (
    "ag.ring",
    "ag.a2a",
    "ar.oneshot",
    "rs.oneshot",
    "rs.ring",
    "gemm_rs",
    "ag_gemm",
    "ep.a2a",
    "moe.ag_group_gemm",
)


def _register_probe_variant(base_name: str) -> None:
    @_comm.register(f"{base_name}+probe")
    def _build(world: int, _base=base_name) -> "_comm.TraceSpec":
        base = _comm.get(_base).build(world)
        n_steps = 1
        for g in base.grid:
            n_steps *= int(g)

        def body(*args, **kwargs):
            pbuf, pord = args[-2], args[-1]
            probe = Probe(pbuf, pord, n_steps=n_steps)
            return base.body(*args[:-2], probe=probe, **kwargs)

        return _comm.TraceSpec(
            body=body,
            args=[*base.args,
                  _comm.Buf("probe_buf", (n_rows(n_steps), N_FIELDS),
                            np.int32, space="smem"),
                  _comm.Buf("probe_ord", (1,), np.int32, space="smem")],
            grid=base.grid,
            kwargs=dict(base.kwargs),
            ranks=base.ranks,
            axes=base.axes,
        )


for _base in PROBE_BASES:
    _register_probe_variant(_base)
del _base
