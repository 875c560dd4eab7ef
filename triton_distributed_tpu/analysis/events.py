"""Instrumented SPMD abstract interpretation of the distributed kernels.

The kernel *bodies* under ``kernels/`` are plain Python functions over
Pallas refs.  This module runs them — once per rank, with concrete Python
rank values — against fake refs/semaphores/DMAs that record a per-rank
**event log** (semaphore id, target rank, inc/wait amount, buffer
byte-range) instead of touching hardware.  ``comm_graph.py`` then replays
the N logs against each other and ``checks.py`` asserts the safety
properties.  No TPU, no XLA compilation of the kernel: ``jnp`` math inside
the body executes eagerly on CPU over tiny representative shapes.

What gets shimmed while a trace is active (restored on exit):

* ``pltpu.semaphore_wait / semaphore_signal / get_barrier_semaphore /
  make_async_copy / make_async_remote_copy`` — the entire sync surface
  that ``language/primitives.py``, ``language/shmem.py`` and
  ``kernels/common.py`` bottom out in, so ``dl.wait/notify/barrier_all``,
  ``shmem.putmem_* / signal_op / signal_wait_until / quiet`` and
  ``common.remote_copy / wait_recv / wait_send / local_copy`` are all
  recorded without any kernel-visible API change.
* ``pl.when / program_id / num_programs / ds / cdiv`` — grid + predication,
  evaluated concretely.
* ``jax.lax.axis_index / rem / fori_loop`` — rank arithmetic and loops,
  evaluated as Python ints / loops.
* ``runtime.compat.axis_size / mesh_device_id`` — including every
  ``_axis_size = axis_size``-style module binding, found by scanning
  ``sys.modules`` for attributes that *are* the originals.

Semaphore unit currencies mirror the hardware: DMA semaphores count
**bytes** (an async copy increments by the transferred byte count and the
matching wait decrements the same), regular/barrier semaphores count
**signals**.

Tracing is two-round: round 0 is a warm-up whose events are discarded but
whose *data movement* still happens (so data-dependent predicates — e.g.
the EP all-to-all receiver gating chunk waits on a DMA-received count —
see the same values every sender used); round 1 is recorded.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import sys
from typing import Any, Callable, Sequence

import numpy as np

from triton_distributed_tpu.analysis import registry as _registry


class CommTraceError(RuntimeError):
    """A kernel body performed an operation the tracer can prove ill-formed
    (semaphore index outside the declared array, signal to a rank outside
    the world, copy between mismatched shapes, ...)."""


# ---------------------------------------------------------------------------
# Event model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Event:
    """One program point in one rank's trace."""

    eid: int                    # globally unique
    kind: str                   # 'inc' | 'wait' | 'read' | 'write'
    rank: int                   # rank whose program executed this
    seq: int                    # index in that rank's log (program order)
    sem: tuple | None = None    # inc/wait: semaphore identity tuple
    target: int | None = None   # inc: rank whose count is incremented
    amount: int = 0             # inc/wait: signal count or DMA bytes
    buf: str | None = None      # read/write: root buffer name
    lo: int = 0                 # read/write: byte range [lo, hi) in buffer
    hi: int = 0
    # a DMA's read/write of a STRIDED view: its contiguous byte runs, where
    # [lo, hi) is only their bounding box (() = the range is exact)
    runs: tuple = ()
    dma: int | None = None      # id of the DMA this event belongs to
    side: str | None = None     # inc: 'send' | 'recv' for DMA increments
    label: str = ""

    def where(self) -> str:
        return f"rank {self.rank} @ event {self.seq}"


@dataclasses.dataclass
class DmaRecord:
    """One started async copy (local or cross-rank)."""

    did: int
    kind: str                   # 'local' | 'remote'
    src_rank: int
    dst_rank: int
    src_buf: str
    src_lo: int
    src_hi: int
    dst_buf: str
    dst_lo: int
    dst_hi: int
    send_sem: tuple | None      # None for local copies (single semaphore)
    recv_sem: tuple
    start_seq: int              # seq (src rank log) where .start() ran
    send_eid: int | None        # eid of the send-side inc (remote only)
    recv_eid: int | None        # eid of the recv-side inc
    src_runs: tuple = ()        # the runs of a strided source / destination
    dst_runs: tuple = ()        # (``FakeRef.runs``; () = the range is exact)

    def describe(self) -> str:
        if self.kind == "local":
            return (f"local copy #{self.did} {self.src_buf}[{self.src_lo}:"
                    f"{self.src_hi}] -> {self.dst_buf}[{self.dst_lo}:"
                    f"{self.dst_hi}] on rank {self.src_rank}")
        return (f"remote put #{self.did} rank {self.src_rank} "
                f"{self.src_buf}[{self.src_lo}:{self.src_hi}] -> rank "
                f"{self.dst_rank} {self.dst_buf}[{self.dst_lo}:{self.dst_hi}]")


@dataclasses.dataclass
class OobRecord:
    """An index expression that reaches past its buffer's declared extent.

    numpy silently CLIPS out-of-range slices, so without this record the
    trace would quietly read/write a smaller bbox than the kernel asked
    for — exactly the class of bug Mosaic rejects at compile time on TPU.
    The tracer records the violation and lets the clipped access proceed,
    so one bad index does not abort the rest of the trace.
    """

    buf: str
    rank: int
    op: str                 # 'read' | 'write' | 'view'
    index: str              # the offending index expression, formatted
    shape: tuple[int, ...]  # shape of the view the index was applied to

    def describe(self) -> str:
        return (f"rank {self.rank}: {self.op} {self.buf}[{self.index}] "
                f"past declared shape {self.shape}")


@dataclasses.dataclass
class TraceResult:
    world: int
    ranks: int
    logs: list              # list[list[Event]], one per traced rank
    dmas: list              # list[DmaRecord]
    # Final per-rank buffer contents, keyed (name, rank). Lets callers read
    # back data the kernel produced during the trace — e.g. the device-probe
    # buffers of the "+probe" variants (obs/kprobe.py decodes them).
    store: dict | None = None
    # Out-of-bounds index expressions seen during the recorded round.
    oob: list = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# Tracer state
# ---------------------------------------------------------------------------

class Tracer:
    def __init__(self, world: int, ranks: int, grid: tuple[int, ...],
                 axes: tuple[tuple[str, int], ...] | None = None):
        self.world = world
        self.ranks = ranks
        self.grid = tuple(grid)
        self.grid_point: tuple[int, ...] = (0,) * len(grid)
        self.axes = tuple(axes) if axes else None
        self.store: dict[tuple[str, int], np.ndarray] = {}
        self.logs: list[list[Event]] = [[] for _ in range(ranks)]
        self.dmas: list[DmaRecord] = []
        self.oob: list[OobRecord] = []
        self.rank = 0
        self.recording = False
        self._eid = 0
        self._did = 0

    def emit(self, **kw) -> Event | None:
        if not self.recording:
            return None
        log = self.logs[self.rank]
        ev = Event(eid=self._eid, rank=self.rank, seq=len(log), **kw)
        self._eid += 1
        log.append(ev)
        return ev

    def new_dma_id(self) -> int | None:
        if not self.recording:
            return None
        did = self._did
        self._did += 1
        return did

    def note_oob(self, rec: OobRecord) -> None:
        if self.recording:
            self.oob.append(rec)

    # -- named mesh axes (TraceSpec.axes) ----------------------------------
    def _axis_stride(self, axis: str) -> tuple[int, int]:
        """(size, row-major stride) of a declared axis; raises on unknown
        names when a mesh is declared (a typo'd axis name is a kernel bug,
        not something to silently flatten)."""
        assert self.axes is not None
        stride = 1
        found = None
        for name, size in reversed(self.axes):
            if name == axis:
                found = (size, stride)
            stride *= size
        if found is None:
            raise CommTraceError(
                f"axis {axis!r} not in declared mesh "
                f"{tuple(n for n, _ in self.axes)}")
        return found

    def axis_coord(self, axis: str) -> int:
        """This rank's coordinate along ``axis`` (rank if no mesh)."""
        if self.axes is None:
            return self.rank
        size, stride = self._axis_stride(axis)
        return (self.rank // stride) % size

    def axis_size_of(self, axis) -> int:
        if self.axes is None:
            return self.world
        return self._axis_stride(axis)[0]

    def global_rank_with(self, axis, peer: int) -> int:
        """Global rank of the device at coordinate ``peer`` along ``axis``,
        keeping this rank's other coordinates — the tracer-side analog of
        ``compat.mesh_device_id``."""
        if self.axes is None:
            return int(peer)
        size, stride = self._axis_stride(axis)
        if not 0 <= int(peer) < size:
            raise CommTraceError(
                f"peer {int(peer)} outside axis {axis!r} of size {size}")
        return self.rank + (int(peer) - self.axis_coord(axis)) * stride


# ---------------------------------------------------------------------------
# Fake refs / semaphores / DMAs
# ---------------------------------------------------------------------------

def _normalize_index(idx) -> tuple:
    """Coerce traced scalars (np/jnp ints) in an index to Python ints so the
    same index can be re-applied to a peer's buffer instance."""
    if not isinstance(idx, tuple):
        idx = (idx,)
    out = []
    for i in idx:
        if i is Ellipsis or i is None:
            out.append(i)
        elif isinstance(i, slice):
            out.append(slice(
                None if i.start is None else int(i.start),
                None if i.stop is None else int(i.stop),
                None if i.step is None else int(i.step)))
        else:
            out.append(int(i))
    return tuple(out)


def _fmt_index(nidx: tuple) -> str:
    def one(i):
        if isinstance(i, slice):
            a = "" if i.start is None else i.start
            b = "" if i.stop is None else i.stop
            return f"{a}:{b}"
        return str(i)
    return ", ".join(one(i) for i in nidx)


class FakeRef:
    """numpy-view-backed stand-in for a Pallas ref.

    Keeps the root buffer plus the chain of indices that produced this view
    so a remote DMA can rebind the same ref expression to the *peer's*
    instance of the buffer (store is keyed ``(name, rank)``).
    """

    def __init__(self, tracer: Tracer, name: str, rank: int,
                 root: np.ndarray, view: np.ndarray | None = None,
                 chain: tuple = ()):
        self._tracer = tracer
        self.name = name
        self.rank = rank
        self._root = root
        self._view = root if view is None else view
        self._chain = tuple(chain)

    # -- geometry ----------------------------------------------------------
    @property
    def shape(self):
        return self._view.shape

    @property
    def dtype(self):
        return self._view.dtype

    @property
    def ndim(self):
        return self._view.ndim

    @property
    def size(self):
        return self._view.size

    @property
    def nbytes(self):
        return int(self._view.nbytes)

    def bbox(self) -> tuple[int, int]:
        """Byte range [lo, hi) of this view inside its root buffer."""
        v = self._view
        if v.size == 0:
            return (0, 0)
        off = (v.__array_interface__["data"][0]
               - self._root.__array_interface__["data"][0])
        ext = sum((s - 1) * abs(st)
                  for s, st in zip(v.shape, v.strides)) + v.itemsize
        return (int(off), int(off + ext))

    #: Most runs ``runs`` lists; a view in more pieces keeps its bounding box.
    MAX_RUNS = 64

    def runs(self) -> tuple:
        """The contiguous byte runs ``((lo, hi), ...)`` of a STRIDED view
        inside its root buffer, in order, or ``()`` where the view is one
        run (``bbox`` is then exact) or has more than ``MAX_RUNS`` (its
        bounding box stands for it: conservative). A copy into the two
        planes of a staging slot is two runs; between them lie the rows of
        the tile's other blocks, which another copy may write meanwhile."""
        v = self._view
        if v.size == 0:
            return ()
        block, dim = v.itemsize, v.ndim
        while dim and (v.shape[dim - 1] == 1 or v.strides[dim - 1] == block):
            block *= v.shape[dim - 1]
            dim -= 1
        lead = v.shape[:dim]
        if not lead or int(np.prod(lead)) > self.MAX_RUNS:
            return ()
        base = self.bbox()[0]       # strides are positive: the first byte
        out: list[tuple[int, int]] = []
        for idx in np.ndindex(*lead):
            lo = base + int(np.dot(idx, v.strides[:dim]))
            if out and out[-1][1] == lo:
                out[-1] = (out[-1][0], lo + block)
            else:
                out.append((lo, lo + block))
        return tuple(out) if len(out) > 1 else ()

    # -- slicing (no event: pure view, like pl.Ref.at) ---------------------
    @property
    def at(self):
        return _RefIndexer(self)

    def _check_bounds(self, nidx: tuple, op: str) -> None:
        """Record slices that reach past the view's extent. numpy CLIPS such
        slices silently, so without this the trace under-reports the bbox
        the kernel actually asked for (Mosaic would reject it on TPU)."""
        if any(i is Ellipsis or i is None for i in nidx):
            return  # rare in kernel code; the simple positional walk below
                    # would misalign dims, so skip rather than mis-report
        for i, dim in zip(nidx, self._view.shape):
            bad = False
            if isinstance(i, slice):
                start = 0 if i.start is None else i.start
                stop = dim if i.stop is None else i.stop
                bad = start < 0 or stop > dim or start > stop
            elif isinstance(i, int):
                bad = not -dim <= i < dim
            if bad:
                self._tracer.note_oob(OobRecord(
                    buf=self.name, rank=self.rank, op=op,
                    index=_fmt_index(nidx), shape=tuple(self._view.shape)))
                return

    def _sub(self, idx) -> "FakeRef":
        idx = _normalize_index(idx)
        self._check_bounds(idx, "view")
        try:
            sub = self._view[idx]
        except Exception as e:  # noqa: BLE001 — re-raise with context
            raise CommTraceError(
                f"bad index {idx} into ref {self.name!r} of shape "
                f"{self._view.shape}: {e}") from e
        if not isinstance(sub, np.ndarray):
            sub = self._view[self._widen(idx)]
        return FakeRef(self._tracer, self.name, self.rank, self._root,
                       sub, self._chain + (idx,))

    def _widen(self, idx) -> tuple:
        """Integer indices -> length-1 slices, so the result stays an
        ndarray view (for byte-range computation)."""
        out = []
        for i in idx:
            if isinstance(i, int):
                if i < 0:
                    raise CommTraceError(
                        f"negative index {i} into ref {self.name!r} — the "
                        "tracer only models non-negative kernel indexing")
                out.append(slice(i, i + 1))
            else:
                out.append(i)
        return tuple(out)

    def _rebind(self, rank: int) -> "FakeRef":
        """The same ref expression, on ``rank``'s instance of the buffer."""
        try:
            root = self._tracer.store[(self.name, rank)]
        except KeyError:
            raise CommTraceError(
                f"no instance of buffer {self.name!r} on rank {rank} — "
                f"remote DMA targeting a rank outside the traced world?")
        view = root
        for idx in self._chain:
            view = view[idx]
        return FakeRef(self._tracer, self.name, rank, root, view,
                       self._chain)

    # -- value access (recorded) -------------------------------------------
    def __getitem__(self, idx):
        nidx = _normalize_index(idx)
        self._check_bounds(nidx, "read")
        val = self._view[nidx]
        sub = self._view[self._widen(nidx)]
        lo, hi = FakeRef(self._tracer, self.name, self.rank, self._root,
                         sub).bbox() if sub.size else (0, 0)
        self._tracer.emit(kind="read", buf=self.name, lo=lo, hi=hi)
        return val

    def __setitem__(self, idx, value):
        nidx = _normalize_index(idx)
        self._check_bounds(nidx, "write")
        sub = self._view[self._widen(nidx)]
        lo, hi = FakeRef(self._tracer, self.name, self.rank, self._root,
                         sub).bbox() if sub.size else (0, 0)
        self._tracer.emit(kind="write", buf=self.name, lo=lo, hi=hi)
        self._view[nidx] = np.asarray(value)

    def __array__(self, dtype=None):
        lo, hi = self.bbox()
        self._tracer.emit(kind="read", buf=self.name, lo=lo, hi=hi)
        arr = np.asarray(self._view)
        return arr.astype(dtype) if dtype is not None else arr


class _RefIndexer:
    def __init__(self, ref: FakeRef):
        self._ref = ref

    def __getitem__(self, idx) -> FakeRef:
        return self._ref._sub(idx)


class FakeSem:
    """Semaphore (array) stand-in; identity is the tuple ``(name, *idx)``
    which is shared across ranks — each rank has its *own count* of the
    *same* semaphore, which is exactly the hardware model."""

    def __init__(self, sid: tuple, shape: tuple[int, ...],
                 decl_shape: tuple[int, ...]):
        self.sid = sid
        self.shape = tuple(shape)
        self.decl_shape = tuple(decl_shape)

    @property
    def at(self):
        return _SemIndexer(self)

    def require_scalar(self, what: str) -> None:
        if self.shape:
            raise CommTraceError(
                f"{what} on semaphore array {self.sid[0]!r} (remaining dims "
                f"{self.shape}) — index it with .at[...] down to a single "
                "semaphore first")

    def describe(self) -> str:
        return _fmt_sem(self.sid)


def _fmt_sem(sid: tuple) -> str:
    name, *idx = sid
    return f"{name}[{', '.join(map(str, idx))}]" if idx else str(name)


class _SemIndexer:
    def __init__(self, sem: FakeSem):
        self._sem = sem

    def __getitem__(self, idx) -> FakeSem:
        s = self._sem
        nidx = _normalize_index(idx)
        if len(nidx) > len(s.shape):
            raise CommTraceError(
                f"semaphore {s.sid[0]!r}: index {nidx} has more dims than "
                f"remaining shape {s.shape}")
        for i, d in zip(nidx, s.shape):
            if not isinstance(i, int):
                raise CommTraceError(
                    f"semaphore {s.sid[0]!r}: non-integer index {i!r} — "
                    "semaphore arrays take static integer indices")
            if not 0 <= i < d:
                raise CommTraceError(
                    f"semaphore index {nidx} out of range for "
                    f"{s.sid[0]!r} declared shape {s.decl_shape} — fix the "
                    "kernel-side slot arithmetic or the dma_sems(...) "
                    "slot count at the call site")
        return FakeSem(s.sid + nidx, s.shape[len(nidx):], s.decl_shape)


class FakeDMA:
    """Decoupled start/wait async-copy handle.

    * ``make_async_copy(src, dst, sem)`` (local): ``start()`` moves the
      bytes and increments ``sem`` **once** by ``dst.nbytes`` (the send
      semaphore *is* the recv semaphore); ``wait()`` decrements the same.
      Wait-without-start is the ``wait_dma_arrival`` / ``wait_send_bytes``
      idiom and creates no DMA record.
    * ``make_async_remote_copy(...)`` : ``start()`` eagerly copies into the
      *peer's* instance of the destination buffer, increments the send
      semaphore on the issuing rank by ``src.nbytes`` and the recv
      semaphore on the **target** rank by ``dst.nbytes``.  Placing both
      increments at the start point is sound for the checks: the system is
      monotone, so crediting signals as early as possible can only *hide*
      deadlocks that larger delays would also hide — and the
      happens-before check separately requires the consumer to wait.
    """

    def __init__(self, tracer: Tracer, kind: str, src: FakeRef, dst: FakeRef,
                 send_sem: FakeSem | None, recv_sem: FakeSem,
                 dst_rank: int):
        self._tracer = tracer
        self.kind = kind
        self.src = src
        self.dst = dst
        self.send_sem = send_sem
        self.recv_sem = recv_sem
        self.dst_rank = dst_rank
        self._started = False

    def start(self):
        if self._started:
            raise CommTraceError("DMA handle started twice")
        self._started = True
        t = self._tracer
        did = t.new_dma_id()
        src_lo, src_hi = self.src.bbox()
        src_runs = self.src.runs()
        start_seq = len(t.logs[t.rank]) if t.recording else 0
        t.emit(kind="read", buf=self.src.name, lo=src_lo, hi=src_hi,
               runs=src_runs, dma=did)
        if self.kind == "local":
            dst_lo, dst_hi = self.dst.bbox()
            dst_runs = self.dst.runs()
            self._copy_into(self.dst)
            t.emit(kind="write", buf=self.dst.name, lo=dst_lo, hi=dst_hi,
                   runs=dst_runs, dma=did)
            ev = t.emit(kind="inc", sem=self.recv_sem.sid, target=t.rank,
                        amount=self.dst.nbytes, dma=did, side="recv")
            if did is not None:
                t.dmas.append(DmaRecord(
                    did=did, kind="local", src_rank=t.rank, dst_rank=t.rank,
                    src_buf=self.src.name, src_lo=src_lo, src_hi=src_hi,
                    dst_buf=self.dst.name, dst_lo=dst_lo, dst_hi=dst_hi,
                    send_sem=None, recv_sem=self.recv_sem.sid,
                    start_seq=start_seq, send_eid=None,
                    recv_eid=ev.eid if ev else None,
                    src_runs=src_runs, dst_runs=dst_runs))
        else:
            peer_dst = self.dst._rebind(self.dst_rank)
            dst_lo, dst_hi = peer_dst.bbox()
            self._copy_into(peer_dst)
            send_ev = t.emit(kind="inc", sem=self.send_sem.sid,
                             target=t.rank, amount=self.src.nbytes,
                             dma=did, side="send")
            recv_ev = t.emit(kind="inc", sem=self.recv_sem.sid,
                             target=self.dst_rank, amount=peer_dst.nbytes,
                             dma=did, side="recv")
            if did is not None:
                t.dmas.append(DmaRecord(
                    did=did, kind="remote", src_rank=t.rank,
                    dst_rank=self.dst_rank,
                    src_buf=self.src.name, src_lo=src_lo, src_hi=src_hi,
                    dst_buf=peer_dst.name, dst_lo=dst_lo, dst_hi=dst_hi,
                    send_sem=self.send_sem.sid, recv_sem=self.recv_sem.sid,
                    start_seq=start_seq,
                    send_eid=send_ev.eid if send_ev else None,
                    recv_eid=recv_ev.eid if recv_ev else None,
                    src_runs=src_runs, dst_runs=peer_dst.runs()))
        return self

    def _copy_into(self, dst: FakeRef) -> None:
        if dst.shape != self.src.shape:
            raise CommTraceError(
                f"DMA shape mismatch: src {self.src.name!r}{self.src.shape}"
                f" -> dst {dst.name!r}{dst.shape}")
        np.copyto(dst._view, np.asarray(self.src._view))

    def wait(self):
        if self.kind == "local":
            self._tracer.emit(kind="wait", sem=self.recv_sem.sid,
                              amount=self.dst.nbytes)
        else:
            self.wait_send()
            self.wait_recv()

    def wait_send(self):
        sem = self.send_sem if self.send_sem is not None else self.recv_sem
        self._tracer.emit(kind="wait", sem=sem.sid, amount=self.src.nbytes)

    def wait_recv(self):
        self._tracer.emit(kind="wait", sem=self.recv_sem.sid,
                          amount=self.dst.nbytes)


# ---------------------------------------------------------------------------
# The patch surface
# ---------------------------------------------------------------------------

def _as_rank(device_id, ranks: int) -> int:
    if isinstance(device_id, dict):
        if len(device_id) != 1:
            raise CommTraceError(
                f"multi-axis device_id {device_id!r} — the tracer models a "
                "single mesh axis")
        device_id = next(iter(device_id.values()))
    r = int(device_id)
    if not 0 <= r < ranks:
        raise CommTraceError(
            f"signal/DMA targets rank {r}, outside the traced world of "
            f"{ranks} ranks")
    return r


def _require_ref(x, what: str) -> FakeRef:
    if not isinstance(x, FakeRef):
        raise CommTraceError(
            f"{what} expected a kernel ref, got {type(x).__name__} — the "
            "tracer only models ref-to-ref copies")
    return x


def _require_sem(x, what: str) -> FakeSem:
    if not isinstance(x, FakeSem):
        raise CommTraceError(f"{what} expected a semaphore, got "
                             f"{type(x).__name__}")
    return x


@contextlib.contextmanager
def patched_sync_surface(tracer: Tracer):
    """Swap the sync surface for recording fakes; restore on exit."""
    import jax
    from jax.experimental import pallas as pl_mod
    from jax.experimental.pallas import tpu as pltpu_mod

    from triton_distributed_tpu.runtime import compat

    saved: list[tuple[Any, str, Any]] = []

    def swap(obj, attr, new):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    # ---- fakes ----
    def fake_axis_index(axis):
        # np.int32, not Python int: comparisons must yield np.bool_ so that
        # jnp idioms like ``~is_own`` are logical-not, not bitwise-not on a
        # Python bool (``~True == -2`` is truthy and inverts predication).
        return np.int32(tracer.axis_coord(axis))

    def fake_axis_size(axis):
        return tracer.axis_size_of(axis)

    def fake_mesh_device_id(axis, peer):
        return tracer.global_rank_with(axis, int(peer))

    def fake_rem(a, b):
        return a % b

    def fake_fori_loop(lo, hi, body, init, **kw):
        val = init
        for i in range(int(lo), int(hi)):
            val = body(i, val)
        return val

    def fake_when(cond):
        def deco(fn):
            if bool(cond):
                fn()
            return fn
        return deco

    def fake_program_id(i):
        return np.int32(tracer.grid_point[i])  # np.int32: see fake_axis_index

    def fake_num_programs(i):
        return np.int32(tracer.grid[i])

    def fake_ds(start, size):
        start = int(start)
        return slice(start, start + int(size))

    def fake_cdiv(a, b):
        return -(-int(a) // int(b))

    def fake_semaphore_wait(sem, value=1):
        sem = _require_sem(sem, "semaphore_wait")
        sem.require_scalar("semaphore_wait")
        tracer.emit(kind="wait", sem=sem.sid, amount=int(value))

    def fake_semaphore_signal(sem, inc=1, *, device_id=None,
                              device_id_type=None, core_index=None):
        sem = _require_sem(sem, "semaphore_signal")
        sem.require_scalar("semaphore_signal")
        target = (tracer.rank if device_id is None
                  else _as_rank(device_id, tracer.ranks))
        tracer.emit(kind="inc", sem=sem.sid, target=target, amount=int(inc))

    def fake_get_barrier_semaphore():
        return FakeSem(("barrier",), (), ())

    def fake_make_async_copy(src_ref, dst_ref, sem):
        src = _require_ref(src_ref, "make_async_copy src")
        dst = _require_ref(dst_ref, "make_async_copy dst")
        sem = _require_sem(sem, "make_async_copy sem")
        sem.require_scalar("make_async_copy")
        return FakeDMA(tracer, "local", src, dst, None, sem, tracer.rank)

    def fake_make_async_remote_copy(src_ref=None, dst_ref=None,
                                    send_sem=None, recv_sem=None,
                                    device_id=None, device_id_type=None):
        src = _require_ref(src_ref, "make_async_remote_copy src")
        dst = _require_ref(dst_ref, "make_async_remote_copy dst")
        ssem = _require_sem(send_sem, "make_async_remote_copy send_sem")
        rsem = _require_sem(recv_sem, "make_async_remote_copy recv_sem")
        ssem.require_scalar("make_async_remote_copy send_sem")
        rsem.require_scalar("make_async_remote_copy recv_sem")
        peer = _as_rank(device_id, tracer.ranks)
        return FakeDMA(tracer, "remote", src, dst, ssem, rsem, peer)

    orig_axis_size = compat.axis_size
    orig_mesh_device_id = compat.mesh_device_id

    swap(jax.lax, "axis_index", fake_axis_index)
    swap(jax.lax, "rem", fake_rem)
    swap(jax.lax, "fori_loop", fake_fori_loop)
    swap(pl_mod, "when", fake_when)
    swap(pl_mod, "program_id", fake_program_id)
    swap(pl_mod, "num_programs", fake_num_programs)
    swap(pl_mod, "ds", fake_ds)
    swap(pl_mod, "cdiv", fake_cdiv)
    swap(pltpu_mod, "semaphore_wait", fake_semaphore_wait)
    swap(pltpu_mod, "semaphore_signal", fake_semaphore_signal)
    swap(pltpu_mod, "get_barrier_semaphore", fake_get_barrier_semaphore)
    swap(pltpu_mod, "make_async_copy", fake_make_async_copy)
    swap(pltpu_mod, "make_async_remote_copy", fake_make_async_remote_copy)
    swap(compat, "axis_size", fake_axis_size)
    swap(compat, "mesh_device_id", fake_mesh_device_id)
    # Modules bind `_axis_size = axis_size` at import time; patch every
    # binding whose value IS one of the originals.
    for mod in list(sys.modules.values()):
        if mod is None or not getattr(mod, "__name__", "").startswith(
                "triton_distributed_tpu"):
            continue
        for attr, val in list(vars(mod).items()):
            if val is orig_axis_size:
                swap(mod, attr, fake_axis_size)
            elif val is orig_mesh_device_id:
                swap(mod, attr, fake_mesh_device_id)
    try:
        yield
    finally:
        for obj, attr, old in reversed(saved):
            setattr(obj, attr, old)


# ---------------------------------------------------------------------------
# Trace driver
# ---------------------------------------------------------------------------

def _grid_points(grid: tuple[int, ...]):
    """Row-major grid iteration, LAST dimension fastest — matching Mosaic's
    sequential ("arbitrary") grid semantics on TPU."""
    if not grid:
        return [()]
    return itertools.product(*(range(g) for g in grid))


def trace_kernel(spec: "_registry.TraceSpec", world: int) -> TraceResult:
    """Run ``spec.body`` once per rank per grid point under the patched
    sync surface and return the per-rank event logs + DMA records."""
    ranks = spec.ranks if spec.ranks is not None else world
    axes = getattr(spec, "axes", None)
    if axes:
        n = 1
        for _, size in axes:
            n *= size
        if n != ranks:
            raise CommTraceError(
                f"declared mesh {axes} covers {n} ranks; spec traces "
                f"{ranks}")
    tracer = Tracer(world=world, ranks=ranks, grid=spec.grid, axes=axes)
    for arg in spec.args:
        if isinstance(arg, _registry.Buf):
            for r in range(ranks):
                tracer.store[(arg.name, r)] = arg.make(r, world)

    def make_refs(rank: int):
        refs = []
        for arg in spec.args:
            if isinstance(arg, _registry.Buf):
                refs.append(FakeRef(tracer, arg.name, rank,
                                    tracer.store[(arg.name, rank)]))
            else:
                refs.append(FakeSem((arg.name,), arg.shape, arg.shape))
        return refs

    with patched_sync_surface(tracer):
        for rnd in (0, 1):
            tracer.recording = rnd == 1
            for rank in range(ranks):
                tracer.rank = rank
                refs = make_refs(rank)
                for pt in _grid_points(spec.grid):
                    tracer.grid_point = pt
                    spec.body(*refs, **dict(spec.kwargs))
    return TraceResult(world=world, ranks=ranks, logs=tracer.logs,
                       dmas=tracer.dmas, store=tracer.store,
                       oob=tracer.oob)
