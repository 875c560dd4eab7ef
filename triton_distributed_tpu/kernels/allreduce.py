"""AllReduce kernels over ICI remote DMA.

TPU-native analog of the reference's ``kernels/nvidia/allreduce.py`` (1102 LoC:
one-shot push :364, two-shot :476, double-tree :223, multimem :633) and its
method enum (``kernels/allreduce.py:8-31``).

Method mapping (hardware-driven, per SURVEY.md §7 hard-part 3):
- **one-shot**: every rank pushes its full buffer to all peers' staging; each
  rank reduces locally. Latency-optimal for small buffers — the role the
  reference's one-shot/multimem variants play. (No NVLink-SHARP/multimem
  analog exists on ICI, so the multicast variants collapse into this.)
- **two-shot**: ring reduce-scatter then ring allgather, fused in one Pallas
  kernel so the AG leg reuses the RS kernel's semaphores and staging —
  bandwidth-optimal (2·(world-1)/world · bytes per link), the same structure
  as the reference's two-shot (:476).
- **double-tree**: a latency/bandwidth middle ground on NVLink; on a wrapped
  ICI torus the ring already achieves link-optimality, so the tree variant is
  intentionally not carried over.

Per-device forms compose inside ``shard_map``; host wrapper ``all_reduce``
takes stacked ``(world, m, ...)`` inputs and returns the reduced ``(m, ...)``.
"""

from __future__ import annotations

import enum
import functools

import jax
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.compat import shard_map
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.language import primitives as dl
from triton_distributed_tpu.kernels import common
from triton_distributed_tpu.kernels import probes as _probes
from triton_distributed_tpu.obs import comm_ledger as _ledger
from triton_distributed_tpu.runtime.mesh import get_default_mesh


class AllReduceMethod(enum.Enum):
    """Reference parity: kernels/allreduce.py:8-31 (multimem/double-tree fold
    into these two on ICI — see module docstring)."""

    AUTO = "auto"
    ONE_SHOT = "one_shot"
    TWO_SHOT = "two_shot"


def choose_all_reduce_method(world: int, nbytes: int, leading_dim: int) -> AllReduceMethod:
    """Model-driven dispatch (``runtime/perf_model.py``; reference auto
    dispatch + comm_perf_model): one-shot moves (world-1)·n bytes out per
    rank in one hop; two-shot moves 2·(world-1)/world·n per link over
    2(world-1) hops — the crossover falls out of link bandwidth/degree, hop
    latency and the HBM reduce passes, not a hardcoded threshold. Two-shot
    additionally needs the leading dim divisible by world."""
    from triton_distributed_tpu.runtime import perf_model as pm

    if world <= 2 or leading_dim % world:
        return AllReduceMethod.ONE_SHOT
    one = pm.est_oneshot_all_reduce(nbytes, world)
    two = pm.est_twoshot_all_reduce(nbytes, world)
    return AllReduceMethod.ONE_SHOT if one <= two else AllReduceMethod.TWO_SHOT


# ---------------------------------------------------------------------------
# One-shot
# ---------------------------------------------------------------------------


def _oneshot_ar_kernel(x_ref, o_ref, staging, send_sems, recv_sems, copy_sem,
                       acc_ref, tmp_ref, out_vmem, *, axis: str, world: int,
                       br: int, probe=_probes.NULL):
    me = jax.lax.axis_index(axis)
    m = x_ref.shape[0]
    probe.enter(0, me, world)

    dl.barrier_all(axis)
    probe.sem_spin(world - 1)

    sends = []
    for i in range(world - 1):
        peer = jax.lax.rem(me + 1 + i, world)
        dma = common.remote_copy(
            x_ref, staging.at[common.peer_slot(me, peer)],
            send_sems.at[i], recv_sems.at[me], axis, peer, probe=probe)
        sends.append(dma)

    for src in range(world):
        @pl.when(src != me)
        def _wait(src=src):
            common.wait_recv(staging.at[common.peer_slot(src, me)],
                             recv_sems.at[src], probe=probe)

    # Fixed global reduce order 0..world-1 (own contribution read straight
    # from x_ref at its slot) — the replicated output is bitwise identical
    # across ranks (ADVICE r1: rank-relative order diverged); row-tiled VMEM.
    common.reduce_slots_tiled(
        x_ref, 0, staging, world, me, o_ref, m=m, br=br, acc_ref=acc_ref,
        tmp_ref=tmp_ref, out_ref=out_vmem, copy_sem=copy_sem, probe=probe)
    for dma in sends:
        probe.dma_wait(x_ref)
        dma.wait_send()


def oneshot_all_reduce(x_local, *, axis: str = "tp", interpret=None,
                       probes: bool = False):
    """Latency-optimal allreduce of ``x_local (m, ...)`` along ``axis``.
    ``probes=True`` builds the instrumented variant and returns
    ``(out, probe_buf)`` (see kernels/probes.py)."""
    world = _axis_size(axis)
    if world == 1:
        return (x_local, _probes.host_stub_buffer()) if probes else x_local
    shape = x_local.shape
    rest = shape[1:]
    from triton_distributed_tpu.runtime import perf_model as pm

    # A series of its own beside the host wrapper's "one_shot", which
    # times the same traffic when it is the caller.
    _ledger.record_traced(
        "all_reduce", axis=axis, world=world, method="one_shot_device",
        nbytes=pm.wire_bytes_all_reduce(
            x_local.size * x_local.dtype.itemsize, world,
            AllReduceMethod.ONE_SHOT.value))
    br = common.stage_row_tile(shape[0], rest, x_local.dtype.itemsize)
    body = functools.partial(_oneshot_ar_kernel, axis=axis, world=world,
                             br=br)
    # Arrival staging is an ANY-space OUTPUT (discarded): Mosaic has no HBM
    # scratch; kernel arg order unchanged (first-scratch -> last-output).
    out_shape = [jax.ShapeDtypeStruct(shape, x_local.dtype),
                 jax.ShapeDtypeStruct((world - 1, *shape), x_local.dtype)]
    out_specs = [common.hbm_spec()] * 2
    scratch = [
        common.dma_sems(world),
        common.dma_sems(world),
        pltpu.SemaphoreType.DMA(()),
        pltpu.VMEM((br, *rest), jnp.float32),
        pltpu.VMEM((br, *rest), x_local.dtype),
        pltpu.VMEM((br, *rest), x_local.dtype),
    ]
    if probes:
        def body(x_ref, o_ref, staging, pbuf, send_sems, recv_sems, copy_sem,
                 acc_ref, tmp_ref, out_vmem, pord):
            _oneshot_ar_kernel(
                x_ref, o_ref, staging, send_sems, recv_sems, copy_sem,
                acc_ref, tmp_ref, out_vmem, axis=axis, world=world, br=br,
                probe=_probes.Probe(pbuf, pord, n_steps=1))

        out_shape = out_shape + [_probes.out_shape(1)]
        out_specs = out_specs + [_probes.out_spec()]
        scratch = scratch + [_probes.ord_scratch()]
    outs = common.make_pallas_call(
        body,
        out_shape=out_shape,
        in_specs=[common.any_spec()],
        out_specs=out_specs,
        scratch_shapes=scratch,
        collective_id=common.collective_id_for("ar_oneshot"),
        name="allreduce_one_shot",
        interpret=interpret,
    )(x_local)
    return (outs[0], outs[2]) if probes else outs[0]


def _oneshot_ar_loopback_kernel(x_ref, o_ref, staging, seg_sems, copy_sem,
                                acc_ref, tmp_ref, out_vmem, *, world: int,
                                br: int):
    m = x_ref.shape[0]
    # The world-1 peer pushes, through the local DMA engine: same staging
    # buffer, same per-source semaphores, same arrival waits.
    for i in range(world - 1):
        pltpu.make_async_copy(x_ref, staging.at[i], seg_sems.at[i]).start()
    for i in range(world - 1):
        common.wait_recv(staging.at[i], seg_sems.at[i])
    common.reduce_slots_tiled(
        x_ref, 0, staging, world, jnp.int32(0), o_ref, m=m, br=br,
        acc_ref=acc_ref, tmp_ref=tmp_ref, out_ref=out_vmem,
        copy_sem=copy_sem)


def oneshot_ar_loopback(x, *, world: int = 8, interpret=None):
    """Single-chip SELF-LOOPBACK one-shot allreduce: the full latency-path
    machinery of ``oneshot_all_reduce`` — staging writes, per-source
    arrival waits, fixed-order row-tiled fp32 fold — with the world-1 ICI
    pushes replaced by local DMA copies (every slot carries this chip's
    own buffer, so the result is ``world * x`` — deterministic and
    testable). The small-M AR-mode bench arm measures it to price the
    machinery the reference fuses after its decode-regime GEMMs
    (e2e_dense.md:33-37; VERDICT r3 missing #4)."""
    shape = x.shape
    rest = shape[1:]
    br = common.stage_row_tile(shape[0], rest, x.dtype.itemsize)
    return common.make_pallas_call(
        functools.partial(_oneshot_ar_loopback_kernel, world=world, br=br),
        out_shape=[jax.ShapeDtypeStruct(shape, x.dtype),
                   jax.ShapeDtypeStruct((world - 1, *shape), x.dtype)],
        in_specs=[common.any_spec()],
        out_specs=[common.hbm_spec()] * 2,
        scratch_shapes=[
            common.dma_sems(world - 1),
            pltpu.SemaphoreType.DMA(()),
            pltpu.VMEM((br, *rest), jnp.float32),
            pltpu.VMEM((br, *rest), x.dtype),
            pltpu.VMEM((br, *rest), x.dtype),
        ],
        collective_id=None,
        name="allreduce_one_shot_loopback",
        interpret=interpret,
    )(x)[0]


# ---------------------------------------------------------------------------
# Two-shot: fused ring RS + ring AG in one kernel.
# ---------------------------------------------------------------------------


def _twoshot_ar_kernel(x_ref, o_ref, staging, send_hbm, send_sems, recv_sems,
                       ag_send_sems, ag_recv_sems, copy_sem, acc_ref, tmp_ref,
                       out_vmem, *, axis: str, world: int, br: int):
    me = jax.lax.axis_index(axis)
    m = x_ref.shape[0] // world
    right = jax.lax.rem(me + 1, world)

    dl.barrier_all(axis)

    def reduce_chunk(x_off, stage_idx, dst_ref, dst_off):
        common.reduce_rows_tiled(
            x_ref, x_off, staging, stage_idx, dst_ref, dst_off, m=m, br=br,
            acc_ref=acc_ref, tmp_ref=tmp_ref, out_ref=out_vmem,
            copy_sem=copy_sem)

    # --- reduce-scatter leg (ring; see reduce_scatter._ring_rs_kernel) ---
    for s in range(world - 1):
        c = jax.lax.rem(me - s - 1 + world, world)
        if s > 0:
            common.wait_recv(staging.at[s - 1], recv_sems.at[s - 1])
        reduce_chunk(c * m, s - 1 if s > 0 else None, send_hbm, 0)
        dma = common.remote_copy(
            send_hbm, staging.at[s],
            send_sems.at[s], recv_sems.at[s], axis, right)
        dma.wait_send()

    common.wait_recv(staging.at[world - 2], recv_sems.at[world - 2])
    # Own fully-reduced segment into place.
    reduce_chunk(me * m, world - 2, o_ref, me * m)

    # --- allgather leg (ring; see allgather._ring_ag_kernel) ---
    sends = []
    for s in range(world - 1):
        src = jax.lax.rem(me - s + world, world)
        dma = common.remote_copy(
            o_ref.at[pl.ds(src * m, m)], o_ref.at[pl.ds(src * m, m)],
            ag_send_sems.at[s], ag_recv_sems.at[s], axis, right)
        sends.append(dma)
        rsrc = jax.lax.rem(me - 1 - s + world, world)
        common.wait_recv(o_ref.at[pl.ds(rsrc * m, m)], ag_recv_sems.at[s])
    for dma in sends:
        dma.wait_send()


def twoshot_all_reduce(x_local, *, axis: str = "tp", interpret=None):
    """Bandwidth-optimal allreduce (ring RS + ring AG fused in one kernel).
    Requires ``x_local.shape[0]`` divisible by world."""
    world = _axis_size(axis)
    if world == 1:
        return x_local
    if x_local.shape[0] % world:
        raise ValueError(
            f"two-shot allreduce needs leading dim {x_local.shape[0]} divisible "
            f"by world {world}; use one-shot or pad")
    shape = x_local.shape
    m = shape[0] // world
    rest = shape[1:]
    br = common.stage_row_tile(m, rest, x_local.dtype.itemsize)
    # Staging buffers are ANY-space OUTPUTS (discarded) — see one-shot.
    return common.make_pallas_call(
        functools.partial(_twoshot_ar_kernel, axis=axis, world=world, br=br),
        out_shape=[
            jax.ShapeDtypeStruct(shape, x_local.dtype),
            jax.ShapeDtypeStruct((world - 1, m, *rest), x_local.dtype),
            jax.ShapeDtypeStruct((m, *rest), x_local.dtype),  # ring send
        ],
        in_specs=[common.any_spec()],
        out_specs=[common.hbm_spec()] * 3,
        scratch_shapes=[
            common.dma_sems(world - 1),
            common.dma_sems(world - 1),
            common.dma_sems(world - 1),
            common.dma_sems(world - 1),
            pltpu.SemaphoreType.DMA(()),
            pltpu.VMEM((br, *rest), jnp.float32),
            pltpu.VMEM((br, *rest), x_local.dtype),
            pltpu.VMEM((br, *rest), x_local.dtype),
        ],
        collective_id=common.collective_id_for("ar_twoshot"),
        name="allreduce_two_shot",
        interpret=interpret,
    )(x_local)[0]


# ---------------------------------------------------------------------------
# Host-level wrapper
# ---------------------------------------------------------------------------


def all_reduce(x_stacked, *, mesh: Mesh | None = None, axis: str = "tp",
               method: AllReduceMethod | str = AllReduceMethod.AUTO,
               interpret=None):
    """Standalone allreduce over a mesh axis.

    ``x_stacked``: global ``(world, m, ...)``, device ``r`` holding its
    contribution ``[r]``. Returns the reduced ``(m, ...)`` (replicated).
    """
    mesh = mesh or get_default_mesh()
    world = mesh.shape[axis]
    if isinstance(method, str):
        method = AllReduceMethod(method)
    if method is AllReduceMethod.AUTO:
        method = choose_all_reduce_method(
            world, x_stacked.nbytes // world, x_stacked.shape[1])
    run = _build_ar(mesh, axis, method, interpret, x_stacked.ndim - 1)
    if not _ledger.active():  # ledger recording or resilience hooks
        return run(x_stacked)
    from triton_distributed_tpu.runtime import perf_model as pm

    nbytes = x_stacked.nbytes // world
    est = (pm.est_oneshot_all_reduce if method is AllReduceMethod.ONE_SHOT
           else pm.est_twoshot_all_reduce)(nbytes, world)
    return _ledger.timed(
        lambda: run(x_stacked), "all_reduce", axis=axis, world=world,
        nbytes=pm.wire_bytes_all_reduce(nbytes, world, method.value),
        method=method.value, est_s=est)


@functools.lru_cache(maxsize=None)
def _build_ar(mesh, axis, method, interpret, nd):
    """Jit-cached wrapper builder (see allgather._build_ag)."""
    per_device = oneshot_all_reduce if method is AllReduceMethod.ONE_SHOT \
        else twoshot_all_reduce

    def f(xs):
        return per_device(xs[0], axis=axis, interpret=interpret)

    return jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=P(axis, *([None] * nd)),
            out_specs=P(*([None] * nd)),
            check_vma=False,
        )
    )


# ---------------------------------------------------------------------------
# Comm-safety analyzer registration (tools/comm_check.py; docs/analysis.md)
# ---------------------------------------------------------------------------

from triton_distributed_tpu.analysis import registry as _comm  # noqa: E402

_COMM_M, _COMM_REST = 8, (128,)


@_comm.register("ar.oneshot")
def _comm_spec_oneshot(world: int) -> "_comm.TraceSpec":
    m, rest = _COMM_M, _COMM_REST
    return _comm.TraceSpec(
        body=_oneshot_ar_kernel,
        args=[
            _comm.Buf("x", (m, *rest)),
            _comm.Buf("o", (m, *rest), covered=True),
            _comm.Buf("staging", (world - 1, m, *rest)),
            _comm.Sem("send_sems", (world,)),
            _comm.Sem("recv_sems", (world,)),
            _comm.Sem("copy_sem"),
            _comm.Buf("acc", (m, *rest), space="vmem"),
            _comm.Buf("tmp", (m, *rest), space="vmem"),
            _comm.Buf("out_vmem", (m, *rest), space="vmem"),
        ],
        kwargs=dict(axis="tp", world=world, br=m),
    )


@_comm.register("ar.oneshot_loopback")
def _comm_spec_oneshot_loopback(world: int) -> "_comm.TraceSpec":
    m, rest = _COMM_M, _COMM_REST
    return _comm.TraceSpec(
        body=_oneshot_ar_loopback_kernel,
        ranks=1,  # single-chip self-loopback: world slots on one rank
        args=[
            _comm.Buf("x", (m, *rest)),
            _comm.Buf("o", (m, *rest), covered=True),
            _comm.Buf("staging", (world - 1, m, *rest)),
            _comm.Sem("seg_sems", (world - 1,)),
            _comm.Sem("copy_sem"),
            _comm.Buf("acc", (m, *rest), space="vmem"),
            _comm.Buf("tmp", (m, *rest), space="vmem"),
            _comm.Buf("out_vmem", (m, *rest), space="vmem"),
        ],
        kwargs=dict(world=world, br=m),
    )


@_comm.register("ar.twoshot")
def _comm_spec_twoshot(world: int) -> "_comm.TraceSpec":
    m, rest = _COMM_M, _COMM_REST
    return _comm.TraceSpec(
        body=_twoshot_ar_kernel,
        args=[
            _comm.Buf("x", (world * m, *rest)),
            _comm.Buf("o", (world * m, *rest), covered=True),
            _comm.Buf("staging", (world - 1, m, *rest)),
            _comm.Buf("send_hbm", (m, *rest)),
            _comm.Sem("send_sems", (world - 1,)),
            _comm.Sem("recv_sems", (world - 1,)),
            _comm.Sem("ag_send_sems", (world - 1,)),
            _comm.Sem("ag_recv_sems", (world - 1,)),
            _comm.Sem("copy_sem"),
            _comm.Buf("acc", (m, *rest), space="vmem"),
            _comm.Buf("tmp", (m, *rest), space="vmem"),
            _comm.Buf("out_vmem", (m, *rest), space="vmem"),
        ],
        kwargs=dict(axis="tp", world=world, br=m),
    )
