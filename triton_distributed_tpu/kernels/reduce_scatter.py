"""ReduceScatter kernels over ICI remote DMA.

TPU-native analog of the reference's ``kernels/nvidia/reduce_scatter.py``
(882 LoC: ``ReduceScatter2DContext`` :45, intra-node CE/SM variants :284-:484,
per-node reducer :632). Two methods:

- **one-shot (scatter + local reduce)**: every rank pushes its chunk-for-rank-r
  directly into r's staging slot, then each rank reduces its ``world`` arrivals
  — the structure of the reference's intra-node scatter → local reduce
  (reduce_scatter.py:284,:632), with staging slots in HBM and the per-slot
  arrival signal carried by the DMA receive semaphore.
- **ring**: world-1 neighbor hops; at step s each rank adds its own
  contribution to the partial sum received from the left and forwards. Each
  ICI link carries each byte once (bandwidth-optimal for large chunks).

Accumulation is fp32 in VMEM regardless of wire dtype (the MXU/VPU-friendly
equivalent of the reference's fp16 accumulation concerns).

Per-device forms (``oneshot_reduce_scatter`` / ``ring_reduce_scatter``) are
composable inside ``shard_map``; the host wrapper ``reduce_scatter`` takes the
stacked ``(world, world*m, ...)`` convention and returns ``(world*m, ...)``
global sharded so device r owns segment r (= sum over devices' segment r).
"""

from __future__ import annotations

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


# ---------------------------------------------------------------------------
# One-shot: scatter chunks to owners, owners reduce.
# ---------------------------------------------------------------------------


def _oneshot_rs_kernel(x_ref, o_ref, staging, send_sems, recv_sems, copy_sem,
                       acc_ref, tmp_ref, out_vmem, *, axis: str, world: int,
                       br: int, probe=_probes.NULL):
    me = jax.lax.axis_index(axis)
    m = o_ref.shape[0]
    probe.enter(0, me, world)

    dl.barrier_all(axis)
    probe.sem_spin(world - 1)

    # Push chunk x[peer] into peer's staging slot for source ``me``.
    sends = []
    for i in range(world - 1):
        peer = jax.lax.rem(me + 1 + i, world)
        dma = common.remote_copy(
            x_ref.at[pl.ds(peer * m, m)],
            staging.at[common.peer_slot(me, peer)],
            send_sems.at[i], recv_sems.at[me], axis, peer, probe=probe)
        sends.append(dma)

    for src in range(world):
        @pl.when(src != me)
        def _wait(src=src):
            common.wait_recv(staging.at[common.peer_slot(src, me)],
                             recv_sems.at[src], probe=probe)

    # Fixed global reduce order 0..world-1 (own chunk read straight from
    # x_ref): deterministic, rank-independent bits (ADVICE r1); row-tiled.
    common.reduce_slots_tiled(
        x_ref, me * m, staging, world, me, o_ref, m=m, br=br, acc_ref=acc_ref,
        tmp_ref=tmp_ref, out_ref=out_vmem, copy_sem=copy_sem, probe=probe)
    for dma in sends:
        probe.dma_wait(o_ref)
        dma.wait_send()


# ---------------------------------------------------------------------------
# Ring
# ---------------------------------------------------------------------------


def _ring_rs_kernel(x_ref, o_ref, staging, send_hbm, send_sems, recv_sems,
                    copy_sem, acc_ref, tmp_ref, out_vmem, *, axis: str,
                    world: int, br: int, probe=_probes.NULL):
    me = jax.lax.axis_index(axis)
    m = o_ref.shape[0]
    right = jax.lax.rem(me + 1, world)
    probe.enter(0, me, world)

    dl.barrier_all(axis)
    probe.sem_spin(world - 1)

    def reduce_chunk(x_off, stage_idx, dst_ref, dst_off):
        common.reduce_rows_tiled(
            x_ref, x_off, staging, stage_idx, dst_ref, dst_off, m=m, br=br,
            acc_ref=acc_ref, tmp_ref=tmp_ref, out_ref=out_vmem,
            copy_sem=copy_sem, probe=probe)

    for s in range(world - 1):
        c = jax.lax.rem(me - s - 1 + world, world)  # chunk forwarded at step s
        if s > 0:
            # Partial sum of chunk c from the left (arrived at step s-1).
            common.wait_recv(staging.at[s - 1], recv_sems.at[s - 1],
                             probe=probe)
        reduce_chunk(c * m, s - 1 if s > 0 else None, send_hbm, 0)
        dma = common.remote_copy(
            send_hbm, staging.at[s],
            send_sems.at[s], recv_sems.at[s], axis, right, probe=probe)
        # send_hbm is rewritten next step: wait local drain now. The ring is
        # latency-bound by the recv dependency anyway (pipelining across
        # sub-chunks is the further optimization, as in the reference's
        # ring CE variants).
        probe.dma_wait(send_hbm)
        dma.wait_send()

    # Final arrival completes own segment: sum over all other ranks of chunk
    # ``me``, plus our own contribution.
    common.wait_recv(staging.at[world - 2], recv_sems.at[world - 2],
                     probe=probe)
    reduce_chunk(me * m, world - 2, o_ref, 0)


# ---------------------------------------------------------------------------
# Per-device entry points
# ---------------------------------------------------------------------------


def _rs_call(kernel, x_local, *, name: str, axis: str, interpret,
             collective_id: int, n_staging_key: str, probes: bool = False):
    world = _axis_size(axis)
    if world == 1:
        return (x_local, _probes.host_stub_buffer()) if probes else x_local
    if x_local.shape[0] % world:
        raise ValueError(f"leading dim {x_local.shape[0]} not divisible by world {world}")
    m = x_local.shape[0] // world
    rest = x_local.shape[1:]
    br = common.stage_row_tile(m, rest, x_local.dtype.itemsize)
    oneshot = n_staging_key == "oneshot"
    # HBM staging buffers are ANY-space OUTPUTS (discarded): Mosaic does not
    # allocate HBM scratch, and remote DMAs need stable per-device HBM
    # buffers — kernel arg order is unchanged (leading-scratch ->
    # trailing-output positions).
    out_shape = [jax.ShapeDtypeStruct((m, *rest), x_local.dtype),
                 jax.ShapeDtypeStruct((world - 1, m, *rest), x_local.dtype)]
    if not oneshot:
        out_shape.append(jax.ShapeDtypeStruct((m, *rest), x_local.dtype))
    scratch = [
        common.dma_sems(world),                            # send
        common.dma_sems(world),                            # recv
        pltpu.SemaphoreType.DMA(()),                       # local copies
        pltpu.VMEM((br, *rest), jnp.float32),              # accumulator tile
        pltpu.VMEM((br, *rest), x_local.dtype),            # copy-in tile
        pltpu.VMEM((br, *rest), x_local.dtype),            # cast-out tile
    ]
    body = functools.partial(kernel, axis=axis, world=world, br=br)
    out_specs = [common.hbm_spec()] * len(out_shape)
    if probes:
        n_base_out = len(out_shape)

        def body(*refs):
            # probe buffer rides as the LAST output, ordinal as LAST scratch
            ins, rest_refs = refs[:1], refs[1:]
            outs = rest_refs[:n_base_out]
            pbuf = rest_refs[n_base_out]
            scratch_refs = rest_refs[n_base_out + 1:-1]
            pord = rest_refs[-1]
            kernel(*ins, *outs, *scratch_refs, axis=axis, world=world, br=br,
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
        collective_id=collective_id,
        name=name,
        interpret=interpret,
    )(x_local)
    return (outs[0], outs[-1]) if probes else outs[0]


def oneshot_reduce_scatter(x_local, *, axis: str = "tp", interpret=None,
                           probes: bool = False):
    """Scatter+local-reduce RS of ``x_local (world*m, ...)`` → ``(m, ...)``:
    returns sum over ranks of segment ``me``. ``probes=True`` builds the
    instrumented variant and returns ``(out, probe_buf)``."""
    return _rs_call(_oneshot_rs_kernel, x_local,
                    name="reduce_scatter_one_shot", axis=axis,
                    interpret=interpret,
                    collective_id=common.collective_id_for("rs_oneshot"),
                    n_staging_key="oneshot", probes=probes)


def ring_reduce_scatter(x_local, *, axis: str = "tp", interpret=None,
                        probes: bool = False):
    """Bandwidth-optimal ring RS (see module docstring); ``probes=True`` →
    ``(out, probe_buf)``."""
    return _rs_call(_ring_rs_kernel, x_local, name="reduce_scatter_ring",
                    axis=axis, interpret=interpret,
                    collective_id=common.collective_id_for("rs_ring"),
                    n_staging_key="ring", probes=probes)


# ---------------------------------------------------------------------------
# Host-level wrapper
# ---------------------------------------------------------------------------


def reduce_scatter(x_stacked, *, mesh: Mesh | None = None, axis: str = "tp",
                   method: str = "auto", dcn_axis: str | None = None,
                   interpret=None):
    """Standalone reduce-scatter over a mesh axis.

    ``x_stacked``: global ``(world, world*m, ...)``, device ``r`` holding its
    full contribution ``[r]``. Returns global ``(world*m, ...)`` sharded
    ``P(axis)``: segment ``r`` = sum over devices of their segment ``r``.

    Pass ``dcn_axis`` on a multi-slice ``(dcn, ici)`` mesh: AUTO then
    dispatches to the hierarchical 2D method (reference 2D RS,
    reduce_scatter.py:45), with ``axis`` as the intra-slice axis. On that
    path the stacked leading dim (and the per-device contribution's
    segment count) is the TOTAL device count
    ``mesh.shape[dcn_axis] * mesh.shape[axis]`` (dcn-major rank order).
    """
    mesh = mesh or get_default_mesh()
    world = mesh.shape[axis]
    if method == "auto":
        if dcn_axis and mesh.shape.get(dcn_axis, 1) > 1:
            method = "ring_2d"
        else:
            # Model-driven crossover (runtime/perf_model.py): one-shot wins
            # on latency for small contributions, the ring on bandwidth.
            from triton_distributed_tpu.runtime import perf_model as pm

            per_dev = x_stacked.nbytes // world
            method = ("oneshot"
                      if pm.est_oneshot_reduce_scatter(per_dev, world)
                      <= pm.est_ring_reduce_scatter(per_dev, world)
                      else "ring")
    if method == "ring_2d":
        if dcn_axis is None:
            raise ValueError("method ring_2d needs dcn_axis (a (dcn, ici) "
                             "mesh; see runtime.mesh.make_2d_mesh)")
        from triton_distributed_tpu.kernels.collective_2d import (
            reduce_scatter_2d,
        )

        return reduce_scatter_2d(x_stacked, mesh=mesh, ici_axis=axis,
                                 dcn_axis=dcn_axis, interpret=interpret)
    if method not in ("oneshot", "ring"):
        raise ValueError(f"unknown reduce_scatter method {method!r}: "
                         f"expected 'auto', 'oneshot', 'ring', or 'ring_2d'")
    run = _build_rs(mesh, axis, method, interpret, x_stacked.ndim - 1)
    if not _ledger.active():  # ledger recording or resilience hooks
        return run(x_stacked).reshape(x_stacked.shape[1:])
    from triton_distributed_tpu.runtime import perf_model as pm

    per_dev = x_stacked.nbytes // world
    est = (pm.est_oneshot_reduce_scatter if method == "oneshot"
           else pm.est_ring_reduce_scatter)(per_dev, world)
    return _ledger.timed(
        lambda: run(x_stacked).reshape(x_stacked.shape[1:]),
        "reduce_scatter", axis=axis, world=world,
        nbytes=pm.wire_bytes_reduce_scatter(per_dev, world), method=method,
        est_s=est)


@functools.lru_cache(maxsize=None)
def _build_rs(mesh, axis, method, interpret, nd):
    """Jit-cached wrapper builder (see allgather._build_ag)."""
    per_device = oneshot_reduce_scatter if method == "oneshot" else ring_reduce_scatter

    def f(xs):  # xs: (1, world*m, ...)
        return per_device(xs[0], axis=axis, interpret=interpret)[None]

    return jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=P(axis, *([None] * nd)),
            out_specs=P(axis, *([None] * nd)),
            check_vma=False,
        )
    )


# ---------------------------------------------------------------------------
# Comm-safety analyzer registration (tools/comm_check.py; docs/analysis.md)
# ---------------------------------------------------------------------------

from triton_distributed_tpu.analysis import registry as _comm  # noqa: E402


@_comm.register("rs.oneshot")
def _comm_spec_oneshot_rs(world: int) -> "_comm.TraceSpec":
    m, rest = 8, (128,)
    return _comm.TraceSpec(
        body=_oneshot_rs_kernel,
        args=[
            _comm.Buf("x", (world * m, *rest)),
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


@_comm.register("rs.ring")
def _comm_spec_ring_rs(world: int) -> "_comm.TraceSpec":
    m, rest = 8, (128,)
    return _comm.TraceSpec(
        body=_ring_rs_kernel,
        args=[
            _comm.Buf("x", (world * m, *rest)),
            _comm.Buf("o", (m, *rest), covered=True),
            _comm.Buf("staging", (world - 1, m, *rest)),
            _comm.Buf("send_hbm", (m, *rest)),
            _comm.Sem("send_sems", (world - 1,)),
            _comm.Sem("recv_sems", (world - 1,)),
            _comm.Sem("copy_sem"),
            _comm.Buf("acc", (m, *rest), space="vmem"),
            _comm.Buf("tmp", (m, *rest), space="vmem"),
            _comm.Buf("out_vmem", (m, *rest), space="vmem"),
        ],
        kwargs=dict(axis="tp", world=world, br=m),
    )
