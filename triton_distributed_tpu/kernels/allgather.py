"""AllGather kernels over ICI remote DMA.

TPU-native analog of the reference's ``kernels/nvidia/allgather.py`` (593 LoC):
its ``AllGatherMethod`` enum (allgather.py:46 — Auto/All2All/Ring1D/Ring2D/
RingNuma2D) and the copy-engine push rings (``cp_engine_producer_all_gather_
intra_node`` allgather.py:263, per-segment ``set_signal``/``wait_eq``).

Design (not a translation):
- The reference drives allgather with host-issued ``cudaMemcpyAsync`` on comm
  streams, synchronized by signal cells in symmetric memory. On TPU the copy
  engine analog is the per-chip DMA engines, driven *from inside one Pallas
  kernel*: each device starts remote DMAs over ICI and waits per-segment
  receive semaphores — the semaphore IS the signal cell (language/shmem.py).
- ``Ring1D`` maps to the ICI torus wraparound ring: at step s every device
  forwards the chunk it received at step s-1 to its right neighbor; world-1
  steps, each link carries each chunk exactly once (bandwidth-optimal).
- ``All2All`` maps to direct pushes to every peer (world-1 concurrent DMAs;
  torus routing spreads them over links) — lower latency for small messages,
  the same trade the reference makes (allgather.py:46 method choice).
- 2D / NUMA variants become intra-slice ICI ring + inter-slice DCN; the DCN
  leg routes through XLA collectives (see SURVEY.md §5 backend mapping) and
  lands with multi-slice support.

Each kernel is exposed two ways:
- a *per-device* function (``ring_all_gather``/``a2a_all_gather``) callable
  inside any ``shard_map`` — the composable form used by overlap ops;
- a host-level ``all_gather(x_stacked, mesh=...)`` wrapper for standalone use
  and tests, taking the symmetric-workspace stacked convention
  ``(world, *local)`` (runtime/symm.py) and returning the gathered array.
"""

from __future__ import annotations

import enum
import functools

import jax
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.compat import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.language import primitives as dl
from triton_distributed_tpu.kernels import common
from triton_distributed_tpu.kernels import probes as _probes
from triton_distributed_tpu.obs import comm_ledger as _ledger
from triton_distributed_tpu.runtime.mesh import get_default_mesh


class AllGatherMethod(enum.Enum):
    """Reference parity: allgather.py:46 (Auto/All2All/Ring1D + the 2D
    inter-node variant; NUMA-2D has no TPU analog — ICI is symmetric)."""

    AUTO = "auto"
    ALL2ALL = "all2all"
    RING_1D = "ring_1d"
    RING_2D = "ring_2d"   # intra-slice ring + DCN leg (collective_2d.py)
    LL = "ll"             # persistent-staging low-latency (ll_allgather.py)


def choose_all_gather_method(world: int, nbytes: int,
                             num_slices: int = 1) -> AllGatherMethod:
    """Model-driven dispatch (analog of ``get_auto_all_gather_method``,
    allgather.py:57, backed by the comm_perf_model analogs in
    ``runtime/perf_model.py``): a DCN-spanning mesh must go hierarchical
    (2D); otherwise direct push (one hop, world-1 concurrent DMAs) vs ring
    (each link carries each byte once) by estimated time — the crossover is
    derived from link bandwidth/degree and hop latency, not a hardcoded
    byte threshold. ``num_slices`` comes from ``Topology.num_slices``."""
    from triton_distributed_tpu.runtime import perf_model as pm

    if num_slices > 1:
        return AllGatherMethod.RING_2D
    if world <= 2:
        return AllGatherMethod.ALL2ALL  # one peer: push IS the ring, no barrier needed
    push = pm.est_push_all_gather(nbytes, world)
    ring = pm.est_ring_all_gather(nbytes, world)
    return AllGatherMethod.ALL2ALL if push <= ring else AllGatherMethod.RING_1D


# ---------------------------------------------------------------------------
# Ring 1D
# ---------------------------------------------------------------------------


def _ring_ag_kernel(x_ref, o_ref, send_sems, recv_sems, copy_sem, *, axis: str,
                    world: int, probe=_probes.NULL):
    me = jax.lax.axis_index(axis)
    m = x_ref.shape[0]
    right = jax.lax.rem(me + 1, world)
    probe.enter(0, me, world)

    # All devices must have entered the kernel (so o_ref is live everywhere)
    # before anyone pushes into a peer's o_ref.
    dl.barrier_all(axis)
    probe.sem_spin(world - 1)

    # Own shard into its slot.
    common.local_copy(x_ref, o_ref.at[pl.ds(me * m, m)], copy_sem,
                      probe=probe)

    sends = []
    for s in range(world - 1):
        src = jax.lax.rem(me - s + world, world)  # chunk forwarded at step s
        dma = common.remote_copy(
            o_ref.at[pl.ds(src * m, m)], o_ref.at[pl.ds(src * m, m)],
            send_sems.at[s], recv_sems.at[s], axis, right, probe=probe)
        sends.append(dma)
        # Chunk (me-1-s) arrives from the left at step s; it is what we
        # forward at step s+1, so the wait doubles as the send dependency.
        rsrc = jax.lax.rem(me - 1 - s + world, world)
        common.wait_recv(o_ref.at[pl.ds(rsrc * m, m)], recv_sems.at[s],
                         probe=probe)
    for dma in sends:
        probe.dma_wait(x_ref)
        dma.wait_send()


# ---------------------------------------------------------------------------
# All2All (direct push)
# ---------------------------------------------------------------------------


def _a2a_ag_kernel(x_ref, o_ref, send_sems, recv_sems, copy_sem, *, axis: str,
                   world: int, probe=_probes.NULL):
    me = jax.lax.axis_index(axis)
    m = x_ref.shape[0]
    probe.enter(0, me, world)

    dl.barrier_all(axis)
    probe.sem_spin(world - 1)

    sends = []
    for i in range(world - 1):
        peer = jax.lax.rem(me + 1 + i, world)
        # Receiver waits slot ``src``; we are src ``me`` on every peer.
        dma = common.remote_copy(
            x_ref, o_ref.at[pl.ds(me * m, m)],
            send_sems.at[i], recv_sems.at[me], axis, peer, probe=probe)
        sends.append(dma)

    common.local_copy(x_ref, o_ref.at[pl.ds(me * m, m)], copy_sem,
                      probe=probe)

    for i in range(world - 1):
        src = jax.lax.rem(me + 1 + i, world)
        common.wait_recv(o_ref.at[pl.ds(src * m, m)], recv_sems.at[src],
                         probe=probe)
    for dma in sends:
        probe.dma_wait(x_ref)
        dma.wait_send()


# ---------------------------------------------------------------------------
# Per-device entry points (usable inside shard_map)
# ---------------------------------------------------------------------------


def _ag_call(kernel, x_local, *, name: str, axis: str, interpret,
             collective_id: int, probes: bool = False):
    world = _axis_size(axis)
    if world == 1:
        return (x_local, _probes.host_stub_buffer()) if probes else x_local
    m = x_local.shape[0]
    body = functools.partial(kernel, axis=axis, world=world)
    out_shape = jax.ShapeDtypeStruct((world * m, *x_local.shape[1:]),
                                     x_local.dtype)
    out_specs = common.hbm_spec()
    scratch = [
        common.dma_sems(world - 1),   # send
        common.dma_sems(world),       # recv (slot-per-src; ring uses [:world-1])
        pltpu.SemaphoreType.DMA(()),  # local copy
    ]
    if probes:
        # Separate build: probe buffer as last output, ordinal as last
        # scratch (the disabled build above stays byte-identical).
        def body(x_ref, o_ref, pbuf, send_sems, recv_sems, copy_sem, pord):
            kernel(x_ref, o_ref, send_sems, recv_sems, copy_sem, axis=axis,
                   world=world, probe=_probes.Probe(pbuf, pord, n_steps=1))

        out_shape = [out_shape, _probes.out_shape(1)]
        out_specs = [out_specs, _probes.out_spec()]
        scratch = scratch + [_probes.ord_scratch()]
    return common.make_pallas_call(
        body,
        out_shape=out_shape,
        in_specs=[common.any_spec()],
        out_specs=out_specs,
        scratch_shapes=scratch,
        collective_id=collective_id,
        name=name,
        interpret=interpret,
    )(x_local)


def ring_all_gather(x_local, *, axis: str = "tp", interpret=None,
                    probes: bool = False):
    """Bandwidth-optimal ring allgather of ``x_local (m, ...)`` along ``axis``
    → ``(world*m, ...)``, segment ``r`` holding rank ``r``'s shard.
    ``probes=True`` builds the instrumented variant and returns
    ``(out, probe_buf)`` (see kernels/probes.py)."""
    return _ag_call(_ring_ag_kernel, x_local, name="allgather_ring",
                    axis=axis, interpret=interpret,
                    collective_id=common.collective_id_for("ag_ring"),
                    probes=probes)


def a2a_all_gather(x_local, *, axis: str = "tp", interpret=None,
                   probes: bool = False):
    """Latency-optimal direct-push allgather (see module docstring);
    ``probes=True`` → ``(out, probe_buf)``."""
    return _ag_call(_a2a_ag_kernel, x_local, name="allgather_push",
                    axis=axis, interpret=interpret,
                    collective_id=common.collective_id_for("ag_a2a"),
                    probes=probes)


# ---------------------------------------------------------------------------
# Host-level wrapper
# ---------------------------------------------------------------------------


def all_gather(x_stacked, *, mesh: Mesh | None = None, axis: str = "tp",
               method: AllGatherMethod | str = AllGatherMethod.AUTO,
               dcn_axis: str | None = None, interpret=None):
    """Standalone allgather over a mesh axis.

    ``x_stacked``: global ``(world, *local)`` array, device ``r`` owning slice
    ``[r]`` (the symmetric-workspace convention). Returns the gathered
    ``(world * local[0], *local[1:])`` array (replicated).

    Pass ``dcn_axis`` on a multi-slice ``(dcn, ici)`` mesh (see
    ``runtime.mesh.make_2d_mesh``): AUTO then dispatches to the hierarchical
    2D method, with ``axis`` as the intra-slice (ICI) axis. On that path the
    stacked leading dim is the TOTAL device count
    ``mesh.shape[dcn_axis] * mesh.shape[axis]`` (dcn-major rank order).
    """
    mesh = mesh or get_default_mesh()
    world = mesh.shape[axis]
    if isinstance(method, str):
        method = AllGatherMethod(method)
    if method is AllGatherMethod.AUTO:
        num_slices = mesh.shape.get(dcn_axis, 1) if dcn_axis else 1
        method = choose_all_gather_method(world, x_stacked.nbytes // world,
                                          num_slices)
    if method is AllGatherMethod.RING_2D:
        if dcn_axis is None:
            raise ValueError("method ring_2d needs dcn_axis (a (dcn, ici) "
                             "mesh; see runtime.mesh.make_2d_mesh)")
        from triton_distributed_tpu.kernels.collective_2d import all_gather_2d

        return all_gather_2d(x_stacked, mesh=mesh, ici_axis=axis,
                             dcn_axis=dcn_axis, interpret=interpret)
    run = _build_ag(mesh, axis, method, interpret, x_stacked.ndim - 1)
    if not _ledger.active():  # ledger recording or resilience hooks
        return run(x_stacked)
    from triton_distributed_tpu.runtime import perf_model as pm

    shard = x_stacked.nbytes // world
    est = (pm.est_push_all_gather if method is AllGatherMethod.ALL2ALL
           else pm.est_ring_all_gather)(shard, world)
    return _ledger.timed(
        lambda: run(x_stacked), "all_gather", axis=axis, world=world,
        nbytes=pm.wire_bytes_all_gather(shard, world), method=method.value,
        est_s=est)


@functools.lru_cache(maxsize=None)
def _build_ag(mesh, axis, method, interpret, nd):
    """Jit-cached wrapper builder (jit caches by callable identity, so the
    callable must be built once per (mesh, axis, method) — not per call)."""
    per_device = ring_all_gather if method is AllGatherMethod.RING_1D else a2a_all_gather

    def f(xs):  # xs: (1, *local)
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


@_comm.register("ag.ring")
def _comm_spec_ring(world: int) -> "_comm.TraceSpec":
    m, rest = 8, (128,)
    return _comm.TraceSpec(
        body=_ring_ag_kernel,
        args=[
            _comm.Buf("x", (m, *rest)),
            _comm.Buf("o", (world * m, *rest), covered=True),
            _comm.Sem("send_sems", (world - 1,)),
            _comm.Sem("recv_sems", (world,)),
            _comm.Sem("copy_sem"),
        ],
        kwargs=dict(axis="tp", world=world),
    )


@_comm.register("ag.a2a")
def _comm_spec_a2a(world: int) -> "_comm.TraceSpec":
    m, rest = 8, (128,)
    return _comm.TraceSpec(
        body=_a2a_ag_kernel,
        args=[
            _comm.Buf("x", (m, *rest)),
            _comm.Buf("o", (world * m, *rest), covered=True),
            _comm.Sem("send_sems", (world - 1,)),
            _comm.Sem("recv_sems", (world,)),
            _comm.Sem("copy_sem"),
        ],
        kwargs=dict(axis="tp", world=world),
    )
