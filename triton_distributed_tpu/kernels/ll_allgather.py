"""Low-latency allgather for small (decode-shape) messages.

TPU-native analog of the reference's ``low_latency_allgather.py`` (994 LoC:
LL protocol ``_pack_ll_block``/``_recv_ll_block`` :549/:531, staging
double-buffered by ``signal_target``, ``FastAllGatherContext`` :780): the
decode-latency workhorse under distributed flash-decode.

What the LL protocol buys the reference is removing per-call
synchronization from the critical path: flag-in-data packing means a
receiver can consume a slot the moment the flag matches the current epoch,
and epoch-rotated flags make slot reuse safe WITHOUT a barrier between
calls. The TPU translation keeps the two load-bearing ideas and drops the
flag packing (an epoch-parity-indexed receive semaphore is a per-transfer
arrival flag bound to its epoch — no byte-level polling needed):

- **Persistent symmetric staging** (``runtime/symm.py`` workspaces): the
  receive buffer is allocated ONCE and threaded through every call as an
  input/output-aliased array, so it is permanently live on every device —
  peers can push into it at any time without an entry barrier (a fresh
  scratch buffer would need the barrier the plain ``a2a_all_gather`` pays).
- **Double-buffering by epoch parity** (the ``signal_target`` rotation,
  low_latency_allgather.py:531): epoch ``e`` writes slot ``e % 2``. Device
  A entering call N implies A finished call N-1, which implies it received
  every peer's N-1 push, which implies every peer entered N-1 and thus
  finished N-2 — so the slot written at N (parity of N-2) is no longer
  being read anywhere. The allgather's own data dependence chain carries
  the synchronization across calls; no barrier, no ack round-trip.

Per-call cost vs ``a2a_all_gather``: world-1 concurrent DMAs + one local
copy per segment, and NO ``barrier_all`` (which costs a full
signal/wait round-trip before any payload moves) — the latency win for
repeated small-message calls. Large messages should keep using the
ring (bandwidth-optimal).
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

from triton_distributed_tpu.kernels import common
from triton_distributed_tpu.obs import comm_ledger as _ledger
from triton_distributed_tpu.runtime.mesh import get_default_mesh
from triton_distributed_tpu.runtime.platform import resolve_interpret
from triton_distributed_tpu.runtime import symm


def _ll_ag_kernel(p_ref, x_ref, staging_ref, o_ref, staging_out, send_sems,
                  recv_sems, copy_sem, *, axis: str, world: int):
    del staging_out  # aliased with staging_ref; peers write it remotely
    me = jax.lax.axis_index(axis)
    m = x_ref.shape[0]
    p = p_ref[0]

    # Push our shard into every peer's CURRENT-parity staging slot. The
    # staging array is input/output-aliased persistent state — live on every
    # device before this kernel even starts, so no entry barrier is needed.
    #
    # Recv semaphores are indexed by (epoch parity, source): dma.wait_send()
    # only guarantees the LOCAL buffer drained, so a sender may enter epoch N
    # while its N-1 push is still in flight, and two ICI DMAs to the same
    # receiver are unordered — a shared per-source semaphore would let the
    # epoch-N arrival satisfy the receiver's epoch-N-1 wait. Parity-tagged
    # semaphores re-bind each wait to its epoch (the reference's
    # signal_wait_until(CMP_EQ, signal_target) epoch check,
    # low_latency_allgather.py:531); the double-buffer argument above bounds
    # skew to <2 calls, so parity is enough.
    sends = []
    for i in range(world - 1):
        peer = jax.lax.rem(me + 1 + i, world)
        dma = common.remote_copy(
            x_ref, staging_ref.at[p, common.peer_slot(me, peer)],
            send_sems.at[i], recv_sems.at[p, me], axis, peer)
        sends.append(dma)

    # Own shard straight into the output.
    common.local_copy(x_ref, o_ref.at[pl.ds(me * m, m)], copy_sem)

    # Consume arrivals: wait each source's DMA, copy its slot to the output.
    for src in range(world):
        @pl.when(src != me)
        def _consume(src=src):
            slot = common.peer_slot(src, me)
            common.wait_recv(staging_ref.at[p, slot], recv_sems.at[p, src])
            common.local_copy(staging_ref.at[p, slot],
                              o_ref.at[pl.ds(src * m, m)], copy_sem)
    for dma in sends:
        dma.wait_send()


def ll_all_gather_device(x_local, staging, epoch, *, axis: str = "tp",
                         interpret=None):
    """Per-device low-latency allgather (composable inside shard_map).

    x_local (m, ...); staging (2, world-1, m, ...) — this device's
    persistent receive buffer (see ``make_ll_staging``); epoch () int32 —
    the call counter driving slot parity. Returns (gathered (world*m, ...),
    staging) — thread the returned staging (same buffer, aliased) into the
    next call."""
    world = _axis_size(axis)
    if world == 1:
        return x_local, staging
    m = x_local.shape[0]
    p = (epoch % 2).astype(jnp.int32).reshape(1)
    out, staging = pl.pallas_call(
        functools.partial(_ll_ag_kernel, axis=axis, world=world),
        out_shape=[
            jax.ShapeDtypeStruct((world * m, *x_local.shape[1:]),
                                 x_local.dtype),
            jax.ShapeDtypeStruct(staging.shape, staging.dtype),
        ],
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            common.any_spec(),
            common.any_spec(),
        ],
        out_specs=[common.hbm_spec(), common.hbm_spec()],
        input_output_aliases={2: 1},
        scratch_shapes=[
            common.dma_sems(world - 1),
            common.dma_sems((2, world)),
            pltpu.SemaphoreType.DMA(()),
        ],
        # No barrier semaphore is ever touched (that is the LL protocol's
        # point), so no collective_id (Mosaic rejects an unused one).
        compiler_params=common.compiler_params(None),
        name="ll_allgather",
        interpret=resolve_interpret(interpret),
    )(p, x_local, staging)
    return out, staging


def make_ll_staging(local_shape, dtype, *, mesh: Mesh | None = None,
                    axis: str = "tp", name: str = "ll_ag"):
    """Persistent double-buffered receive staging for ``ll_all_gather``:
    a ``runtime/symm.py`` workspace of per-device shape
    ``(2, world-1, *local_shape)`` (2 epoch-parity slots x world-1 sources)
    — the ``FastAllGatherContext`` symmetric buffer analog
    (low_latency_allgather.py:780)."""
    mesh = mesh or get_default_mesh()
    world = mesh.shape[axis]
    return symm.get_workspace(
        name, (2, max(world - 1, 1), *tuple(local_shape)), dtype,
        mesh=mesh, axis=axis)


def ll_all_gather(x_stacked, staging_ws: symm.SymmetricWorkspace, epoch, *,
                  mesh: Mesh | None = None, axis: str = "tp", interpret=None):
    """Stacked-convention LL allgather: ``(world, *local)`` (device r owns
    ``[r]``) -> gathered ``(world*local[0], ...)`` replicated. Mutates
    ``staging_ws.array`` in place (donated and re-bound) so successive
    calls reuse the same physical staging buffer."""
    mesh = mesh or get_default_mesh()
    run = _build_ll_ag(mesh, axis, interpret, x_stacked.ndim - 1)
    if not _ledger.active():  # ledger recording or resilience hooks
        out, new_staging = run(x_stacked, staging_ws.array,
                               jnp.asarray(epoch, jnp.int32))
        staging_ws.array = new_staging
        return out
    from triton_distributed_tpu.runtime import perf_model as pm

    world = mesh.shape[axis]
    shard = x_stacked.nbytes // world
    out, new_staging = _ledger.timed(
        lambda: run(x_stacked, staging_ws.array,
                    jnp.asarray(epoch, jnp.int32)),
        "ll_all_gather", axis=axis, world=world,
        nbytes=pm.wire_bytes_all_gather(shard, world), method="ll",
        est_s=pm.est_ll_all_gather(shard, world))
    staging_ws.array = new_staging
    return out


@functools.lru_cache(maxsize=None)
def _build_ll_ag(mesh, axis, interpret, nd):
    def f(xs, stg, ep):
        out, stg = ll_all_gather_device(xs[0], stg[0], ep, axis=axis,
                                        interpret=interpret)
        return out, stg[None]

    rest = [None] * nd
    return jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=(P(axis, *rest), P(axis), P()),
            out_specs=(P(*rest), P(axis)),
            check_vma=False,
        ),
        donate_argnums=(1,),
    )


def ll_all_gather_2d_device(x_local, staging, epoch, *, ici_axis: str = "ici",
                            dcn_axis: str = "dcn", interpret=None):
    """Inter-slice low-latency allgather over a (dcn, ici) mesh — the
    analog of the reference's inter-node fast-allgather variants
    (low_latency_allgather.py 2d/3d push kernels). Intra-slice the
    barrier-free LL kernel runs as-is (persistent staging + epoch parity);
    the inter-slice hop is one XLA ``all_gather`` over ``dcn_axis`` of the
    slice-gathered block — latency-critical small messages cross DCN
    exactly once, already aggregated (w_ici messages ride one DCN
    transfer). Output is in dcn-major global rank order. Returns
    (gathered (n_slices*w_ici*m, ...), staging)."""
    n_slices = _axis_size(dcn_axis)
    intra, staging = ll_all_gather_device(x_local, staging, epoch,
                                          axis=ici_axis, interpret=interpret)
    if n_slices == 1:
        return intra, staging
    return (jax.lax.all_gather(intra, dcn_axis, axis=0, tiled=True),
            staging)


# ---------------------------------------------------------------------------
# Comm-safety analyzer registration (tools/comm_check.py; docs/analysis.md)
# ---------------------------------------------------------------------------

import numpy as _np  # noqa: E402

from triton_distributed_tpu.analysis import registry as _comm  # noqa: E402


@_comm.register("ag.ll")
def _comm_spec_ll(world: int) -> "_comm.TraceSpec":
    m, rest = 8, (128,)
    return _comm.TraceSpec(
        body=_ll_ag_kernel,
        args=[
            _comm.Buf("p", (1,), _np.int32, space="smem"),
            _comm.Buf("x", (m, *rest)),
            _comm.Buf("staging", (2, world - 1, m, *rest)),
            _comm.Buf("o", (world * m, *rest), covered=True),
            _comm.Buf("staging_out", (1,)),
            _comm.Sem("send_sems", (world - 1,)),
            _comm.Sem("recv_sems", (2, world)),
            _comm.Sem("copy_sem"),
        ],
        kwargs=dict(axis="tp", world=world),
    )
