"""Shared infrastructure for the Pallas collective/overlap kernels.

Analog of ``python/triton_dist/kernels/nvidia/common_ops.py`` in the reference
(grid barriers, signal helpers) plus the kernel-call boilerplate the reference
keeps in each op's ``create_*_context``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.runtime import compat as _compat  # noqa: F401
from triton_distributed_tpu.runtime.platform import resolve_interpret
from triton_distributed_tpu.kernels import probes as _probes

# ---------------------------------------------------------------------------
# Collective-id registry.
#
# Pallas selects the cross-device barrier semaphore by ``collective_id``;
# concurrently-running kernels (or kernels whose barrier traffic could
# interleave in one program) must use distinct ids. The reference has the same
# concern with its symmetric-heap barrier cells, solved by per-op context
# allocation (e.g. allgather_gemm.py:404). Here ops claim a stable small id by
# name at import time.
# ---------------------------------------------------------------------------

# Explicit table (not lazy registration): every process resolves the same
# name -> id mapping regardless of which kernels it happens to call first.
# Add new kernel families here.
_COLLECTIVE_IDS: dict[str, int] = {
    name: i
    for i, name in enumerate([
        "ag_ring",
        "ag_a2a",
        "ag_ll",
        "rs_oneshot",
        "rs_ring",
        "ar_oneshot",
        "ar_twoshot",
        "ag_gemm",
        "gemm_rs",
        "ep_a2a_dispatch",
        "ep_a2a_combine",
        "ag_group_gemm",
        "moe_reduce_rs",
        "sp_ag_attn",
        "flash_decode_combine",
    ])
}


def collective_id_for(name: str) -> int:
    """Stable collective id for a kernel family, from the explicit table above
    (SPMD requires every device/process agree on the barrier-semaphore id)."""
    try:
        return _COLLECTIVE_IDS[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel family {name!r}: add it to common._COLLECTIVE_IDS "
            f"so all processes agree on its collective id"
        ) from None


def compiler_params(collective_id: int | None,
                    vmem_limit_bytes: int | None = None
                    ) -> pltpu.CompilerParams:
    """``collective_id=None`` for kernels that never touch the barrier
    semaphore (Mosaic rejects an unused collective_id: "collective_id has to
    be unspecified ... when not using a custom barrier" — e.g. the LL
    allgather, whose whole point is needing no barrier)."""
    if collective_id is None:
        return pltpu.CompilerParams(has_side_effects=True)
    return pltpu.CompilerParams(has_side_effects=True,
                                collective_id=collective_id,
                                vmem_limit_bytes=vmem_limit_bytes)


def cost_estimate(*, flops: int, bytes_accessed: int,
                  remote_bytes: int = 0):
    """Kernel cost metadata for XLA's scheduler and the profiler — the
    analog of the reference GEMM kernels' ``launch_metadata`` flops/bytes
    annotations (allgather_gemm.py:132); shows up in XPlane traces
    (``group_profile``) and informs XLA's async scheduling around the
    kernel."""
    import dataclasses

    from jax.experimental import pallas as pl

    kw = dict(flops=int(flops), transcendentals=0,
              bytes_accessed=int(bytes_accessed))
    # old jax's CostEstimate predates the remote-bytes field
    if "remote_bytes_transferred" in {f.name for f in
                                      dataclasses.fields(pl.CostEstimate)}:
        kw["remote_bytes_transferred"] = int(remote_bytes)
    return pl.CostEstimate(**kw)


def local_copy(src_ref, dst_ref, sem, *, probe=_probes.NULL):
    """Synchronous local HBM<->VMEM/HBM copy via the DMA engine: started
    and waited for on the spot, so the caller pays the copy's whole latency
    and nothing else is in flight meanwhile. Right for one copy between two
    phases; NOT for a loop over blocks — there it serialises (a 32 KiB copy
    a turn reads HBM at 70 GB/s of 819, PERF.md section 6): start every
    copy of the batch, then wait, as ``paged_attention``'s walk does."""
    probe.dma_issue(src_ref)
    dma = pltpu.make_async_copy(src_ref, dst_ref, sem)
    dma.start()
    dma.wait()
    probe.dma_wait(src_ref)


def wait_recv(dst_ref, recv_sem, *, probe=_probes.NULL):
    """Receiver-side arrival wait; the single implementation lives in the
    language layer (the shmem putmem_signal counterpart). Thin wrapper so
    the device-probe layer can count the wait and its bytes."""
    from triton_distributed_tpu.language.shmem import wait_dma_arrival

    probe.dma_wait(dst_ref)
    return wait_dma_arrival(dst_ref, recv_sem)


def wait_send(src_ref, send_sem, *, probe=_probes.NULL):
    """Sender-side drain wait (shmem ``wait_send_bytes``); probe-counting
    wrapper like :func:`wait_recv`."""
    from triton_distributed_tpu.language.shmem import wait_send_bytes

    probe.dma_wait(src_ref)
    return wait_send_bytes(src_ref, send_sem)


def remote_copy(src_ref, dst_ref, send_sem, recv_sem, axis: str, peer, *,
                probe=_probes.NULL):
    """Start an async ICI put of ``src_ref`` into ``dst_ref`` on the device at
    rank ``peer`` along mesh ``axis`` (kernel-side argument order; delegates
    to the language layer's shmem primitive)."""
    from triton_distributed_tpu.language.shmem import putmem_nbi

    probe.dma_issue(src_ref, remote=True)
    return putmem_nbi(src_ref, dst_ref, peer, send_sem, recv_sem, axis=axis)


def dma_sems(shape: int | tuple):
    """Scratch spec for an array of DMA semaphores (int n = 1-D of n).

    Rejects empty and non-positive slot counts up front: a ``world - 1``-
    style count goes to zero at ``world == 1`` and Mosaic's own error for a
    zero-extent semaphore array (or the later out-of-range ``.at[i]``) says
    nothing about where the count came from. Kernels must branch to their
    single-device fallback (or skip the peer loop) *before* building the
    grid spec rather than allocate a zero-slot semaphore array.
    """
    if isinstance(shape, int):
        shape = (shape,)
    shape = tuple(shape)
    bad = [d for d in shape if not isinstance(d, (int, np.integer))]
    if bad:
        raise ValueError(
            f"dma_sems({shape!r}): non-integer dimension(s) {bad!r} — "
            "semaphore slot counts must be concrete Python ints (hoist the "
            "count out of traced values in the kernel wrapper)")
    if any(d <= 0 for d in shape):
        raise ValueError(
            f"dma_sems({shape!r}): non-positive slot count — a 'world - 1' "
            "count hits zero at world == 1; take the kernel's single-device "
            "fallback (or drop the peer loop) before building scratch_shapes "
            "instead of allocating an empty semaphore array")
    return pltpu.SemaphoreType.DMA(tuple(int(d) for d in shape))


# Mosaic's scoped-VMEM stack limit per kernel (v5e/v5p default 16MB): the
# budget every kernel's resident buffers + double-buffered pipeline blocks
# must fit (verified against the real enforcer via AOT topology compiles,
# tests/test_chip_compile.py). Block auto-selection targets the limit minus a
# margin: the enforcer counts alignment padding and bookkeeping beyond the
# plain buffer arithmetic (a 15.4M working set was rejected at the 16M
# limit), so plan for ~14M.
MOSAIC_VMEM_LIMIT = 16 * 2 ** 20
MOSAIC_VMEM_MARGIN = 2 * 2 ** 20
MOSAIC_VMEM_BUDGET = MOSAIC_VMEM_LIMIT - MOSAIC_VMEM_MARGIN

# Per-kernel VMEM working-set target for collective staging buffers. Mosaic's
# scoped-VMEM budget is ~16MB/core; collectives keep their row-tile buffers
# well under half of it so the compiler has room for pipelining (ADVICE r1:
# full-shape VMEM staging blew the budget at target shapes).
VMEM_STAGE_BUDGET = 4 * 2 ** 20


def sublane_rows(dtype) -> int:
    """Rows of one native (sublane, 128-lane) tile for ``dtype``: 8 for
    4-byte, 16 for 2-byte, 32 for 1-byte elements. Mosaic refuses a block
    or DMA slice whose second-minor extent is neither a multiple of this
    nor the whole array, so the distributed GEMMs pad their per-device row
    count up to it (decode steps carry ``n_slots / world`` rows — 2 at
    8 slots over TP=4)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def mosaic_row_pad(m: int, dtype, interpret) -> int:
    """``m`` rounded up to the sublane tile when the kernel will be compiled
    by Mosaic (``interpret`` resolves to False); ``m`` itself under the
    interpreter, which has no tiling constraint — the CPU tests keep their
    exact shapes."""
    if resolve_interpret(interpret) is not False:
        return m
    sub = sublane_rows(dtype)
    return -(-m // sub) * sub


def row_tile(m: int, row_bytes: int, budget: int = VMEM_STAGE_BUDGET) -> int:
    """Row-tile size so a kernel's VMEM row buffers (``row_bytes`` combined
    bytes per row across all tile buffers) stay under ``budget``; 8-aligned
    (sublane) when tiling at all."""
    br = max(1, budget // max(row_bytes, 1))
    if br >= m:
        return m
    return max(8, br - br % 8) if br >= 8 else br


def stage_row_tile(m: int, rest: tuple, itemsize: int) -> int:
    """Row-tile for the standard 3-buffer reduce staging (fp32 accumulator +
    wire-dtype in + wire-dtype out tiles of shape ``(br, *rest)``)."""
    rest_elems = 1
    for d in rest:
        rest_elems *= d
    return row_tile(m, rest_elems * (4 + 2 * itemsize))


def choose_lane_block(dim: int, vmem_of_block, what: str) -> int:
    """Largest 128-multiple divisor of ``dim`` (or ``dim`` itself) whose
    working set ``vmem_of_block(block)`` fits ``MOSAIC_VMEM_BUDGET`` —
    the shared block auto-selection of the overlap consumers
    (ag_gemm / gemm_rs; per-kernel cost formula passed in)."""
    for b in range(dim, 0, -1):
        if dim % b == 0 and (b % 128 == 0 or b == dim) \
                and vmem_of_block(b) <= MOSAIC_VMEM_BUDGET:
            return b
    raise ValueError(
        f"no feasible {what}: resident buffers alone overflow the "
        f"{MOSAIC_VMEM_BUDGET >> 20}MB VMEM budget")


def weight_operand(b, layer, a_k: int):
    """``(stacked, n)`` of an overlap GEMM's weight operand: a matrix
    ``(K, N)``, or a layer stack ``(L, K, N)`` with ``layer`` () int32 —
    the one rule of the three kernels that take either (``ag_gemm_device``,
    ``matmul_tail_into``, ``gemm_rs_device``). ``a_k`` is the contraction
    width of the rows it multiplies."""
    stacked = b.ndim == 3
    if stacked != (layer is not None):
        raise ValueError("layer must be passed exactly when b is "
                         "layer-stacked (L, K, N)")
    k, n = b.shape[-2:]
    if k != a_k:
        raise ValueError(f"K mismatch: A has {a_k}, B has {k}")
    return stacked, n


# The most VMEM an overlap GEMM asks for to keep its weight tiles RESIDENT
# (``resident_weight_limit``): the grant the AG-GEMM overlap kernel has run
# with since round 5. A 47MB+ grant was measured to trigger S(1)
# result-buffer promotions that starve neighboring kernels.
RESIDENT_WEIGHT_VMEM_CAP = 36 * 2 ** 20


def rank_and_weight_spec(axis: str, k: int, bn: int, layer, resident: bool):
    """``(scalars, b_spec)`` of an overlap GEMM over the grid ``(segment,
    column tile)``: the one prefetched int32 vector, this device's rank on
    ``axis`` and, behind it, the ``layer`` of a stacked weight (None: a
    matrix), with the weight operand's BlockSpec. ``resident``: the operand
    stays in HBM whole and the kernel copies its tiles itself
    (``weight_tile_copy``); else the ``(k, bn)`` tile comes through the
    pipeline, the stack's index map reading ``[layer, :, tile]`` straight
    out of ``(L, K, N)``. The kernel bodies read ``scalars[0]`` for the
    rank and see a ``(k, bn)`` tile either way."""
    me = jax.lax.axis_index(axis).astype(jnp.int32)[None]
    if layer is not None:
        me = jnp.concatenate([me, jnp.asarray(layer, jnp.int32).reshape(1)])
    if resident:
        return me, pl.BlockSpec(memory_space=pl.ANY)
    if layer is None:
        return me, pl.BlockSpec((k, bn), lambda s, j, sc: (0, j))
    return me, pl.BlockSpec((None, k, bn), lambda s, j, sc: (sc[1], 0, j))


def fits_kernel_vmem(need: int):
    """``(fits, vmem_limit_bytes)`` of a kernel whose working set is
    ``need`` bytes: whether it is within ``RESIDENT_WEIGHT_VMEM_CAP``, the
    most VMEM a kernel of this package asks for, and the limit to hand
    Mosaic (None where the default budget holds it)."""
    if need > RESIDENT_WEIGHT_VMEM_CAP:
        return False, None
    if need <= MOSAIC_VMEM_BUDGET:
        return True, None
    # Sized to the need plus headroom for Mosaic's bookkeeping.
    return True, need + 8 * 2 ** 20


def resident_weight_limit(need: int, probes: bool):
    """``(resident, vmem_limit_bytes)`` of an overlap GEMM whose working
    set WITH every weight tile of the call held in VMEM is ``need`` bytes.
    The grid walks ``(segment, column tile)``, so a tile that comes through
    the pipeline is fetched once a SEGMENT, ``world`` times a call, one
    copy in flight; resident, every tile is copied once, all copies
    started at the kernel's first step and each awaited where the first
    segment meets it. On a v5e at Qwen3-8B's TP=4 decode shapes the down
    projection's 25.2 MB a chip took 145 us a call through the pipeline
    (100.7 MB at 692 GB/s) and 65 us resident (one-chip probe of the fetch
    alone, PERF.md section 6, PR 47). Not under ``probes`` (the
    instrumented build keeps the pipeline's fetch) and not past what
    ``fits_kernel_vmem`` allows."""
    return (False, None) if probes else fits_kernel_vmem(need)


def with_resident_tiles(kernel, scratch_shapes, n_tiles: int, k: int,
                        bn: int, dtype):
    """``(kernel, scratch_shapes)`` of an overlap GEMM that copies its
    weight tiles itself (``n_tiles`` slots: every tile of the call where
    the weight is resident, a ring where each tile is met once): the
    tiles' VMEM and their semaphores go LAST among the scratch, and the
    body gets them as ``b_tiles``."""
    def body(*refs):
        kernel(*refs[:-2], b_tiles=refs[-2:])

    return body, [*scratch_shapes, pltpu.VMEM((n_tiles, k, bn), dtype),
                  dma_sems(n_tiles)]


def weight_tile_copy(scalars_ref, b_hbm, tiles, jj, bn: int, slot=None):
    """The copy of column tile ``jj`` of a weight from HBM into its slot
    (``jj`` itself where every tile of the call is resident; a walk that
    meets each tile once names a ``slot`` of its ring): ``tiles`` is the
    kernel's ``(b_vmem (slots, K, bn), sems (slots,))``, ``b_hbm`` the
    matrix ``(K, N)`` or, where the prefetched ``scalars_ref`` carries a
    layer behind the rank, the stack ``(L, K, N)`` read at that layer."""
    b_vmem, sems = tiles
    slot = jj if slot is None else slot
    cols = pl.ds(jj * bn, bn)
    src = (b_hbm.at[:, cols] if scalars_ref.shape[0] == 1
           else b_hbm.at[scalars_ref[1], :, cols])
    return pltpu.make_async_copy(src, b_vmem.at[slot], sems.at[slot])


def _elems(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def peer_slot(src, me):
    """Slot index of source ``src`` in a (world-1)-slot receive staging that
    omits the owner's own slot (sources in rank order, ``me`` removed).
    Senders pushing to ``peer`` use ``peer_slot(me, peer)``; receivers read
    source ``src`` at ``peer_slot(src, me)``."""
    return src - (src > me)


def reduce_slots_tiled(x_ref, x_off, staging, world, me, o_ref, *, m, br,
                       acc_ref, tmp_ref, out_ref, copy_sem,
                       probe=_probes.NULL):
    """Row-tiled fp32 reduce in FIXED global rank order (src = 0..world-1,
    bitwise rank-independent) shared by the one-shot AR / RS kernels:
    the own contribution reads straight from ``x_ref[x_off:]`` (no staging
    round-trip), remote ones from the (world-1)-slot ``staging`` at
    ``peer_slot(src, me)``; result rows land in ``o_ref[0:m]``. VMEM held
    to ``(br, ...)`` tiles (ADVICE r1)."""
    for t in range(pl.cdiv(m, br)):
        rows = min(br, m - t * br)
        acc = acc_ref.at[pl.ds(0, rows)]
        tmp = tmp_ref.at[pl.ds(0, rows)]
        out = out_ref.at[pl.ds(0, rows)]
        for src in range(world):
            @pl.when(src == me)
            def _own(t=t, rows=rows):
                local_copy(x_ref.at[pl.ds(x_off + t * br, rows)],
                           tmp_ref.at[pl.ds(0, rows)], copy_sem, probe=probe)

            @pl.when(src != me)
            def _remote(src=src, t=t, rows=rows):
                local_copy(staging.at[peer_slot(src, me), pl.ds(t * br, rows)],
                           tmp_ref.at[pl.ds(0, rows)], copy_sem, probe=probe)

            if src == 0:
                acc[...] = tmp[...].astype(jnp.float32)
            else:
                acc[...] += tmp[...].astype(jnp.float32)
                probe.compute(rows * _elems(tmp_ref.shape[1:]))
        out[...] = acc[...].astype(out_ref.dtype)
        local_copy(out, o_ref.at[pl.ds(t * br, rows)], copy_sem, probe=probe)


def reduce_rows_tiled(x_ref, x_off, staging, stage_idx, dst_ref, dst_off, *,
                      m, br, acc_ref, tmp_ref, out_ref, copy_sem,
                      probe=_probes.NULL):
    """Row-tiled fp32 accumulate shared by the ring RS / two-shot AR kernels:
    ``dst_ref[dst_off+r] = x_ref[x_off+r] (+ staging[stage_idx][r])`` with
    VMEM held to ``(br, ...)`` tiles (ADVICE r1 VMEM-budget fix).
    ``stage_idx=None`` skips the staged addend (ring step 0)."""
    for t in range(pl.cdiv(m, br)):
        rows = min(br, m - t * br)
        acc = acc_ref.at[pl.ds(0, rows)]
        tmp = tmp_ref.at[pl.ds(0, rows)]
        out = out_ref.at[pl.ds(0, rows)]
        local_copy(x_ref.at[pl.ds(x_off + t * br, rows)], tmp, copy_sem,
                   probe=probe)
        acc[...] = tmp[...].astype(jnp.float32)
        if stage_idx is not None:
            local_copy(staging.at[stage_idx, pl.ds(t * br, rows)], tmp,
                       copy_sem, probe=probe)
            acc[...] += tmp[...].astype(jnp.float32)
            probe.compute(rows * _elems(tmp_ref.shape[1:]))
        out[...] = acc[...].astype(out_ref.dtype)
        local_copy(out, dst_ref.at[pl.ds(dst_off + t * br, rows)], copy_sem,
                   probe=probe)


def make_pallas_call(kernel, *, name, out_shape, in_specs, out_specs,
                     scratch_shapes, collective_id, interpret=None, grid=None,
                     grid_spec=None):
    """Uniform ``pl.pallas_call`` wrapper: ANY-space refs by default,
    side-effectful, interpret-resolved (compiled on real TPU, interpreted with
    faithful remote-DMA simulation elsewhere — see runtime/platform.py).
    ``name`` is the kernel's name in the lowered program and so in a device
    trace."""
    kwargs = {}
    if grid is not None:
        kwargs["grid"] = grid
    if grid_spec is not None:
        kwargs["grid_spec"] = grid_spec
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
        compiler_params=compiler_params(collective_id),
        interpret=resolve_interpret(interpret),
        name=name,
        **kwargs,
    )


def any_spec():
    return pl.BlockSpec(memory_space=pl.ANY)


def hbm_spec():
    """Whole-array ref pinned to HBM. Kernel OUTPUTS that stage collective
    traffic must use this rather than ANY: XLA may place a small ANY output
    in VMEM (observed on the gemm_rs (m, n) output at TP=8 shapes, blowing
    the 16MB scoped budget); remote DMAs need the buffer in HBM anyway."""
    return pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
