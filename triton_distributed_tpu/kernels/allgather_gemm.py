"""AG-GEMM: allgather-overlapped matmul — the flagship TP overlap op.

TPU-native analog of the reference's ``kernels/nvidia/allgather_gemm.py``
(744 LoC: ``create_ag_gemm_context`` :489, ``ag_gemm`` :534, persistent
consumer GEMM :146, rank-swizzled tile order via
``ag_gemm_threadblock_swizzle.py``) and its producer
``cp_engine_producer_all_gather_intra_node`` (allgather.py:263).

TPU design (SURVEY.md §7 stage 4, hard-part 1):
- The reference overlaps a copy-engine allgather (comm streams) with a
  persistent consumer GEMM (compute stream), synchronized by per-segment
  signal cells. TPUs have no independent comm streams; overlap comes from
  DMA-compute concurrency *inside one Pallas kernel*: at the first grid step
  every device pushes its A-shard to all peers (async ICI DMAs); the grid
  then walks (segment, n-tile) pairs, waiting on each segment's receive
  semaphore only when first touched, while the MXU computes already-arrived
  segments. The DMA engines run concurrently with the matmuls — comm is
  hidden behind compute exactly as in the reference.
- Rank-swizzled consumer order: segment ``s`` maps to source rank
  ``(me + s) % world``, so every device computes its *own* segment first
  (zero wait) and meets remote segments in expected-arrival order — the role
  of the reference's threadblock swizzle, done with a scalar-prefetched
  ``me`` in the output BlockSpec index map.
- Producer variants: ``all2all`` direct pushes (one hop, world-1 concurrent
  DMAs). A ring-forward producer lands with multi-slice support, mirroring
  AllGatherMethod.

Sharding convention (column-parallel TP matmul, reference TP_MLP up-proj):
  A: (M, K) sharded on M over ``axis``  -> per-device (m, K), m = M/world
  B: (K, N) sharded on N over ``axis``  -> per-device (K, n_local)
  C: (M, N) sharded on N over ``axis``  -> per-device (M, n_local)
"""

from __future__ import annotations

import dataclasses
import functools
import math

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
from triton_distributed_tpu.runtime.platform import resolve_interpret


@dataclasses.dataclass(frozen=True)
class AGGEMMConfig:
    """Tile configuration (the analog of the reference's per-op context block
    sizes, allgather_gemm.py:404). ``block_n`` tiles the local N dimension of
    the consumer matmul; the M dimension is walked per rank segment.
    ``block_n=None`` auto-selects the largest lane-aligned divisor of
    ``n_local`` whose VMEM working set fits Mosaic's scoped budget.

    ``overlap_cols`` bounds the column width the segment-granular overlap
    kernel computes; the remaining ``n_local - overlap_cols`` columns run in
    a plain tuned-block matmul over the gathered A (see ``ag_gemm_device``).
    ``None`` auto-sizes it from the perf model: just wide enough that the
    overlap kernel's compute outlasts the A gather. Must be a multiple of
    the resolved ``block_n``."""

    block_n: int | None = None
    overlap_cols: int | None = None

    def n_tiles(self, n_local: int) -> int:
        if self.block_n is None or n_local % self.block_n:
            raise ValueError(
                f"n_local {n_local} not divisible by block_n {self.block_n}")
        return n_local // self.block_n

    def resolve(self, m: int, k: int, n_local: int, in_itemsize: int,
                out_itemsize: int) -> "AGGEMMConfig":
        if self.block_n is not None:
            return self
        return AGGEMMConfig(
            block_n=_choose_consumer_block_n(
                m, k, n_local, in_itemsize, out_itemsize),
            overlap_cols=self.overlap_cols)


def _choose_consumer_block_n(m: int, k: int, n_local: int, in_isz: int,
                             out_isz: int) -> int:
    """Largest lane-aligned block_n whose consumer working set — the full
    (m, k) A segment in VMEM plus double-buffered (k, bn) B and (m, bn) out
    tiles — fits the scoped-VMEM budget Mosaic enforces (the enforcer
    rejected block_n=640 at the Qwen3-32B TP=8 shape with exactly this
    arithmetic: 18.75M > 16M)."""
    return common.choose_lane_block(
        n_local,
        lambda bn: _overlap_vmem(m, k, bn, in_isz, out_isz),
        f"ag_gemm consumer block_n (A segment {m}x{k})")


def _auto_overlap_cols(m: int, k: int, n_local: int, world: int, bn: int,
                       itemsize: int, *, gather_bw: float | None = None
                       ) -> int:
    """Column width for the segment-granular overlap kernel: the smallest
    multiple of ``bn`` whose consumer compute outlasts the A gather (perf
    model), so the comm stays hidden while the bulk of the matmul runs at
    bare tuned-block speed in the tail kernel. ``gather_bw`` overrides the
    transport (the loopback arms gather over the local DMA engine at HBM
    bandwidth rather than ICI)."""
    from triton_distributed_tpu.runtime.perf_model import (
        detect_hardware, est_matmul, est_push_all_gather)

    hw = detect_hardware()
    if gather_bw is not None:
        t_gather = world * m * k * itemsize / gather_bw
    else:
        t_gather = est_push_all_gather(m * k * itemsize, world, hw)
    t_col = max(est_matmul(world * m, k, bn, itemsize, hw), 1e-9)
    tiles = max(1, math.ceil(t_gather / t_col))
    return min(n_local, tiles * bn)


# The overlap kernel may exceed the default 16MB scoped budget (it then
# gets an explicit working-set-sized vmem_limit): a single full-width
# (640) B tile with constant index map stays VMEM-resident across all
# segments, deleting the per-segment B re-fetch that made the kernel
# DMA-bound at bn=128. Modest cap — a 47MB+ grant was measured to trigger
# S(1) result-buffer promotions that starve neighboring kernels.
_OVERLAP_VMEM_CAP = 36 * 2 ** 20


def _overlap_vmem(m: int, k: int, bn: int, in_isz: int, out_isz: int) -> int:
    """Overlap-kernel working set: TWO (m, k) A-segment slots (the load
    double-buffer) + double-buffered (k, bn) B and (m, bn) out tiles."""
    return 2 * m * k * in_isz + 2 * k * bn * in_isz + 2 * m * bn * out_isz


def _overlap_vlim(m: int, k: int, bn: int, in_isz: int, out_isz: int):
    """Explicit vmem_limit for the overlap kernel when its working set
    exceeds the default scoped budget (None otherwise). Sized to the need
    plus headroom for Mosaic bookkeeping — NOT the 100MB cap, which was
    measured to trigger program-wide S(1) buffer promotions."""
    need = _overlap_vmem(m, k, bn, in_isz, out_isz)
    if need <= common.MOSAIC_VMEM_BUDGET:
        return None
    return need + 8 * 2 ** 20


def _split_blocks(config: "AGGEMMConfig", m: int, k: int, n_local: int,
                  in_isz: int, out_isz: int) -> tuple["AGGEMMConfig", int]:
    """Resolve the overlap kernel's ``block_n`` and the tail kernel's
    ``block_n`` for the two-kernel split. An explicit ``config.block_n``
    is used for both (tests pin it). In auto mode the tail picks the bare
    matmul's tuned width first (640-preferred — full-size MXU tiles for
    the bulk of the FLOPs), then the overlap kernel's block is chosen from
    divisors of the tail block so ``overlap_cols`` is a multiple of both —
    against the raised ``_OVERLAP_VMEM_CAP`` (the overlap call passes an
    explicit working-set-sized vmem_limit via ``_overlap_vlim``), so at
    flagship shapes the overlap kernel runs the same full-width tiles as
    the tail with its B tile VMEM-resident across segments."""
    if config.block_n is not None:
        return config, config.block_n
    try:
        bn_tail = _fit_block(n_local, 640, 128)
    except ValueError:
        resolved = config.resolve(m, k, n_local, in_isz, out_isz)
        return resolved, resolved.block_n
    bn1 = None
    for cand in range(bn_tail, 0, -1):
        if bn_tail % cand == 0 and (cand % 128 == 0 or cand == bn_tail) \
                and _overlap_vmem(m, k, cand, in_isz,
                                  out_isz) <= _OVERLAP_VMEM_CAP:
            bn1 = cand
            break
    if bn1 is None:
        resolved = config.resolve(m, k, n_local, in_isz, out_isz)
        return resolved, resolved.block_n
    return AGGEMMConfig(block_n=bn1, overlap_cols=config.overlap_cols), bn_tail


def _resolve_overlap_cols(config: "AGGEMMConfig", m: int, k: int, n: int,
                          world: int, bn: int, bn_tail: int, itemsize: int,
                          *, loopback: bool) -> int:
    """Resolve + validate ``overlap_cols`` for the three split entry points
    (one definition of the rule): explicit config wins, else perf-model
    auto-sizing — over local-DMA bandwidth for the loopback arms, the ICI
    push model for the device kernel."""
    cols = config.overlap_cols
    if cols is None:
        if loopback:
            from triton_distributed_tpu.runtime.perf_model import (
                detect_hardware)

            cols = _auto_overlap_cols(m, k, n, world, bn_tail, itemsize,
                                      gather_bw=detect_hardware().hbm_bw)
        else:
            cols = _auto_overlap_cols(m, k, n, world, bn_tail, itemsize)
    if cols % bn or cols % bn_tail or cols > n:
        raise ValueError(f"overlap_cols {cols} must be a multiple of "
                         f"block_n {bn} / tail block {bn_tail} and <= {n}")
    return cols


def _matmul_tail_into_kernel(*refs, k_tiles: int, j0: int, bn: int):
    # A stacked b's layer index rides in front as a prefetched scalar; only
    # the index maps read it.
    c_ref, a_ref, b_ref, o_ref, acc_ref = refs[-5:]
    j = pl.program_id(1)
    kk = pl.program_id(2)

    # Pass-through columns: the overlap kernel's result rides from c into
    # the full-width output (static slices — j0 is small by construction).
    for jj in range(j0):
        @pl.when((j == jj) & (kk == 0))
        def _passthrough(jj=jj):
            o_ref[...] = c_ref[:, jj * bn:(jj + 1) * bn]

    @pl.when(j >= j0)
    def _compute():
        @pl.when(kk == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32)

        @pl.when(kk == k_tiles - 1)
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_tail_into(c, a, b, col_start: int, *, block_n: int,
                     block_m: int = 1024, block_k: int | None = None,
                     interpret=None, layer=None):
    """Assemble the AG-GEMM split result in ONE kernel pass: returns the
    full ``(m, n)`` product where columns ``[0, col_start)`` come from ``c``
    (the overlap kernel's output, copied through VMEM) and columns
    ``[col_start, n)`` are computed as ``a @ b[:, col_start:]`` at plain
    tuned-block speed. The grid covers every column block; pass-through
    blocks skip the MXU and write the staged ``c`` tile. Why this shape:
    a materialized ``concatenate`` of the two halves measured 0.57 ms at
    the bench shape, and an input_output_aliases hand-off between the two
    pallas calls measured ~0.6 ms of XLA defensive-copy machinery — the
    pass-through grid deletes both (measured round 5).

    ``col_start`` must be a multiple of ``block_n``. Falls back to XLA
    compute + dynamic_update_slice when the tail blocks are infeasible
    (ragged K — same delegation bound as ``ag_gemm_single_chip``).

    ``block_k`` None: where the rows are ONE block (``m <= block_m``) B is
    read exactly once and the kernel is its stream, so a tile takes the
    whole of K if that fits: one copy of ``(K, block_n)`` a column tile in
    place of K / 1024 of a quarter the size, one in flight at a time
    (Qwen3-8B's gate-up quarter at 64 rows on a v5e: 74.5 us against 133,
    PERF.md section 6, PR 47). Else 1024, the sweep's winner at large M.

    ``b`` may be a layer stack ``(L, K, N)`` with ``layer`` () int32 (see
    ``ag_gemm_device``): B's index map then reads the layer from a
    prefetched scalar and the tiles come straight out of the stack; the
    XLA fall-back takes ``b[layer]``, a slice its dot fuses."""
    m, k = a.shape
    stacked, n = common.weight_operand(b, layer, k)
    ncols = n - col_start
    if c.shape != (m, col_start):
        raise ValueError(f"c {c.shape} != ({m}, {col_start})")
    if col_start % block_n or ncols % block_n:
        raise ValueError(
            f"col_start {col_start} / tail {ncols} not multiples of "
            f"block_n {block_n}")
    bn = block_n
    out_dtype = c.dtype

    def vmem(bm, bk):
        return (_matmul_vmem(bm, bn, bk, a.dtype.itemsize,
                             out_dtype.itemsize)
                + 2 * bm * col_start * out_dtype.itemsize)

    try:
        bm = _fit_block(m, min(block_m, m), 8)
        if block_k is None:
            block_k = k if (bm == m and
                            vmem(bm, k) <= common.MOSAIC_VMEM_BUDGET) else 1024
        bk = _fit_block(k, min(block_k, k), 128)
        if vmem(bm, bk) > _AUTO_VMEM_BUDGET:
            raise ValueError("tail blocks exceed the auto VMEM budget")
    except ValueError:
        # Tail columns only: the overlap kernel already produced
        # [0, col_start) in ``c`` — recomputing the full product just to
        # slice it would redo col_start/n of the FLOPs for nothing.
        tail = jnp.dot(
            a, jax.lax.slice_in_dim(b[layer] if stacked else b,
                                    col_start, n, axis=1),
            preferred_element_type=jnp.float32).astype(out_dtype)
        return jnp.concatenate([c, tail], axis=1)
    j0 = col_start // bn
    k_tiles = k // bk
    # The index maps take the grid indices and then the prefetched scalars
    # (none for a matrix, the layer for a stack).
    if stacked:
        b_spec = pl.BlockSpec(
            (None, bk, bn),
            lambda i, j, kk, li_ref: (li_ref[0], kk, jnp.maximum(j, j0)))
        scalars = (jnp.asarray(layer, jnp.int32).reshape(1),)
    else:
        b_spec = pl.BlockSpec((bk, bn),
                              lambda i, j, kk: (kk, jnp.maximum(j, j0)))
        scalars = ()
    return pl.pallas_call(
        functools.partial(_matmul_tail_into_kernel, k_tiles=k_tiles,
                          j0=j0, bn=bn),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(m // bm, n // bn, k_tiles),
            in_specs=[
                # One c row-panel per i, reused across (j, kk) — fetched
                # once.
                pl.BlockSpec((bm, col_start), lambda i, j, kk, *_: (i, 0)),
                # Clamped index maps below j0: pass-through steps re-point
                # at blocks the first compute column needs anyway (B) or at
                # a constant block (A) instead of streaming operands the
                # MXU never reads — pass-through columns cost one c panel,
                # not a wasted 40MB A sweep.
                pl.BlockSpec((bm, bk),
                             lambda i, j, kk, *_: (
                                 i, jnp.where(j >= j0, kk, 0))),
                b_spec,
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ag_gemm_tail",
        interpret=resolve_interpret(interpret),
    )(*scalars, c, a, b)


def _ag_gemm_kernel(me_ref, a_ref, b_ref, o_ref, a_full, a_vmem, send_sems,
                    recv_sems, copy_sems, *, axis: str, world: int,
                    n_tiles: int, probe=_probes.NULL, b_tiles=None):
    # ``b_tiles`` None: ``b_ref`` is this step's (k, bn) tile, through the
    # pipeline. Else B is RESIDENT: ``b_ref`` is the whole operand in HBM
    # and ``b_tiles`` its ``(b_vmem, sems)``
    # (``common.resident_weight_limit``).
    s = pl.program_id(0)
    j = pl.program_id(1)
    me = me_ref[0]
    m = a_ref.shape[0]
    k = a_ref.shape[1]
    bn = o_ref.shape[1]
    probe.enter(s * n_tiles + j, me, world)
    src = jax.lax.rem(me + s, world)
    nxt = jax.lax.rem(me + s + 1, world)
    cur_slot = jax.lax.rem(s, 2)
    nxt_slot = jax.lax.rem(s + 1, 2)

    @pl.when((s == 0) & (j == 0))
    def _startup():
        if b_tiles is not None:
            # Every tile's copy in flight before anything waits.
            for jj in range(n_tiles):
                common.weight_tile_copy(me_ref, b_ref, b_tiles, jj, bn).start()
        # All devices in the kernel before anyone receives remote pushes.
        dl.barrier_all(axis)
        probe.sem_spin(world - 1)
        common.local_copy(a_ref, a_full.at[me], copy_sems.at[0], probe=probe)
        for i in range(world - 1):
            peer = jax.lax.rem(me + 1 + i, world)
            common.remote_copy(
                a_ref, a_full.at[me],
                send_sems.at[i], recv_sems.at[me], axis, peer, probe=probe)
        # Own segment into slot 0 synchronously (it computes this step).
        probe.dma_issue(a_vmem.at[0])
        dma = pltpu.make_async_copy(a_full.at[me], a_vmem.at[0],
                                    copy_sems.at[0])
        dma.start()
        probe.dma_wait(a_vmem.at[0])
        dma.wait()

    # Complete the HBM->VMEM prefetch issued while segment s-1 computed.
    @pl.when((j == 0) & (s > 0))
    def _wait_cur():
        probe.dma_wait(a_vmem.at[cur_slot])
        pltpu.make_async_copy(a_full.at[src], a_vmem.at[cur_slot],
                              copy_sems.at[cur_slot]).wait()

    if b_tiles is not None:
        # First touch (the own segment's walk); the gathered segments
        # multiply what is already there.
        @pl.when(s == 0)
        def _tile_arrived():
            common.weight_tile_copy(me_ref, b_ref, b_tiles, j, bn).wait()

    o_ref[...] = jnp.dot(
        a_vmem[cur_slot], b_ref[...] if b_tiles is None else b_tiles[0][j],
        preferred_element_type=jnp.float32).astype(o_ref.dtype)
    probe.compute(2 * m * k * bn)

    # First-touch arrival wait for the NEXT segment (the dl.wait +
    # consume_token of the reference consumer, allgather_gemm.py:146), then
    # prefetch it into the other VMEM slot while this segment's dot runs on
    # the MXU — the dot above is already queued, so the scalar core blocking
    # here costs nothing (double-buffered loads: +22% on kernel1, round 5).
    @pl.when((j == 0) & (s < world - 1))
    def _prefetch():
        common.wait_recv(a_full.at[nxt], recv_sems.at[nxt], probe=probe)
        probe.dma_issue(a_vmem.at[nxt_slot])
        pltpu.make_async_copy(a_full.at[nxt], a_vmem.at[nxt_slot],
                              copy_sems.at[nxt_slot]).start()

    # Drain sends before kernel exit.
    @pl.when((s == world - 1) & (j == n_tiles - 1))
    def _drain():
        for i in range(world - 1):
            common.wait_send(a_ref, send_sems.at[i], probe=probe)


def ag_gemm_device(a_local, b_local, *, axis: str = "tp",
                   config: AGGEMMConfig | None = None, interpret=None,
                   probes: bool = False, layer=None):
    """Per-device AG-GEMM (composable inside shard_map):
    ``(m, K) x (K, n_local) -> (world*m, n_local)`` with the allgather of A
    overlapped into the matmul.

    With ``probes=True`` (a separate compile) returns ``(out, probe_buf)``:
    the overlap kernel records device telemetry (one row per grid step,
    decoded by ``obs.kprobe``); the tail matmul is not instrumented.

    Two-kernel split (round 5 — kills the grid-structure cost VERDICT r4
    decomposed to 0.156 ms): the segment-granular overlap kernel computes
    only the first ``overlap_cols`` columns — just enough MXU work to hide
    the gather (perf-model-sized) — while staging the full gathered A; the
    remaining columns run as a plain tuned-block matmul over the gathered A
    at bare-kernel speed (B read once, big block_m tiles). The reference's
    persistent consumer reaches the same steady state by revisiting tiles
    after the last segment signal (allgather_gemm.py:146); on TPU the tail
    is a second Pallas call so Mosaic pipelines it with full-size blocks.

    ``b_local`` may be the layer STACK ``(L, K, n_local)`` with ``layer`` ()
    int32, traced: how a model's ``lax.scan`` body calls it. The index
    rides as a second prefetched scalar beside ``me``, so the kernel's
    own fetch of ``[layer, :, tile]`` is the one read of the weights. A
    matrix sliced out of the stack by the scan (the stack in ``xs``) feeds
    a custom call XLA cannot fuse the slice into: the whole layer was
    staged by a serial pass BEFORE the kernel started and then streamed a
    second time (66.6 us a layer for Qwen3-8B's gate-up quarter on a v5e,
    PERF.md section 6, PR 47). Grid, tiles, semaphores and the ledger's
    record are the 2-D form's; the single-device branch and the ``probes``
    build take ``b_local[layer]``.

    Either form's overlap columns are RESIDENT where they fit
    (``common.resident_weight_limit``): the grid walks ``(segment, column
    tile)``, and a tile through the pipeline would be fetched once a
    segment."""
    config = config or AGGEMMConfig()
    world = _axis_size(axis)
    m, k = a_local.shape
    stacked, n_local = common.weight_operand(b_local, layer, k)
    if stacked and (world == 1 or probes):
        b_local, layer = b_local[layer], None
    if world == 1:
        # Degenerate path: single-chip matmul with the sweep-tuned defaults.
        # config.block_n tiles the multi-device consumer only — passing it
        # here would count as an explicit block and forfeit the automatic
        # XLA delegation on ragged/VMEM-infeasible shapes.
        out = ag_gemm_single_chip(a_local, b_local, interpret=interpret)
        return (out, _probes.host_stub_buffer()) if probes else out
    m_pad = common.mosaic_row_pad(m, a_local.dtype, interpret)
    if m_pad != m:
        # Mosaic only: a decode step's per-device row count (n_slots /
        # world) is below the sublane tile the (m, bn) out block needs.
        # Zero rows ride the gather and are dropped from every segment.
        res = ag_gemm_device(
            jnp.pad(a_local, ((0, m_pad - m), (0, 0))), b_local, axis=axis,
            config=config, interpret=interpret, probes=probes, layer=layer)
        out = (res[0] if probes else res).reshape(world, m_pad, n_local)
        out = out[:, :m].reshape(world * m, n_local)
        return (out, res[1]) if probes else out
    from triton_distributed_tpu.runtime import perf_model as pm

    # The gather of A is the op's only traffic (the rows as they travel:
    # after the Mosaic row pad above). A series of its own beside the host
    # wrapper's "overlap", which times the same traffic when it is the
    # caller.
    _ledger.record_traced(
        "ag_gemm", axis=axis, world=world, method="device",
        nbytes=pm.wire_bytes_all_gather(
            m * k * a_local.dtype.itemsize, world))
    out_dtype = jnp.promote_types(a_local.dtype, b_local.dtype)
    config, bn_tail = _split_blocks(config, m, k, n_local,
                                    a_local.dtype.itemsize,
                                    out_dtype.itemsize)
    bn = config.block_n
    config.n_tiles(n_local)  # divisibility check
    cols = _resolve_overlap_cols(config, m, k, n_local, world, bn, bn_tail,
                                 a_local.dtype.itemsize, loopback=False)
    n_tiles = cols // bn

    # Two A-segment slots, the overlap columns of B whole, two out tiles.
    resident, vmem_limit = common.resident_weight_limit(
        (2 * m * k + k * cols) * a_local.dtype.itemsize
        + 2 * m * bn * out_dtype.itemsize, probes)
    if not resident:
        vmem_limit = _overlap_vlim(m, k, bn, a_local.dtype.itemsize,
                                   out_dtype.itemsize)
    me, b_spec = common.rank_and_weight_spec(axis, k, bn, layer, resident)

    # The gathered-A staging is an ANY-space OUTPUT, not scratch: Mosaic only
    # allocates vmem/smem/semaphore scratch memrefs, and remote DMAs need a
    # stable HBM buffer on every device — kernel outputs provide exactly that
    # (the standard compiled-Pallas distributed pattern). The staging output
    # feeds the tail matmul (it IS the gathered A, in absolute rank order).
    out_specs = [
        pl.BlockSpec(
            (m, bn),
            lambda s, j, me_ref: (jax.lax.rem(me_ref[0] + s, world), j),
        ),
        common.hbm_spec(),                     # gathered-A staging
    ]
    scratch_shapes = [
        pltpu.VMEM((2, m, k), a_local.dtype),     # segment double-buffer
        common.dma_sems(world - 1),               # send
        common.dma_sems(world),                   # recv (slot per src)
        common.dma_sems(2),                       # per-slot local copies
    ]
    kernel = functools.partial(_ag_gemm_kernel, axis=axis, world=world,
                               n_tiles=n_tiles)
    out_shape = [
        jax.ShapeDtypeStruct((world * m, cols), out_dtype),
        jax.ShapeDtypeStruct((world, m, k), a_local.dtype),
    ]
    if resident:
        kernel, scratch_shapes = common.with_resident_tiles(
            kernel, scratch_shapes, n_tiles, k, bn, b_local.dtype)
    if probes:
        n_steps = world * n_tiles

        def body(me_ref, a_ref, b_ref, o_ref, a_full, pbuf, a_vmem,
                 send_sems, recv_sems, copy_sems, pord, kernel=kernel):
            kernel(me_ref, a_ref, b_ref, o_ref, a_full, a_vmem, send_sems,
                   recv_sems, copy_sems,
                   probe=_probes.Probe(pbuf, pord, n_steps=n_steps))

        kernel = body
        out_specs = [*out_specs, _probes.out_spec()]
        scratch_shapes = [*scratch_shapes, _probes.ord_scratch()]
        out_shape = [*out_shape, _probes.out_shape(n_steps)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(world, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),     # a_local
            b_spec,                                # b tile
        ],
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=common.collective_id_for("ag_gemm"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=common.cost_estimate(
            flops=2 * world * m * k * cols,
            bytes_accessed=(2 * world * m * k * a_local.dtype.itemsize
                            + k * cols * b_local.dtype.itemsize
                            + world * m * cols * out_dtype.itemsize),
            remote_bytes=(world - 1) * m * k * a_local.dtype.itemsize),
        name="ag_gemm",
        interpret=resolve_interpret(interpret),
    )(me, a_local, b_local)
    out1, a_full = outs[0], outs[1]
    if cols != n_local:
        out1 = matmul_tail_into(out1, a_full.reshape(world * m, k), b_local,
                                cols, block_n=bn_tail, interpret=interpret,
                                layer=layer)
    return (out1, outs[2]) if probes else out1


def _ag_gemm_loopback_kernel(a_ref, b_ref, o_ref, a_full, a_vmem, seg_sems,
                             copy_sems, *, segments: int):
    s = pl.program_id(0)
    j = pl.program_id(1)
    m = a_ref.shape[0] // segments
    cur_slot = jax.lax.rem(s, 2)
    nxt_slot = jax.lax.rem(s + 1, 2)

    # Staging DMAs issue STAGGERED, one per consumer step (startup seeds
    # segments 0-1, each later step issues s+2) — the loopback stand-in for
    # the world-1 ICI pushes of ag_gemm_device plus the own-shard staging
    # copy (the real kernel stages its own shard too, so the staging buffer
    # IS the gathered A the tail matmul consumes). Same HBM staging buffer,
    # same per-segment semaphores, local DMA engine instead of ICI links.
    # Why staggered: 8 concurrent local DMAs round-robin the engine and all
    # complete together (~51us) while the consumer wants segment 1 at
    # ~18us — a loopback artifact; real ICI ingress serializes the 7 peer
    # pushes, so arrivals ARE spread. Staggering models that and was
    # measured to cut the exposed staging cost. Own segment lands in VMEM
    # slot 0 synchronously.
    @pl.when((s == 0) & (j == 0))
    def _startup():
        for seg in range(min(2, segments)):
            pltpu.make_async_copy(
                a_ref.at[pl.ds(seg * m, m)], a_full.at[seg],
                seg_sems.at[seg]).start()
        common.wait_recv(a_full.at[0], seg_sems.at[0])
        dma = pltpu.make_async_copy(a_full.at[0], a_vmem.at[0],
                                    copy_sems.at[0])
        dma.start()
        dma.wait()

    # Issue-ahead: segment s+2's staging DMA, one step before its wait.
    @pl.when((j == 0) & (s < segments - 2))
    def _issue_ahead():
        pltpu.make_async_copy(
            a_ref.at[pl.ds((s + 2) * m, m)], a_full.at[s + 2],
            seg_sems.at[s + 2]).start()

    # Complete the HBM->VMEM prefetch issued while segment s-1 computed.
    @pl.when((j == 0) & (s > 0))
    def _wait_cur():
        pltpu.make_async_copy(a_full.at[s], a_vmem.at[cur_slot],
                              copy_sems.at[cur_slot]).wait()

    o_ref[...] = jnp.dot(
        a_vmem[cur_slot], b_ref[...], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)

    # First touch of the NEXT segment: wait its staging DMA (the consumer
    # dl.wait), then prefetch it into the other VMEM slot while this
    # segment's dot runs (double-buffered loads; +22% on kernel1, round 5).
    @pl.when((j == 0) & (s < segments - 1))
    def _prefetch():
        common.wait_recv(a_full.at[s + 1], seg_sems.at[s + 1])
        pltpu.make_async_copy(a_full.at[s + 1], a_vmem.at[nxt_slot],
                              copy_sems.at[nxt_slot]).start()


def ag_gemm_loopback(a, b, *, segments: int = 8,
                     config: AGGEMMConfig | None = None, interpret=None):
    """Single-chip SELF-LOOPBACK AG-GEMM: the full overlap machinery of
    ``ag_gemm_device`` — HBM staging buffer, per-segment DMA semaphores,
    first-touch waits, segment-granular consumer grid, tuned-block tail
    matmul over the staged gather — with the world-1 remote pushes replaced
    by local DMA-engine copies. The one-chip honest measurement of "comm
    hidden behind compute": comparing this against the bare consumer matmul
    quantifies how much the staging machinery costs when the DMA engine
    must hide a full extra pass over A (bench.py ``overlap_efficiency``;
    VERDICT r2 weak #2). Mirrors ``ag_gemm_device``'s two-kernel split:
    only ``overlap_cols`` columns pay segment-granularity."""
    config = config or AGGEMMConfig()
    M, k = a.shape
    _, n = b.shape
    if M % segments:
        raise ValueError(f"M {M} not divisible by segments {segments}")
    m = M // segments
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    config, bn_tail = _split_blocks(config, m, k, n, a.dtype.itemsize,
                                    out_dtype.itemsize)
    config.n_tiles(n)  # divisibility check
    bn = config.block_n
    cols = _resolve_overlap_cols(config, m, k, n, segments, bn, bn_tail,
                                 a.dtype.itemsize, loopback=True)
    out1, a_full = pl.pallas_call(
        functools.partial(_ag_gemm_loopback_kernel, segments=segments),
        out_shape=[
            jax.ShapeDtypeStruct((M, cols), out_dtype),
            jax.ShapeDtypeStruct((segments, m, k), a.dtype),
        ],
        grid=(segments, cols // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((k, bn), lambda s, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((m, bn), lambda s, j: (s, j)),
            common.hbm_spec(),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, m, k), a.dtype),
            common.dma_sems(segments),
            common.dma_sems(2),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            vmem_limit_bytes=_overlap_vlim(
                m, k, bn, a.dtype.itemsize, out_dtype.itemsize)),
        name="ag_gemm_loopback",
        interpret=resolve_interpret(interpret),
    )(a, b)
    if cols == n:
        return out1
    return matmul_tail_into(out1, a_full.reshape(M, k), b, cols,
                            block_n=bn_tail, interpret=interpret)


def _ag_gemm_segmented_bare_kernel(a_ref, b_ref, o_ref, a_vmem, copy_sems,
                                   *, segments: int):
    s = pl.program_id(0)
    j = pl.program_id(1)
    m = a_vmem.shape[1]
    cur_slot = jax.lax.rem(s, 2)
    nxt_slot = jax.lax.rem(s + 1, 2)

    @pl.when((s == 0) & (j == 0))
    def _first():
        dma = pltpu.make_async_copy(a_ref.at[pl.ds(0, m)], a_vmem.at[0],
                                    copy_sems.at[0])
        dma.start()
        dma.wait()

    @pl.when((j == 0) & (s > 0))
    def _wait_cur():
        pltpu.make_async_copy(a_ref.at[pl.ds(s * m, m)], a_vmem.at[cur_slot],
                              copy_sems.at[cur_slot]).wait()

    o_ref[...] = jnp.dot(
        a_vmem[cur_slot], b_ref[...], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)

    @pl.when((j == 0) & (s < segments - 1))
    def _prefetch():
        pltpu.make_async_copy(a_ref.at[pl.ds((s + 1) * m, m)],
                              a_vmem.at[nxt_slot],
                              copy_sems.at[nxt_slot]).start()


def ag_gemm_segmented_bare(a, b, *, segments: int = 8,
                           config: AGGEMMConfig | None = None,
                           interpret=None):
    """The loopback's consumer structure WITHOUT the staging machinery: same
    segment-granular walk over ``overlap_cols``, same per-segment VMEM loads
    and block sizes, same tuned-block tail matmul — but A segments come
    straight from the input: no staging buffer, no DMA semaphores, no waits.
    The middle arm of the bench's overlap-gap decomposition (VERDICT r3
    next #2):

        bare -> segmented_bare   = grid-structure cost (the overlap-column
                                   kernel's segment granularity + the split)
        segmented_bare -> loopback = staging machinery cost (the extra HBM
                                   pass + semaphore protocol)
    """
    config = config or AGGEMMConfig()
    M, k = a.shape
    _, n = b.shape
    if M % segments:
        raise ValueError(f"M {M} not divisible by segments {segments}")
    m = M // segments
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    config, bn_tail = _split_blocks(config, m, k, n, a.dtype.itemsize,
                                    out_dtype.itemsize)
    config.n_tiles(n)  # divisibility check
    bn = config.block_n
    cols = _resolve_overlap_cols(config, m, k, n, segments, bn, bn_tail,
                                 a.dtype.itemsize, loopback=True)
    out1 = pl.pallas_call(
        functools.partial(_ag_gemm_segmented_bare_kernel, segments=segments),
        out_shape=jax.ShapeDtypeStruct((M, cols), out_dtype),
        grid=(segments, cols // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((k, bn), lambda s, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda s, j: (s, j)),
        scratch_shapes=[
            pltpu.VMEM((2, m, k), a.dtype),
            common.dma_sems(2),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_overlap_vlim(
                m, k, bn, a.dtype.itemsize, out_dtype.itemsize)),
        name="ag_gemm_segmented_bare",
        interpret=resolve_interpret(interpret),
    )(a, b)
    if cols == n:
        return out1
    return matmul_tail_into(out1, a, b, cols, block_n=bn_tail,
                            interpret=interpret)


def ag_gemm_2d_device(a_local, b_local, *, ici_axis: str = "ici",
                      dcn_axis: str = "dcn",
                      config: AGGEMMConfig | None = None, interpret=None):
    """Inter-slice AG-GEMM over a (dcn, ici) mesh — the DCN leg of the
    flagship overlap op (the reference gathers A across nodes with NVSHMEM
    put kernels, ``allgather.py:554`` / ``allgather_gemm.py`` inter-node
    dispatch; SURVEY §2.5 "inter_node" scope).

    A is sharded on M over ALL devices (dcn-major): per-device ``(m, K)``;
    B is sharded on N over the full world: per-device ``(K, n_local)``.
    Returns ``(n_slices * w_ici * m, n_local)`` — the full-M product.

    TPU design (SURVEY §7 hard-part 6: DCN has no device-initiated one-sided
    op): intra-slice gathering stays inside the Pallas overlap kernel
    (``ag_gemm_device``); INTER-slice A blocks ride a slice-level
    ``lax.ppermute`` ring over ``dcn_axis``. The permute of the next A block
    has no data dependence on the current kernel call, so XLA schedules the
    DCN hop concurrently with the intra-slice overlapped matmul — comm
    hidden at both levels (ICI inside the kernel, DCN behind whole kernel
    calls)."""
    from triton_distributed_tpu.kernels.collective_2d import dcn_ring_walk

    n_slices = _axis_size(dcn_axis)
    if n_slices == 1:
        return ag_gemm_device(a_local, b_local, axis=ici_axis, config=config,
                              interpret=interpret)
    w_ici = _axis_size(ici_axis)
    m, k = a_local.shape
    n_local = b_local.shape[1]
    out_dtype = jnp.promote_types(a_local.dtype, b_local.dtype)

    def block(step, cur, ab):                         # (w_ici*m, n_local)
        return ag_gemm_device(ab, b_local, axis=ici_axis, config=config,
                              interpret=interpret)

    def place(acc, cur, blk):
        return jax.lax.dynamic_update_slice(
            acc, blk.astype(out_dtype), (cur * (w_ici * m), 0))

    return dcn_ring_walk(
        block, place, jnp.zeros((n_slices * w_ici * m, n_local), out_dtype),
        (a_local,), dcn_axis=dcn_axis)


# ---------------------------------------------------------------------------
# Single-chip tiled matmul (world == 1 degenerate path; also the bench.py
# kernel: MXU-tiled, f32 accumulation).
# ---------------------------------------------------------------------------


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_tiles: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_tiles - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fit_block(dim: int, preferred: int, align: int) -> int:
    """Largest divisor of ``dim`` that is <= ``preferred`` and a multiple of
    ``align`` (Mosaic tiling: last block dim must be a multiple of 128 and
    the second-minor a multiple of 8, unless equal to the full dimension).
    When no aligned divisor exists (prime / odd-multiple dims) the only
    legal block is the FULL dimension — that is returned only when it keeps
    the kernel's VMEM footprint plausible; otherwise this raises so callers
    pad instead of silently compiling a VMEM-blowing block."""
    if preferred >= dim:
        return dim
    for cand in range(preferred, 0, -1):
        if dim % cand == 0 and cand % align == 0:
            return cand
    # No aligned divisor. Full-dim blocks are legal for Mosaic; allow modest
    # overshoot of the preference, refuse silent multi-x blowups.
    if dim <= 4 * preferred:
        return dim
    raise ValueError(
        f"no {align}-aligned divisor of {dim} <= {preferred}; pad the "
        f"operand to a multiple of {align} or pass an explicit block size")


# Two VMEM ceilings for the single-chip matmul:
# - AUTO blocks delegate to XLA beyond the conservative budget (ragged
#   shapes produce full-dim fallback blocks whose true footprint Mosaic may
#   refuse — the v5e granted ~30MB for a 3696-full-K block and OOM'd; XLA's
#   emitter handles those shapes well, so delegation is the design —
#   MEASURED at the reference smoke shape 8192x3696x8192 (bench r4):
#   XLA 2.96 ms = 168 TF/s ~ 85% MFU vs pad-and-mask Pallas (K->3712,
#   512x512xfull-K blocks) 4.05 ms ~ 61%; XLA delegation wins.
# - EXPLICIT blocks (autotuner candidates) get the raised cap with
#   ``vmem_limit_bytes`` sized generously; a config Mosaic still refuses
#   fails compile and loses the tune gracefully. This is what makes aligned
#   full-K single-pass blockings legal (the hardware has 128MB).
_AUTO_VMEM_BUDGET = 16 * 2 ** 20
_VMEM_CAP = 100 * 2 ** 20


def _matmul_vmem(bm, bn, bk, in_bytes, out_bytes) -> int:
    return (2 * (bm * bk + bk * bn) * in_bytes   # double-buffered A/B blocks
            + bm * bn * 4                        # fp32 accumulator scratch
            + 2 * bm * bn * out_bytes)           # double-buffered out block


def ag_gemm_single_chip(a, b, *, block_m: int | None = None,
                        block_n: int | None = None,
                        block_k: int | None = None, auto_block: bool = True,
                        interpret=None):
    """Blocked Pallas matmul ``(M, K) x (K, N) -> (M, N)`` with fp32
    accumulation — the world==1 path of ``ag_gemm`` and the bench kernel.
    ``auto_block`` shrinks blocks to the nearest MXU-aligned divisor.

    Default blocks (all three omitted) are the on-chip sweep winner at the
    bench shape (tools/sweep_matmul.py, v5e: 175 TFLOPs ~ 89% MFU; traffic
    argument: with N-divisor block_n fixed at 640, larger block_m cuts
    B-matrix passes — (1024, 640, 1024) fits the 16MB scoped-VMEM budget
    with double-buffered in/out blocks).

    With all-default blocks, shapes with no MXU-aligned divisor (e.g. the
    reference smoke shape's per-rank K 29568/8 = 3696) or no VMEM-feasible
    blocking DELEGATE to XLA's matmul emitter (measured ~85% MFU on ragged K) — the
    world==1 path is a degenerate fallback and Pallas earns its keep in the
    multi-device overlap kernels. Measured at the smoke shape
    (bench.py ``ragged_k_best``): the XLA emitter runs 8192x3696x8192 at
    ~85% MFU and beats a padded-K Pallas variant (~61%) — delegation is
    the documented bound, not an assumption. Explicitly-passed blocks are
    never second-guessed: infeasible explicit blocks raise."""
    m, k = a.shape
    _, n = b.shape
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    explicit = not (block_m is None and block_n is None and block_k is None)
    # GEMV regime: a sub-MXU-tile M (decode steps run M = batch = 8) is
    # pure weight-streaming — XLA's emitter reaches the HBM roofline there
    # (measured: the 28-layer qwen3-1.7b B=8 decode matmul stack runs
    # 3.6 ms vs 3.44 ms of pure weight reads), while a Pallas grid adds
    # per-tile overhead with nothing for the MXU to win back. Delegate
    # auto-blocked small-M calls; explicit blocks still force Pallas.
    if not explicit and m < 64:
        return jnp.dot(a, b, preferred_element_type=jnp.float32
                       ).astype(out_dtype)
    block_m = 1024 if block_m is None else block_m
    block_n = 640 if block_n is None else block_n
    block_k = 1024 if block_k is None else block_k
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    budget = _VMEM_CAP if explicit else _AUTO_VMEM_BUDGET
    if auto_block:
        try:
            bm = _fit_block(m, bm, 8)
            bn = _fit_block(n, bn, 128)
            bk = _fit_block(k, bk, 128)
            if _matmul_vmem(bm, bn, bk, a.dtype.itemsize,
                            out_dtype.itemsize) > budget:
                raise ValueError(
                    f"blocks ({bm},{bn},{bk}) exceed the {budget >> 20}"
                    f"MB VMEM budget")
        except ValueError:
            if explicit:
                raise
            return jnp.dot(a, b, preferred_element_type=jnp.float32
                           ).astype(out_dtype)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{k})x({k},{n}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    k_tiles = k // bk
    need = _matmul_vmem(bm, bn, bk, a.dtype.itemsize, out_dtype.itemsize)
    # Generous headroom: Mosaic's true stack need exceeds the block-math
    # estimate (observed +18% on a full-K fallback block).
    vlim = min(need + max(need // 2, 8 * 2 ** 20), _VMEM_CAP)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_tiles=k_tiles),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=(m // bm, n // bn, k_tiles),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vlim,
        ),
        name="matmul_single_chip",
        interpret=resolve_interpret(interpret),
    )(a, b)


def _fused_step_kernel(s_ref, c_ref, a_ref, b_ref, o_ref, *, n_k: int):
    prod = jnp.dot(a_ref[...], b_ref[...] + s_ref[0].astype(b_ref.dtype),
                   preferred_element_type=jnp.float32)
    if n_k == 1:
        o_ref[...] = c_ref[...] + prod
    else:
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _first():
            o_ref[...] = c_ref[...] + prod

        @pl.when(kk > 0)
        def _rest():
            o_ref[...] += prod


def fused_matmul_step(c, a, b, s=None, *, block_m: int = 512,
                      block_n: int = 640, block_k: int | None = None,
                      interpret=None):
    """One fused accumulate step: ``c + a @ (b + s)`` in fp32, ``c`` donated
    (input/output-aliased). The k-split accumulation building block — the
    epilogue-add and the operand-elementwise ``b + s`` (s scalar, None = 0)
    ride inside the kernel instead of as separate HBM round-trips, which is
    what XLA's emitter fuses for the same expression. ``block_k=None``
    streams the FULL contraction per (i, j) tile (single visit, no
    revisiting) — the measured winner at the bench shape (512, 640, K):
    0.707 ms vs XLA 0.725 at 4096x5120x3200 bf16 (ratio 0.976).

    VMEM: full-K A/B blocks exceed Mosaic's default 16MB scoped stack;
    the call sizes ``vmem_limit_bytes`` to the actual working set (v5e has
    128MB VMEM — the default limit is a guardrail, not the hardware)."""
    m, k = a.shape
    _, n = b.shape
    if c.shape != (m, n):
        raise ValueError(f"c {c.shape} != ({m}, {n})")
    bm = _fit_block(m, block_m, 8)
    bn = _fit_block(n, block_n, 128)
    bk = k if block_k is None else _fit_block(k, block_k, 128)
    n_k = k // bk
    if s is None:
        s = jnp.zeros((1,), jnp.float32)
    else:
        s = jnp.asarray(s, jnp.float32).reshape(1)
    c = c.astype(jnp.float32)
    # Double-buffered c/a/b/out blocks + headroom for Mosaic bookkeeping.
    vlim = 2 * (2 * bm * bn * 4 + bm * bk * a.dtype.itemsize
                + bk * bn * b.dtype.itemsize) + 4 * 2 ** 20
    if vlim > 100 * 2 ** 20:
        raise ValueError(
            f"fused step blocks ({bm},{bn},{bk}) need {vlim >> 20}MB VMEM; "
            f"pass a smaller block_k")
    if n_k == 1:
        grid = (m // bm, n // bn)
        semantics = ("parallel", "parallel")
        ic = lambda i, j, s_: (i, j)
        ia = lambda i, j, s_: (i, 0)
        ib = lambda i, j, s_: (0, j)
    else:
        grid = (m // bm, n // bn, n_k)
        semantics = ("parallel", "parallel", "arbitrary")
        ic = lambda i, j, kk, s_: (i, j)
        ia = lambda i, j, kk, s_: (i, kk)
        ib = lambda i, j, kk, s_: (kk, j)
    return pl.pallas_call(
        functools.partial(_fused_step_kernel, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((bm, bn), ic),
                      pl.BlockSpec((bm, bk), ia),
                      pl.BlockSpec((bk, bn), ib)],
            out_specs=pl.BlockSpec((bm, bn), ic),
        ),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=vlim),
        name="fused_matmul_step",
        interpret=resolve_interpret(interpret),
    )(s, c, a, b)


def ag_gemm_single_chip_autotuned(a, b, *, interpret=None):
    """Single-chip matmul with ON-CHIP tuned blocks: first call at a given
    (m, k, n, dtype) times the candidate blockings through the contextual
    autotuner (cached in memory + on disk), later calls reuse the winner —
    the reference's ``@contextual_autotune`` applied to the ag_gemm/gemm_rs
    consumer GEMM (autotuner.py:97)."""
    from triton_distributed_tpu.runtime.autotuner import tuned_matmul_blocks

    m, k = a.shape
    _, n = b.shape
    blocks = tuned_matmul_blocks(m, k, n, str(a.dtype))
    if blocks is None:  # ragged shape: auto path (delegates to XLA)
        return ag_gemm_single_chip(a, b, interpret=interpret)
    return ag_gemm_single_chip(a, b, block_m=blocks[0], block_n=blocks[1],
                               block_k=blocks[2], interpret=interpret)


# ---------------------------------------------------------------------------
# Host-level wrapper
# ---------------------------------------------------------------------------


def ag_gemm(a, b, *, mesh: Mesh | None = None, axis: str = "tp",
            config: AGGEMMConfig | None = None, interpret=None):
    """Standalone AG-GEMM over a mesh axis.

    ``a``: global ``(M, K)`` (sharded on M); ``b``: global ``(K, N)``
    (sharded on N). Returns global ``(M, N)`` (sharded on N): the matmul of
    the full A against B, with A's allgather overlapped into the matmul.
    """
    mesh = mesh or get_default_mesh()
    config = config or AGGEMMConfig()
    run = _build_ag_gemm(mesh, axis, config, interpret)
    if not _ledger.active():  # ledger recording or resilience hooks
        return run(a, b)
    from triton_distributed_tpu.runtime import perf_model as pm

    world = mesh.shape[axis]
    shard = a.nbytes // world  # the A gather is the op's only comm
    return _ledger.timed(
        lambda: run(a, b), "ag_gemm", axis=axis, world=world,
        nbytes=pm.wire_bytes_all_gather(shard, world), method="overlap",
        est_s=pm.est_push_all_gather(shard, world))


@functools.lru_cache(maxsize=None)
def _build_ag_gemm(mesh, axis, config, interpret):
    def f(al, bl):
        return ag_gemm_device(al, bl, axis=axis, config=config,
                              interpret=interpret)

    return jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=(P(axis, None), P(None, axis)),
            out_specs=P(None, axis),
            check_vma=False,
        )
    )


# ---------------------------------------------------------------------------
# Comm-safety analyzer registration (tools/comm_check.py; docs/analysis.md)
# ---------------------------------------------------------------------------

import numpy as _np  # noqa: E402

from triton_distributed_tpu.analysis import registry as _comm  # noqa: E402


@_comm.register("ag_gemm")
def _comm_spec_ag_gemm(world: int) -> "_comm.TraceSpec":
    m, k, bn, n_tiles = 8, 128, 128, 2
    return _comm.TraceSpec(
        body=_ag_gemm_kernel,
        args=[
            _comm.Buf("me", (1,), _np.int32, space="smem",
                      init=lambda r, w: _np.array([r], _np.int32)),
            _comm.Buf("a", (m, k)),
            _comm.Buf("b", (k, bn)),
            _comm.Buf("o", (m, bn), covered=True),
            _comm.Buf("a_full", (world, m, k)),
            _comm.Buf("a_vmem", (2, m, k), space="vmem"),
            _comm.Sem("send_sems", (world - 1,)),
            _comm.Sem("recv_sems", (world,)),
            _comm.Sem("copy_sems", (2,)),
        ],
        grid=(world, n_tiles),
        kwargs=dict(axis="tp", world=world, n_tiles=n_tiles),
    )
