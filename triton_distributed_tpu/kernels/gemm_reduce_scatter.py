"""GEMM-RS: matmul with the reduce-scatter overlapped into it.

TPU-native analog of the reference's ``kernels/nvidia/gemm_reduce_scatter.py``
(590 LoC: ``create_gemm_rs_context`` :79, ``gemm_rs`` :576, persistent
producer GEMM :130 that notifies per-tile barriers, RS consumer on a
dedicated ``rs_stream``).

TPU design: one Pallas kernel per device and ONE algorithm (partial product,
push each remote destination's rows to their owner's staging as they
complete, fold the arrived partials into the own rows in a fixed global rank
order), under one of two loop nests that ``gemm_rs_device`` chooses from its
operands' shapes alone:

* **One pass over the column tiles** (``_gemm_rs_one_pass_kernel``, grid
  ``(column tiles / piece,)``), where all ``world * m`` rows of A, the own
  ``(m, N)`` block in float32 and the arrived partials fit the VMEM a kernel
  may ask for (``_one_pass_vmem`` against ``common.fits_kernel_vmem``):
  every weight tile is copied from HBM once, in order and a few tiles ahead
  of its use through a ring of slots (B whole need not fit: a tile is met
  once), and meets the MXU ONCE, multiplied by every destination's rows in
  one product. A peer's rows of the result leave in pieces of up to
  ``PUSH_PIECE_BYTES`` (at 16 rows and tiles of 128 columns: 8 tiles, 12
  pushes a call where the other walk makes 96), from send slots
  double-buffered per peer; the own rows stay in VMEM and are folded after
  the last tile. The decode and mixed steps of a served model are here:
  with few rows a product's time is the weight tile's, not the rows', so
  four 16-row products a tile cost four times one 64-row product (PERF.md
  section 6, PR 48).
* **The grid ``(destination, column tile)``** (``_gemm_rs_kernel``) for
  everything else (a prefill-sized M, the ``probes`` build): destinations in
  swizzled order ``dst = (me + 1 + s) % world``, remote segments first, own
  segment last; a remote tile is pushed as soon as its product is done
  (double-buffered by tile parity), the last segment's steps compute the
  own rows and fold in the arrived partials tile by tile. B's tiles stay
  resident where B fits (copied once, multiplied ``world`` times), else
  they come through the pipeline once a destination.

Either way the pushes ride under later products: the reference's
producer-GEMM/RS-consumer stream pair collapsed into one kernel. The comm
ledger's traced record names the walk a call took (``method``:
``"device_one_pass"`` or ``"device"``).

Sharding convention (row-parallel TP matmul, reference TP_MLP down-proj):
  A: (M, K) sharded on K over ``axis``  -> per-device (M, k_local)
  B: (K, N) sharded on K over ``axis``  -> per-device (k_local, N)
  C: (M, N) sharded on M over ``axis``  -> per-device (m, N), m = M/world
  C[me] = sum over ranks of their partial A_r @ B_r segment ``me``.
"""

from __future__ import annotations

import dataclasses
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
from triton_distributed_tpu.runtime.platform import resolve_interpret


@dataclasses.dataclass(frozen=True)
class GEMMRSConfig:
    """Tile configuration (analog of ``ReduceScatter2DContext`` block sizes,
    reduce_scatter.py:45)."""

    block_n: int | None = None

    def n_tiles(self, n: int) -> int:
        if self.block_n is None or n % self.block_n:
            raise ValueError(f"N {n} not divisible by block_n {self.block_n}")
        return n // self.block_n

    def resolve(self, m: int, k_local: int, n: int, in_itemsize: int,
                out_itemsize: int) -> "GEMMRSConfig":
        """``block_n=None`` -> largest lane-aligned divisor of ``n`` whose
        VMEM working set (A rows + double-buffered B tile + send/acc/tmp/out
        tiles) fits Mosaic's scoped budget (see allgather_gemm)."""
        if self.block_n is not None:
            return self

        def vmem(bn: int) -> int:
            return (m * k_local * in_itemsize          # a_vmem
                    + 2 * k_local * bn * in_itemsize   # B tile (dbl-buffered)
                    + 2 * m * bn * out_itemsize        # send parity slots
                    + m * bn * 4                       # fp32 accumulator
                    + 2 * m * bn * out_itemsize)       # tmp + cast-out

        return GEMMRSConfig(block_n=common.choose_lane_block(
            n, vmem, f"gemm_rs block_n (A rows {m}x{k_local})"))


def _gemm_rs_kernel(me_ref, a_ref, b_ref, o_ref, staging, a_vmem, send_tile,
                    acc_tile, tmp_tile, out_tile, send_sems, recv_sems,
                    copy_sem, *, axis: str, world: int, n_tiles: int, bn: int,
                    probe=_probes.NULL, b_tiles=None):
    # ``b_tiles`` None: ``b_ref`` is this step's (k_local, bn) tile, through
    # the pipeline. Else B is RESIDENT: ``b_ref`` is the whole operand in
    # HBM and ``b_tiles`` its ``(b_vmem, sems)``
    # (``common.resident_weight_limit``).
    s = pl.program_id(0)
    j = pl.program_id(1)
    me = me_ref[0]
    m = o_ref.shape[0]
    k_local = a_vmem.shape[1]
    probe.enter(s * n_tiles + j, me, world)
    # Remote segments first (their pushes overlap later compute); own last.
    dst = jax.lax.rem(me + 1 + s, world)
    is_own = s == world - 1
    # VMEM staging is per n-TILE (ADVICE r1: full-segment staging blew the
    # ~16MB budget at target shapes): each remote tile is pushed to its owner
    # as soon as its partial product is done, from a parity-double-buffered
    # (2, m, bn) slot. ``t`` counts remote tiles globally (own segment last,
    # so remote tiles occupy t = 0 .. (world-1)*n_tiles - 1 contiguously).
    t = s * n_tiles + j
    parity = jax.lax.rem(t, 2)
    total_remote = (world - 1) * n_tiles

    @pl.when((s == 0) & (j == 0))
    def _startup():
        if b_tiles is not None:
            # Every tile's copy in flight before anything waits: the one
            # read of the weights, at the DMA engines' rate.
            for jj in range(n_tiles):
                common.weight_tile_copy(me_ref, b_ref, b_tiles, jj, bn).start()
        dl.barrier_all(axis)  # staging live everywhere before pushes land
        probe.sem_spin(world - 1)

    if b_tiles is not None:
        # First touch (the first destination's walk); the other
        # destinations multiply what is already there.
        @pl.when(s == 0)
        def _tile_arrived():
            common.weight_tile_copy(me_ref, b_ref, b_tiles, j, bn).wait()

    # Load this destination's A rows once per segment.
    @pl.when(j == 0)
    def _load():
        common.local_copy(a_ref.at[pl.ds(dst * m, m)], a_vmem, copy_sem,
                          probe=probe)

    # Reusing a send_tile parity slot: its push (started at tile t-2, same
    # parity) must have locally drained.
    @pl.when(~is_own & (t >= 2))
    def _reclaim():
        common.wait_send(send_tile.at[parity], send_sems.at[parity],
                         probe=probe)

    partial = jnp.dot(a_vmem[...],
                      b_ref[...] if b_tiles is None else b_tiles[0][j],
                      preferred_element_type=jnp.float32)
    probe.compute(2 * m * k_local * bn)

    # Tile complete -> push it to its owner's staging column immediately
    # (async; overlaps every later matmul — the reference's per-tile notify +
    # rs_stream, at tile rather than segment granularity).
    @pl.when(~is_own)
    def _push_tile():
        send_tile[parity] = partial.astype(send_tile.dtype)
        common.remote_copy(
            send_tile.at[parity],
            staging.at[common.peer_slot(me, dst), :, pl.ds(j * bn, bn)],
            send_sems.at[parity], recv_sems.at[me], axis, dst, probe=probe)

    # Own segment (last): fold the world-1 remote partials per tile, in a
    # FIXED global rank order so the reduction bits are rank-independent
    # (ADVICE r1: rank-relative order made replicated collectives diverge).
    @pl.when(is_own)
    def _own_segment():
        @pl.when(j == 0)
        def _arrivals():
            for src in range(world):
                @pl.when(src != me)
                def _wait(src=src):
                    common.wait_recv(staging.at[common.peer_slot(src, me)],
                                     recv_sems.at[src], probe=probe)

        acc_tile[...] = jnp.zeros_like(acc_tile)
        for src in range(world):
            @pl.when(src == me)
            def _add_own():
                acc_tile[...] += partial

            @pl.when(src != me)
            def _add_remote(src=src):
                common.local_copy(
                    staging.at[common.peer_slot(src, me), :,
                               pl.ds(j * bn, bn)],
                    tmp_tile, copy_sem, probe=probe)
                acc_tile[...] += tmp_tile[...].astype(jnp.float32)
        probe.compute(world * m * bn)
        out_tile[...] = acc_tile[...].astype(out_tile.dtype)
        common.local_copy(out_tile, o_ref.at[:, pl.ds(j * bn, bn)], copy_sem,
                          probe=probe)

        # Drain the last push per parity slot (every earlier push was
        # reclaimed by the t-2 wait above).
        @pl.when(j == n_tiles - 1)
        def _drain():
            for p in range(min(2, total_remote)):
                common.wait_send(send_tile.at[p], send_sems.at[p],
                                 probe=probe)


# The most a remote destination's piece of the one-pass walk holds before it
# is pushed: a copy costs the same up to about 32 KB (PERF.md section 6,
# PR 42's table), so a piece is as many column tiles as fit under it. At 16
# rows (8 tiles a piece, 4 grid steps where a push a tile makes 32) that is
# 1.5-1.8 us of a 23-45 us call, four chips, real pushes (PERF.md section 6,
# PR 48's review round).
PUSH_PIECE_BYTES = 32 * 2 ** 10


# Weight tiles the one-pass walk keeps in flight ahead of its product, in a
# ring of one slot more. In order and a few ahead, a tile is there when the
# walk meets it and the fetch runs under the products; all of a call's
# copies started at once share the DMA engines, every tile lands near the
# end and the products wait for the whole fetch (one-chip probe, PERF.md
# section 6, PR 48: 44.4 us a call against 54.7 at the down projection's
# decode shape; 2, 4 and 8 ahead read alike).
TILES_IN_FLIGHT = 4


def _ring_slots(n_tiles: int) -> int:
    """Slots of the one-pass walk's ring of weight tiles: the tiles in
    flight and the one being multiplied."""
    return min(TILES_IN_FLIGHT, n_tiles) + 1


def _tiles_a_piece(m: int, bn: int, itemsize: int, n_tiles: int) -> int:
    """Column tiles of one pushed piece ``(m, tiles * bn)`` of the one-pass
    walk: the most that divide ``n_tiles`` and keep the piece within
    ``PUSH_PIECE_BYTES`` (one where a tile alone is past it)."""
    fit = max(1, PUSH_PIECE_BYTES // (m * bn * itemsize))
    return max(g for g in range(1, n_tiles + 1)
               if n_tiles % g == 0 and g <= fit)


def _one_pass_vmem(world: int, m: int, k_local: int, n: int, bn: int,
                   group: int, in_itemsize: int, out_itemsize: int) -> int:
    """VMEM of the one-pass walk: A whole and the ring of weight tiles, the
    own block in float32, the send slots (two a peer), the arrived
    partials, the cast-out block, the fold's accumulator and a step's
    product. B whole is NOT in it: each tile is met once."""
    gw = group * bn
    return ((world * m * k_local + _ring_slots(n // bn) * k_local * bn)
            * in_itemsize
            + m * n * 4
            + (world - 1) * 2 * m * gw * out_itemsize
            + world * m * n * out_itemsize
            + m * gw * 4 + world * m * bn * 4)


def _gemm_rs_one_pass_kernel(me_ref, a_ref, b_ref, o_ref, staging, a_vmem,
                             send, own, land, out_vmem, acc, send_sems,
                             recv_sems, copy_sems, *, axis: str, world: int,
                             n_tiles: int, bn: int, group: int, b_tiles):
    """The walk over the column tiles ONCE (grid ``(n_tiles // group,)``):
    a step multiplies ALL ``world * m`` rows by each of its ``group``
    tiles, so every weight tile is copied once and meets the MXU once a
    call (``b_ref`` is the whole operand in HBM, ``b_tiles`` the ring its
    tiles pass through). A's row blocks lie in ``a_vmem`` in push order,
    the remote destinations ``(me + 1 + s) % world`` first and the own
    block last, so a product's row block ``s`` belongs to a destination
    known at trace time."""
    j = pl.program_id(0)
    me = me_ref[0]
    m = o_ref.shape[0]
    n_groups = n_tiles // group
    gw = group * bn
    parity = jax.lax.rem(j, 2)
    dsts = [jax.lax.rem(me + 1 + s, world) for s in range(world)]  # own last

    def a_block(s):
        return pltpu.make_async_copy(a_ref.at[pl.ds(dsts[s] * m, m)],
                                     a_vmem.at[pl.ds(s * m, m)],
                                     copy_sems.at[s])

    ring = _ring_slots(n_tiles)  # tile jj passes through slot jj % ring
    ahead = ring - 1

    def b_tile(jj):
        return common.weight_tile_copy(me_ref, b_ref, b_tiles, jj, bn,
                                       slot=jax.lax.rem(jj, ring))

    @pl.when(j == 0)
    def _startup():
        for s in range(world):
            a_block(s).start()
        for jj in range(ahead):
            b_tile(jj).start()
        dl.barrier_all(axis)  # staging live everywhere before pushes land
        for s in range(world):
            a_block(s).wait()

    # A peer's send slot of this parity: its push of two steps back must
    # have locally drained.
    @pl.when(j >= 2)
    def _reclaim():
        for s in range(world - 1):
            common.wait_send(send.at[s, parity], send_sems.at[s, parity])

    for t in range(group):
        tile = j * group + t
        b_tile(tile).wait()

        # Into the slot whose tile the product before this one consumed.
        @pl.when(tile + ahead < n_tiles)
        def _next_tile(tile=tile):
            b_tile(tile + ahead).start()

        prod = jnp.dot(a_vmem[...], b_tiles[0][jax.lax.rem(tile, ring)],
                       preferred_element_type=jnp.float32)
        cols = pl.ds(t * bn, bn)
        for s in range(world - 1):
            send[s, parity, :, cols] = prod[s * m:(s + 1) * m].astype(
                send.dtype)
        own[j, :, cols] = prod[(world - 1) * m:]

    # The step's piece of each remote destination's rows, to its owner's
    # staging: world - 1 pushes in flight under the next step's products.
    for s in range(world - 1):
        common.remote_copy(
            send.at[s, parity],
            staging.at[common.peer_slot(me, dsts[s]), :, pl.ds(j * gw, gw)],
            send_sems.at[s, parity], recv_sems.at[me], axis, dsts[s])

    @pl.when(j == n_groups - 1)
    def _fold():
        for src in range(world):
            @pl.when(src != me)
            def _wait(src=src):
                common.wait_recv(staging.at[common.peer_slot(src, me)],
                                 recv_sems.at[src])

        # A piece at a time, in loops (a body is compiled once whatever the
        # pieces: 32 at the mixed step's rows). Every source's partial of a
        # piece comes in ONE copy, all copies in flight together and all
        # awaited before any is read (they share a semaphore, which counts
        # bytes, not pieces); a folded piece leaves for the output while
        # the next is folded, one such copy in flight.
        def arrived(g):
            return pltpu.make_async_copy(
                staging.at[:, :, pl.ds(g * gw, gw)], land.at[g],
                copy_sems.at[0])

        def leaves(g):
            return pltpu.make_async_copy(
                out_vmem.at[g], o_ref.at[:, pl.ds(g * gw, gw)],
                copy_sems.at[1])

        def fold_piece(g):
            # The same sum as the two-axis walk's: FIXED global rank
            # order, remote partials in the dtype they travelled in, own
            # in float32.
            acc[...] = jnp.zeros_like(acc)
            for src in range(world):
                @pl.when(src == me)
                def _add_own():
                    acc[...] += own[g]

                @pl.when(src != me)
                def _add_remote(src=src):
                    acc[...] += land[g, common.peer_slot(src, me)].astype(
                        jnp.float32)
            out_vmem[g] = acc[...].astype(out_vmem.dtype)

            @pl.when(g > 0)
            def _left():
                leaves(g - 1).wait()

            leaves(g).start()

        def every_piece(fn):
            jax.lax.fori_loop(0, n_groups, lambda g, _: fn(g), None)

        every_piece(lambda g: arrived(g).start())
        every_piece(lambda g: arrived(g).wait())
        every_piece(fold_piece)
        leaves(n_groups - 1).wait()

        # Drain the last push of each send slot (every earlier one was
        # reclaimed two steps on).
        for s in range(world - 1):
            for p in range(min(2, n_groups)):
                common.wait_send(send.at[s, p], send_sems.at[s, p])


def gemm_rs_device(a_local, b_local, *, axis: str = "tp",
                   config: GEMMRSConfig | None = None, interpret=None,
                   probes: bool = False, layer=None):
    """Per-device GEMM-RS (composable inside shard_map):
    ``(M, k_local) x (k_local, N) -> (m, N)`` — segment ``me`` of the
    reduce-scattered full product, comm overlapped into the matmul.

    With ``probes=True`` (a separate compile) returns ``(out, probe_buf)``
    where ``probe_buf`` is the device-telemetry record decoded by
    ``obs.kprobe`` (one row per grid step).

    ``b_local`` may be the layer STACK ``(L, k_local, N)`` with ``layer`` ()
    int32, traced (a model's ``lax.scan`` body; see ``ag_gemm_device``):
    the index rides as a second prefetched scalar beside ``me``, so the
    tiles come out of the stack where it lies and no pass stages the
    layer's matrix before the kernel starts.

    The walk is chosen from the operands' shapes (module docstring): where A
    whole, the own block in float32 and the arrived partials fit the VMEM a
    kernel may ask for (``_one_pass_vmem`` against
    ``common.fits_kernel_vmem``: Qwen3-8B's TP=4 down projection takes
    5 MB at a decode step's 64 rows and 14 MB at the mixed step's 512) the
    column tiles are walked ONCE, every destination's rows in one product a
    tile, the weight through a ring of a few tiles; else the grid is
    ``(destination, column tile)``, with B's tiles resident where B whole
    fits (copied from HBM once and multiplied ``world`` times) and through
    the pipeline once a destination where it does not. ``block_n`` is the
    weight's column tile in both. The comm ledger's record says which ran
    (``method="device_one_pass"`` / ``"device"``). The single-device branch
    and the ``probes`` build (one record a ``(destination, tile)`` step)
    take ``b_local[layer]``."""
    config = config or GEMMRSConfig()
    world = _axis_size(axis)
    M, k_local = a_local.shape
    stacked, n = common.weight_operand(b_local, layer, k_local)
    if stacked and (world == 1 or probes):
        b_local, layer = b_local[layer], None
    if world == 1:
        from triton_distributed_tpu.kernels.allgather_gemm import ag_gemm_single_chip
        # No block override: an explicit block would forfeit the automatic
        # XLA delegation on ragged/VMEM-infeasible shapes (world==1 is the
        # degenerate path; config.block_n tiles the multi-device grid only).
        out = ag_gemm_single_chip(a_local, b_local, interpret=interpret)
        return (out, _probes.host_stub_buffer()) if probes else out
    if M % world:
        raise ValueError(f"M {M} not divisible by world {world}")
    m = M // world
    m_pad = common.mosaic_row_pad(m, a_local.dtype, interpret)
    if m_pad != m:
        # Mosaic only (see ag_gemm_device): pad every destination segment
        # of A with zero rows; the scattered result drops them again.
        a_pad = jnp.pad(a_local.reshape(world, m, k_local),
                        ((0, 0), (0, m_pad - m), (0, 0)))
        res = gemm_rs_device(
            a_pad.reshape(world * m_pad, k_local), b_local, axis=axis,
            config=config, interpret=interpret, probes=probes, layer=layer)
        return (res[0][:m], res[1]) if probes else res[:m]
    out_dtype = jnp.promote_types(a_local.dtype, b_local.dtype)
    from triton_distributed_tpu.runtime import perf_model as pm

    config = config.resolve(m, k_local, n, a_local.dtype.itemsize,
                            out_dtype.itemsize)
    n_tiles = config.n_tiles(n)
    bn = config.block_n
    isz, osz = a_local.dtype.itemsize, out_dtype.itemsize

    # The walk, from the operands' shapes: ONE pass over the column tiles
    # where all of A, the own block and the arrived partials fit the VMEM a
    # kernel may ask for (the weight passes through a ring), else the grid
    # (destination, column tile), B resident where B fits. No bound on the
    # rows: at 256 and 512 a device, the most that fit, the one pass read
    # 37-47% under the grid on four chips (PERF.md section 6, PR 48's review
    # round).
    group = _tiles_a_piece(m, bn, osz, n_tiles)
    one_pass, vmem_limit = (False, None) if probes else \
        common.fits_kernel_vmem(
            _one_pass_vmem(world, m, k_local, n, bn, group, isz, osz))
    # ``tile_slots``: the VMEM slots the kernel copies B's tiles into
    # itself, B whole in HBM (0: the tiles come through the pipeline).
    if one_pass:
        tile_slots = _ring_slots(n_tiles)
        n_groups, gw = n_tiles // group, group * bn
        grid = (n_groups,)
        kernel = functools.partial(
            _gemm_rs_one_pass_kernel, axis=axis, world=world,
            n_tiles=n_tiles, bn=bn, group=group)
        scratch_shapes = [
            pltpu.VMEM((M, k_local), a_local.dtype),          # A, push order
            pltpu.VMEM((world - 1, 2, m, gw), out_dtype),     # send slots
            pltpu.VMEM((n_groups, m, gw), jnp.float32),       # own block
            pltpu.VMEM((n_groups, world - 1, m, gw), out_dtype),  # arrived
            pltpu.VMEM((n_groups, m, gw), out_dtype),         # cast-out
            pltpu.VMEM((m, gw), jnp.float32),                 # fold acc
            common.dma_sems((world - 1, 2)),           # send (peer, parity)
            common.dma_sems(world),                    # recv (slot per src)
            common.dma_sems(world),                    # A's row blocks
        ]
    else:
        # A rows, B whole, and the send (2), accumulator, remote-partial
        # and cast-out tiles.
        resident, vmem_limit = common.resident_weight_limit(
            (m * k_local + k_local * n) * isz + m * bn * (4 * osz + 4),
            probes)
        tile_slots = n_tiles if resident else 0
        grid = (world, n_tiles)
        kernel = functools.partial(_gemm_rs_kernel, axis=axis, world=world,
                                   n_tiles=n_tiles, bn=bn)
        scratch_shapes = [
            pltpu.VMEM((m, k_local), a_local.dtype),  # dst-segment A rows
            pltpu.VMEM((2, m, bn), out_dtype),        # per-tile send buffer
            pltpu.VMEM((m, bn), jnp.float32),         # own-tile accumulator
            pltpu.VMEM((m, bn), out_dtype),           # remote-partial tile
            pltpu.VMEM((m, bn), out_dtype),           # cast-out tile
            common.dma_sems(2),                       # send (by tile parity)
            common.dma_sems(world),                   # recv (slot per src)
            pltpu.SemaphoreType.DMA(()),
        ]

    # Each device scatters its whole (M, n) partial product (M as it
    # travels: after the Mosaic row pad above). A series of its own beside
    # the host wrapper's "overlap" (see ag_gemm_device); ``method`` names
    # the walk this call took.
    _ledger.record_traced(
        "gemm_rs", axis=axis, world=world,
        method="device_one_pass" if one_pass else "device",
        nbytes=pm.wire_bytes_reduce_scatter(M * n * osz, world))
    me, b_spec = common.rank_and_weight_spec(axis, k_local, bn, layer,
                                             tile_slots > 0)

    # Incoming-partials staging is an ANY-space OUTPUT (discarded): Mosaic
    # does not allocate HBM scratch, and peer pushes need a stable HBM buffer
    # on every device — kernel arg order is unchanged (first-scratch ->
    # last-output position).
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),                    # a_local
        b_spec,
    ]
    out_specs = [
        common.hbm_spec(),                                    # (m, N)
        common.hbm_spec(),                                    # staging
    ]
    out_shape = [
        jax.ShapeDtypeStruct((m, n), out_dtype),
        jax.ShapeDtypeStruct((world - 1, m, n), out_dtype),
    ]
    if tile_slots:
        kernel, scratch_shapes = common.with_resident_tiles(
            kernel, scratch_shapes, tile_slots, k_local, bn, b_local.dtype)
    if probes:
        n_steps = world * n_tiles

        def body(me_ref, a_ref, b_ref, o_ref, staging, pbuf, a_vmem,
                 send_tile, acc_tile, tmp_tile, out_tile, send_sems,
                 recv_sems, copy_sem, pord, kernel=kernel):
            kernel(me_ref, a_ref, b_ref, o_ref, staging, a_vmem, send_tile,
                   acc_tile, tmp_tile, out_tile, send_sems, recv_sems,
                   copy_sem,
                   probe=_probes.Probe(pbuf, pord, n_steps=n_steps))

        kernel = body
        out_specs = [*out_specs, _probes.out_spec()]
        scratch_shapes = [*scratch_shapes, _probes.ord_scratch()]
        out_shape = [*out_shape, _probes.out_shape(n_steps)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        compiler_params=common.compiler_params(
            common.collective_id_for("gemm_rs"), vmem_limit),
        cost_estimate=common.cost_estimate(
            flops=2 * M * k_local * n,
            bytes_accessed=(M * k_local * a_local.dtype.itemsize
                            + (1 if tile_slots else world) * k_local * n
                            * b_local.dtype.itemsize
                            + M * n * out_dtype.itemsize),
            remote_bytes=(world - 1) * m * n * out_dtype.itemsize),
        name="gemm_rs",
        interpret=resolve_interpret(interpret),
    )(me, a_local, b_local)
    return (outs[0], outs[2]) if probes else outs[0]


def _gemm_rs_loopback_kernel(a_ref, b_ref, o_ref, staging, a_vmem, send_tile,
                             acc_tile, tmp_tile, out_tile, send_sems,
                             copy_sem, *, segments: int, n_tiles: int,
                             bn: int):
    s = pl.program_id(0)
    j = pl.program_id(1)
    m = o_ref.shape[0]
    # Same swizzle as the device kernel with me=0: remote destination
    # segments 1..segments-1 first, own segment 0 last.
    dst = jax.lax.rem(1 + s, segments)
    is_own = s == segments - 1
    t = s * n_tiles + j
    parity = jax.lax.rem(t, 2)
    total_remote = (segments - 1) * n_tiles

    # This destination's A rows into VMEM once per segment.
    @pl.when(j == 0)
    def _load():
        common.local_copy(a_ref.at[pl.ds(dst * m, m)], a_vmem, copy_sem)

    # Reusing a send_tile parity slot: its push (tile t-2, same parity) must
    # have drained — identical reclaim discipline to the device kernel.
    @pl.when(~is_own & (t >= 2))
    def _reclaim():
        common.wait_send(send_tile.at[parity], send_sems.at[parity])

    partial = jnp.dot(a_vmem[...], b_ref[...],
                      preferred_element_type=jnp.float32)

    # Tile complete -> "push" it to the owner's staging column: the local
    # DMA engine stands in for the ICI link (same staging buffer, same
    # parity double-buffering, same per-tile async start).
    @pl.when(~is_own)
    def _push_tile():
        send_tile[parity] = partial.astype(send_tile.dtype)
        pltpu.make_async_copy(
            send_tile.at[parity],
            staging.at[dst - 1, :, pl.ds(j * bn, bn)],
            send_sems.at[parity]).start()

    # Own segment (last): fold the segments-1 staged partials per tile. A
    # local DMA's completion semaphore IS the arrival signal, so the
    # remaining in-flight pushes are drained up front (the device kernel
    # tracks arrival with separate recv semaphores and drains at exit).
    @pl.when(is_own)
    def _own_segment():
        @pl.when(j == 0)
        def _drain():
            for p in range(min(2, total_remote)):
                common.wait_send(send_tile.at[p], send_sems.at[p])

        acc_tile[...] = partial
        for src in range(segments - 1):
            common.local_copy(
                staging.at[src, :, pl.ds(j * bn, bn)], tmp_tile, copy_sem)
            acc_tile[...] += tmp_tile[...].astype(jnp.float32)
        out_tile[...] = acc_tile[...].astype(out_tile.dtype)
        common.local_copy(out_tile, o_ref.at[:, pl.ds(j * bn, bn)], copy_sem)


def gemm_rs_loopback(a, b, *, segments: int = 8,
                     config: GEMMRSConfig | None = None, interpret=None):
    """Single-chip SELF-LOOPBACK GEMM-RS: the full overlap machinery of
    ``gemm_rs_device`` — per-tile push-as-computed partials, parity
    double-buffered send tiles, HBM staging, fixed-order fold — with the
    world-1 ICI pushes replaced by local DMA-engine copies (the GEMM-RS
    counterpart of ``ag_gemm_loopback``; VERDICT r3 missing #1).

    ``a``: (M, k) with M = segments * m; ``b``: (k, N). Computes every
    segment's partial product A[seg] @ B (same FLOPs as the full matmul),
    pushes the segments-1 "remote" partials tile-by-tile through staging,
    and folds them into the own segment: returns ``(m, N)`` =
    ``(sum of A row blocks) @ B`` — deterministic and testable.

    Comparing against the bare full matmul at the same FLOPs measures how
    much of the per-tile push/fold traffic hides behind the MXU
    (bench.py ``gemm_rs_overlap_efficiency``)."""
    config = config or GEMMRSConfig()
    M, k = a.shape
    _, n = b.shape
    if M % segments:
        raise ValueError(f"M {M} not divisible by segments {segments}")
    m = M // segments
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    if config.block_n is None:
        # The loopback costs one extra (k, bn) input-tile buffer beyond the
        # device kernel's working set (measured against the Mosaic enforcer
        # at the Qwen3-32B TP=8 shape: 16.46M actual vs 12.97M by the shared
        # formula at bn=512, while gemm_rs_device AOT-compiles there), so it
        # gets its own chooser rather than inflating the shared one.
        isz, osz = a.dtype.itemsize, out_dtype.itemsize

        def vmem(bn: int) -> int:
            return (m * k * isz + 3 * k * bn * isz
                    + 2 * m * bn * osz + m * bn * 4 + 2 * m * bn * osz)

        config = GEMMRSConfig(block_n=common.choose_lane_block(
            n, vmem, f"gemm_rs_loopback block_n (A rows {m}x{k})"))
    n_tiles = config.n_tiles(n)
    bn = config.block_n
    out, _ = pl.pallas_call(
        functools.partial(_gemm_rs_loopback_kernel, segments=segments,
                          n_tiles=n_tiles, bn=bn),
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((segments - 1, m, n), out_dtype),
        ],
        grid=(segments, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((k, bn), lambda s, j: (0, j)),
        ],
        out_specs=[
            common.hbm_spec(),
            common.hbm_spec(),
        ],
        scratch_shapes=[
            pltpu.VMEM((m, k), a.dtype),
            pltpu.VMEM((2, m, bn), out_dtype),
            pltpu.VMEM((m, bn), jnp.float32),
            pltpu.VMEM((m, bn), out_dtype),
            pltpu.VMEM((m, bn), out_dtype),
            common.dma_sems(2),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        name="gemm_rs_loopback",
        interpret=resolve_interpret(interpret),
    )(a, b)
    return out


def gemm_rs_2d_device(a_local, b_local, *, ici_axis: str = "ici",
                      dcn_axis: str = "dcn",
                      config: GEMMRSConfig | None = None, interpret=None):
    """Inter-slice GEMM-RS over a (dcn, ici) mesh — the DCN leg of the
    row-parallel overlap op (the reference's 2D reduce-scatter: intra-node
    scatter -> local reduce -> inter-node p2p of same-local-rank segments,
    ``reduce_scatter.py:45,:605``).

    K is sharded over ALL devices (dcn-major): per-device A ``(M, k_local)``,
    B ``(k_local, N)``. Returns ``(M / (n_slices * w_ici), N)`` — this
    device's segment of the fully-reduced product.

    TPU design: a ring reduce-scatter over the DCN axis at slice-block
    granularity. At step t a slice computes the intra-slice GEMM-RS (the
    Pallas overlap kernel — push-as-computed partials over ICI) for the M
    block owned by slice ``(sid - 1 - t) % n_slices``, adds the partial
    accumulator arriving from the previous slice in the ring, and forwards.
    After ``n_slices`` steps each device holds its own block with all
    ``n_slices * w_ici`` contributions folded in. The next step's kernel has
    no data dependence on the in-flight ppermute (only the cheap add joins
    them), so XLA runs the DCN hop under the intra-slice overlapped matmul."""
    from triton_distributed_tpu.kernels.collective_2d import (
        dcn_ring_reduce_scatter,
    )

    n_slices = _axis_size(dcn_axis)
    if n_slices == 1:
        return gemm_rs_device(a_local, b_local, axis=ici_axis, config=config,
                              interpret=interpret)
    w_ici = _axis_size(ici_axis)
    M, k_local = a_local.shape
    n = b_local.shape[1]
    if M % (n_slices * w_ici):
        raise ValueError(
            f"M {M} not divisible by world {n_slices * w_ici}")
    m_slice = M // n_slices
    m_out = m_slice // w_ici
    out_dtype = jnp.promote_types(a_local.dtype, b_local.dtype)

    def part(blk):                                    # (m_out, n) fp32
        a_blk = jax.lax.dynamic_slice(
            a_local, (blk * m_slice, 0), (m_slice, k_local))
        return gemm_rs_device(a_blk, b_local, axis=ici_axis, config=config,
                              interpret=interpret).astype(jnp.float32)

    acc = dcn_ring_reduce_scatter(
        part, jnp.zeros((m_out, n), jnp.float32), dcn_axis=dcn_axis)
    return acc.astype(out_dtype)


def gemm_rs(a, b, *, mesh: Mesh | None = None, axis: str = "tp",
            config: GEMMRSConfig | None = None, interpret=None):
    """Standalone GEMM-RS over a mesh axis.

    ``a``: global ``(M, K)`` sharded on K; ``b``: global ``(K, N)`` sharded
    on K. Returns global ``(M, N)`` sharded on M = the full product reduced
    over the K partials, scattered by M segment.
    """
    mesh = mesh or get_default_mesh()
    config = config or GEMMRSConfig()
    run = _build_gemm_rs(mesh, axis, config, interpret)
    if not _ledger.active():  # ledger recording or resilience hooks
        return run(a, b)
    from triton_distributed_tpu.runtime import perf_model as pm

    world = mesh.shape[axis]
    # Each device scatters its full (M, N) partial product.
    out_itemsize = jnp.promote_types(a.dtype, b.dtype).itemsize
    per_dev = a.shape[0] * b.shape[1] * out_itemsize
    return _ledger.timed(
        lambda: run(a, b), "gemm_rs", axis=axis, world=world,
        nbytes=pm.wire_bytes_reduce_scatter(per_dev, world),
        method="overlap", est_s=pm.est_oneshot_reduce_scatter(per_dev, world))


@functools.lru_cache(maxsize=None)
def _build_gemm_rs(mesh, axis, config, interpret):
    def f(al, bl):
        return gemm_rs_device(al, bl, axis=axis, config=config,
                              interpret=interpret)

    return jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=(P(None, axis), P(axis, None)),
            out_specs=P(axis, None),
            check_vma=False,
        )
    )


# ---------------------------------------------------------------------------
# Comm-safety analyzer registration (tools/comm_check.py; docs/analysis.md)
# ---------------------------------------------------------------------------

import numpy as _np  # noqa: E402

from triton_distributed_tpu.analysis import registry as _comm  # noqa: E402


@_comm.register("gemm_rs")
def _comm_spec_gemm_rs(world: int) -> "_comm.TraceSpec":
    m, k, bn, n_tiles = 8, 128, 128, 2
    n = bn * n_tiles
    return _comm.TraceSpec(
        body=_gemm_rs_kernel,
        args=[
            _comm.Buf("me", (1,), _np.int32, space="smem",
                      init=lambda r, w: _np.array([r], _np.int32)),
            _comm.Buf("a", (world * m, k)),
            _comm.Buf("b", (k, bn)),
            _comm.Buf("o", (m, n), covered=True),
            _comm.Buf("staging", (world - 1, m, n)),
            _comm.Buf("a_vmem", (m, k), space="vmem"),
            _comm.Buf("send_tile", (2, m, bn), space="vmem"),
            _comm.Buf("acc_tile", (m, bn), space="vmem"),
            _comm.Buf("tmp_tile", (m, bn), space="vmem"),
            _comm.Buf("out_tile", (m, bn), space="vmem"),
            _comm.Sem("send_sems", (2,)),
            _comm.Sem("recv_sems", (world,)),
            _comm.Sem("copy_sem"),
        ],
        grid=(world, n_tiles),
        kwargs=dict(axis="tp", world=world, n_tiles=n_tiles, bn=bn),
    )


@_comm.register("gemm_rs.one_pass")
def _comm_spec_gemm_rs_one_pass(world: int) -> "_comm.TraceSpec":
    # Six column tiles in pieces of two: three grid steps (the third
    # reclaims the first's send slots) over a ring of five weight tiles.
    m, k, bn, n_tiles, group = 8, 128, 128, 6, 2
    n, gw = bn * n_tiles, bn * group

    def body(*refs, **kw):  # as common.with_resident_tiles hands them
        _gemm_rs_one_pass_kernel(*refs[:-2], b_tiles=refs[-2:], **kw)

    return _comm.TraceSpec(
        body=body,
        args=[
            _comm.Buf("me", (1,), _np.int32, space="smem",
                      init=lambda r, w: _np.array([r], _np.int32)),
            _comm.Buf("a", (world * m, k)),
            _comm.Buf("b", (k, n)),
            _comm.Buf("o", (m, n), covered=True),
            _comm.Buf("staging", (world - 1, m, n)),
            _comm.Buf("a_vmem", (world * m, k), space="vmem"),
            _comm.Buf("send", (world - 1, 2, m, gw), space="vmem"),
            _comm.Buf("own", (n_tiles // group, m, gw), space="vmem"),
            _comm.Buf("land", (n_tiles // group, world - 1, m, gw),
                      space="vmem"),
            _comm.Buf("out_vmem", (n_tiles // group, m, gw), space="vmem"),
            _comm.Buf("acc", (m, gw), space="vmem"),
            _comm.Sem("send_sems", (world - 1, 2)),
            _comm.Sem("recv_sems", (world,)),
            _comm.Sem("copy_sems", (world,)),
            _comm.Buf("b_vmem", (_ring_slots(n_tiles), k, bn), space="vmem"),
            _comm.Sem("b_sems", (_ring_slots(n_tiles),)),
        ],
        grid=(n_tiles // group,),
        kwargs=dict(axis="tp", world=world, n_tiles=n_tiles, bn=bn,
                    group=group),
    )
