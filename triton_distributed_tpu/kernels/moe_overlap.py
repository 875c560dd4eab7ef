"""MoE-TP hybrid overlap kernels: AG-GroupGEMM and GroupGEMM-topk-reduce-RS.

TPU-native analogs of the reference's ``allgather_group_gemm.py`` (605 LoC:
``MoEAllGatherGroupGEMMTensorParallelContext`` :198, ``ag_group_gemm`` :398,
sorted gather index calc :83, block-aligned scheduling via the csrc
``moe_ag_scatter_align_block_size`` CUDA op) and ``moe_reduce_rs.py``
(1432 LoC: rowise grouped-GEMM producer :380, topk-reduce + RS consumer
:486/:564, ``moe_reduce_rs_rowise`` :816).

TPU design — the reference's dynamic tile alignment becomes a static
capacity grid, and both ops are SINGLE Pallas kernels with comm overlapped
into the grouped GEMM:

- Each device pre-routes its local (token, k) pairs into an (E, cap, d)
  per-expert capacity grid (``moe_utils.route_to_experts`` — plain jnp
  argsort/scatter; the alignment-op analog). Empty slots are zero, so they
  multiply through to zero rows — no masking inside the kernels.
- ``ag_group_gemm_device``: the AG-GEMM structure (allgather_gemm.py:65)
  with an expert dimension. At startup every device pushes its grid to all
  peers (async ICI DMAs); the grid walks (segment, expert, f-tile) in
  arrival-swizzled order, and the MXU computes each arrived source's
  per-expert (cap, d) x (d, bf) tile while later segments are still in
  flight. Output (E, world*cap, f_local) keeps per-source slot ranges, so
  grouped-layout bookkeeping is implicit (slot (src, e, i) = row
  src*cap + i of expert e).
- ``group_gemm_rs_device``: the GEMM-RS structure (gemm_reduce_scatter.py)
  with an expert dimension: destination segments first, each (dst, e,
  d-tile) partial pushed to its owner the moment the MXU finishes it; the
  own segment folds arrivals in fixed global rank order. Output (E, cap, d)
  = this device's tokens' rows, fully reduced over the f shards.
- ``ag_moe_mlp_device`` chains them: route -> AG-GroupGEMM(up) -> act ->
  GroupGEMM-RS(down) -> local topk-combine.

Sharding convention (EP within TP, reference test_ag_moe.py):
  tokens:   (M, d) sharded on M over ``axis``   -> per-device (m, d)
  topk_ids: (M, k) sharded on M                 -> per-device (m, k)
  w_up:     (E, d, f) sharded on f (column-parallel per expert)
  w_down:   (E, f, d) sharded on f (row-parallel per expert)
"""

from __future__ import annotations

import dataclasses
import functools

import jax
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.language import primitives as dl
from triton_distributed_tpu.kernels import common
from triton_distributed_tpu.kernels import moe_utils
from triton_distributed_tpu.kernels import probes as _probes
from triton_distributed_tpu.obs import comm_ledger as _ledger
from triton_distributed_tpu.runtime.platform import resolve_interpret


@dataclasses.dataclass(frozen=True)
class MoEOverlapConfig:
    """Tile configuration (the analog of the reference context block sizes,
    allgather_group_gemm.py:198). The contraction dims are tiled too
    (``block_k``) so VMEM scales with blocks, not with d/f_local — full-
    contraction VMEM blew the scoped budget at production shapes (r2
    review)."""

    block_f: int = 256   # f_local tiling in the up-projection kernel
    block_d: int = 256   # d tiling in the down-projection RS kernel
    block_k: int = 512   # contraction tiling (d in up, f_local in down)

    @staticmethod
    def tiles(dim: int, block: int) -> tuple[int, int]:
        b = min(block, dim)
        if dim % b:
            raise ValueError(f"dim {dim} not divisible by block {b}")
        return dim // b, b


# ---------------------------------------------------------------------------
# AG-GroupGEMM: allgather of capacity grids overlapped into per-expert GEMMs.
# ---------------------------------------------------------------------------


def _ag_group_gemm_kernel(me_ref, x_ref, w_ref, o_ref, a_full, a_vmem,
                          acc_ref, send_sems, recv_sems, copy_sem, *,
                          axis: str, world: int, n_e: int, n_f: int,
                          n_k: int, bk: int, probe=_probes.NULL):
    s = pl.program_id(0)
    e = pl.program_id(1)
    j = pl.program_id(2)
    kk = pl.program_id(3)
    me = me_ref[0]
    probe.enter(((s * n_e + e) * n_f + j) * n_k + kk, me, world)
    src = jax.lax.rem(me + s, world)  # own grid first, then by distance

    @pl.when((s == 0) & (e == 0) & (j == 0) & (kk == 0))
    def _startup():
        dl.barrier_all(axis)
        probe.sem_spin(world - 1)
        for i in range(world - 1):
            peer = jax.lax.rem(me + 1 + i, world)
            common.remote_copy(x_ref, a_full.at[common.peer_slot(me, peer)],
                               send_sems.at[i], recv_sems.at[me], axis, peer,
                               probe=probe)

    @pl.when((e == 0) & (j == 0) & (kk == 0) & (s > 0))
    def _arrive():
        common.wait_recv(a_full.at[common.peer_slot(src, me)],
                         recv_sems.at[src], probe=probe)

    # (cap, bk) contraction tile: own grid reads straight from x_ref (no
    # staging round-trip; a_full holds only the world-1 remote arrivals).
    ks = pl.ds(kk * bk, bk)

    @pl.when(s == 0)
    def _load_own():
        common.local_copy(x_ref.at[e, :, ks], a_vmem, copy_sem, probe=probe)

    @pl.when(s > 0)
    def _load_remote():
        common.local_copy(a_full.at[common.peer_slot(src, me), e, :, ks],
                          a_vmem, copy_sem, probe=probe)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_vmem[...], w_ref[0],
                            preferred_element_type=jnp.float32)
    probe.compute(2 * a_vmem.shape[0] * bk * acc_ref.shape[1])

    @pl.when(kk == n_k - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)

    @pl.when((s == world - 1) & (e == n_e - 1) & (j == n_f - 1)
             & (kk == n_k - 1))
    def _drain():
        for i in range(world - 1):
            common.wait_send(x_ref, send_sems.at[i], probe=probe)


def ag_group_gemm_device(x_local, topk_ids_local, w_up_local, *,
                         n_experts: int, capacity: int, axis: str = "tp",
                         config: MoEOverlapConfig | None = None,
                         interpret=None, probes: bool = False):
    """AG of per-expert capacity grids + grouped GEMM in one kernel.

    x_local (m, d), topk_ids_local (m, k), w_up_local (E, d, f_local)
    -> (up (E, world*cap, f_local), state): every device computes all
    experts over every source's grid against its f-shard of each expert's
    weight (column-parallel MoE up-projection, reference ``ag_group_gemm``
    allgather_group_gemm.py:398), with the allgather overlapped into the
    expert GEMMs. ``state`` carries the local routing bookkeeping —
    ``slot``/``kept`` for ``combine_from_experts`` (topk weights are passed
    there directly), plus ``n_dropped``: capacity overflow is observable,
    never silent (ADVICE r1). With ``probes=True`` (a separate compile)
    returns ``(up, state, probe_buf)`` — device telemetry decoded by
    ``obs.kprobe``."""
    config = config or MoEOverlapConfig()
    world = _axis_size(axis)
    m, d = x_local.shape
    E, _, f_local = w_up_local.shape
    if E != n_experts:
        raise ValueError(f"w_up has {E} experts, expected {n_experts}")

    grid_x, slot, kept, n_dropped = moe_utils.route_to_experts(
        x_local, topk_ids_local, n_experts=n_experts, capacity=capacity)
    state = {"slot": slot, "kept": kept, "n_dropped": n_dropped}

    n_f, bf = MoEOverlapConfig.tiles(f_local, config.block_f)
    n_k, bk = MoEOverlapConfig.tiles(d, config.block_k)
    out_dtype = jnp.promote_types(x_local.dtype, w_up_local.dtype)

    if world == 1:
        up = jnp.einsum("ecd,edf->ecf", grid_x, w_up_local,
                        preferred_element_type=jnp.float32)
        up = up.astype(out_dtype)
        if probes:
            return up, state, _probes.host_stub_buffer()
        return up, state

    if _ledger.recording():
        from triton_distributed_tpu.runtime import perf_model as pm

        _ledger.record_traced(
            "moe_ag_group_gemm", axis=axis, world=world,
            nbytes=pm.wire_bytes_all_gather(grid_x.nbytes, world),
            method="overlap",
            est_s=pm.est_push_all_gather(grid_x.nbytes, world))

    me = jax.lax.axis_index(axis).astype(jnp.int32)[None]
    out_specs = [
        pl.BlockSpec(
            (1, capacity, bf),
            lambda s, e, j, kk, me_ref:
                (e, jax.lax.rem(me_ref[0] + s, world), j),
        ),
        # Remote-arrival staging: HBM OUTPUT (discarded) — Mosaic
        # has no HBM scratch; arg order unchanged.
        common.hbm_spec(),
    ]
    scratch_shapes = [
        pltpu.VMEM((capacity, bk), x_local.dtype),
        pltpu.VMEM((capacity, bf), jnp.float32),
        common.dma_sems(world - 1),
        common.dma_sems(world),
        pltpu.SemaphoreType.DMA(()),
    ]
    kernel = functools.partial(_ag_group_gemm_kernel, axis=axis, world=world,
                               n_e=E, n_f=n_f, n_k=n_k, bk=bk)
    out_shape = [
        jax.ShapeDtypeStruct((E, world * capacity, f_local), out_dtype),
        jax.ShapeDtypeStruct((world - 1, E, capacity, d), x_local.dtype),
    ]
    if probes:
        n_steps = world * E * n_f * n_k

        def body(me_ref, x_ref, w_ref, o_ref, a_full, pbuf, a_vmem, acc_ref,
                 send_sems, recv_sems, copy_sem, pord, kernel=kernel):
            kernel(me_ref, x_ref, w_ref, o_ref, a_full, a_vmem, acc_ref,
                   send_sems, recv_sems, copy_sem,
                   probe=_probes.Probe(pbuf, pord, n_steps=n_steps))

        kernel = body
        out_specs = [*out_specs, _probes.out_spec()]
        scratch_shapes = [*scratch_shapes, _probes.ord_scratch()]
        out_shape = [*out_shape, _probes.out_shape(n_steps)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(world, E, n_f, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),                # local grid
            pl.BlockSpec((1, bk, bf), lambda s, e, j, kk, me_ref: (e, kk, j)),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        compiler_params=common.compiler_params(
            common.collective_id_for("ag_group_gemm")),
        cost_estimate=common.cost_estimate(
            flops=2 * world * E * capacity * d * f_local,
            bytes_accessed=(2 * world * E * capacity * d
                            * x_local.dtype.itemsize
                            + E * d * f_local * w_up_local.dtype.itemsize
                            + world * E * capacity * f_local
                            * out_dtype.itemsize),
            remote_bytes=(world - 1) * E * capacity * d
            * x_local.dtype.itemsize),
        name="ag_group_gemm",
        interpret=resolve_interpret(interpret),
    )(me, grid_x, w_up_local)
    if probes:
        return outs[0], state, outs[2]
    return outs[0], state


# ---------------------------------------------------------------------------
# GroupGEMM-reduce-RS: per-expert down-projection with each (dst, e, d-tile)
# partial pushed to its owner as computed; owner folds + keeps its cap rows.
# ---------------------------------------------------------------------------


def _group_gemm_rs_kernel(me_ref, a_ref, w_ref, o_ref, staging, a_vmem,
                          send_tile, part_ref, acc_tile, tmp_tile, out_tile,
                          send_sems, recv_sems, copy_sem, *, axis: str,
                          world: int, n_e: int, n_d: int, n_k: int, bd: int,
                          bk: int, cap: int):
    s = pl.program_id(0)
    e = pl.program_id(1)
    j = pl.program_id(2)
    kk = pl.program_id(3)
    me = me_ref[0]
    dst = jax.lax.rem(me + 1 + s, world)  # remote destinations first
    is_own = s == world - 1
    is_last_k = kk == n_k - 1
    t = (s * n_e + e) * n_d + j           # global tile counter (remote first)
    parity = jax.lax.rem(t, 2)
    total_remote = (world - 1) * n_e * n_d

    @pl.when((s == 0) & (e == 0) & (j == 0) & (kk == 0))
    def _startup():
        dl.barrier_all(axis)

    # Load destination dst's rows of expert e, contraction tile kk.
    common.local_copy(
        a_ref.at[e, pl.ds(dst * cap, cap), pl.ds(kk * bk, bk)], a_vmem,
        copy_sem)

    @pl.when(kk == 0)
    def _zero():
        part_ref[...] = jnp.zeros_like(part_ref)

    part_ref[...] += jnp.dot(a_vmem[...], w_ref[0],
                             preferred_element_type=jnp.float32)  # (cap, bd)

    @pl.when(~is_own & is_last_k & (t >= 2))
    def _reclaim():
        common.wait_send(send_tile.at[parity], send_sems.at[parity])

    @pl.when(~is_own & is_last_k)
    def _push_tile():
        send_tile[parity] = part_ref[...].astype(send_tile.dtype)
        common.remote_copy(
            send_tile.at[parity],
            staging.at[common.peer_slot(me, dst), e, :, pl.ds(j * bd, bd)],
            send_sems.at[parity], recv_sems.at[me], axis, dst)

    @pl.when(is_own & is_last_k)
    def _own_segment():
        @pl.when((e == 0) & (j == 0))
        def _arrivals():
            for src in range(world):
                @pl.when(src != me)
                def _wait(src=src):
                    common.wait_recv(staging.at[common.peer_slot(src, me)],
                                     recv_sems.at[src])

        acc_tile[...] = jnp.zeros_like(acc_tile)
        for src in range(world):          # fixed global order (ADVICE r1)
            @pl.when(src == me)
            def _add_own():
                acc_tile[...] += part_ref[...]

            @pl.when(src != me)
            def _add_remote(src=src):
                common.local_copy(
                    staging.at[common.peer_slot(src, me), e, :,
                               pl.ds(j * bd, bd)],
                    tmp_tile, copy_sem)
                acc_tile[...] += tmp_tile[...].astype(jnp.float32)
        out_tile[...] = acc_tile[...].astype(out_tile.dtype)
        common.local_copy(out_tile, o_ref.at[e, :, pl.ds(j * bd, bd)],
                          copy_sem)

        @pl.when((e == n_e - 1) & (j == n_d - 1))
        def _drain():
            for p in range(min(2, total_remote)):
                common.wait_send(send_tile.at[p], send_sems.at[p])


def group_gemm_rs_device(act, w_down_local, *, capacity: int,
                         axis: str = "tp",
                         config: MoEOverlapConfig | None = None,
                         interpret=None):
    """Grouped down-projection fused with the reduce-scatter over f shards.

    act (E, world*cap, f_local) — ``ag_group_gemm_device`` output layout;
    w_down_local (E, f_local, d). Returns (E, cap, d): this device's own
    cap rows per expert, summed over every rank's f-shard partial
    (reference ``moe_reduce_rs_rowise``, moe_reduce_rs.py:816), comm
    overlapped into the expert GEMMs."""
    config = config or MoEOverlapConfig()
    world = _axis_size(axis)
    E, rows, f_local = act.shape
    _, _, d = w_down_local.shape
    if rows != world * capacity:
        raise ValueError(f"act rows {rows} != world*capacity {world * capacity}")
    n_d, bd = MoEOverlapConfig.tiles(d, config.block_d)
    n_k, bk = MoEOverlapConfig.tiles(f_local, config.block_k)
    out_dtype = jnp.promote_types(act.dtype, w_down_local.dtype)

    if world == 1:
        return jnp.einsum("ecf,efd->ecd", act, w_down_local,
                          preferred_element_type=jnp.float32).astype(out_dtype)

    if _ledger.recording():
        from triton_distributed_tpu.runtime import perf_model as pm

        # Each device scatters its (E, world*cap, d) partial down-product.
        per_dev = E * rows * d * out_dtype.itemsize
        _ledger.record_traced(
            "moe_group_gemm_rs", axis=axis, world=world,
            nbytes=pm.wire_bytes_reduce_scatter(per_dev, world),
            method="overlap",
            est_s=pm.est_oneshot_reduce_scatter(per_dev, world))

    me = jax.lax.axis_index(axis).astype(jnp.int32)[None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(world, E, n_d, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),               # act
            pl.BlockSpec((1, bk, bd), lambda s, e, j, kk, me_ref: (e, kk, j)),
        ],
        out_specs=[
            common.hbm_spec(),                               # (E, cap, d)
            # Incoming-partials staging: HBM OUTPUT (discarded).
            common.hbm_spec(),
        ],
        scratch_shapes=[
            pltpu.VMEM((capacity, bk), act.dtype),           # dst row tile
            pltpu.VMEM((2, capacity, bd), out_dtype),        # send buffer
            pltpu.VMEM((capacity, bd), jnp.float32),         # k-accumulator
            pltpu.VMEM((capacity, bd), jnp.float32),         # fold accumulator
            pltpu.VMEM((capacity, bd), out_dtype),           # remote tile
            pltpu.VMEM((capacity, bd), out_dtype),           # cast-out tile
            common.dma_sems(2),
            common.dma_sems(world),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out, _ = pl.pallas_call(
        functools.partial(_group_gemm_rs_kernel, axis=axis, world=world,
                          n_e=E, n_d=n_d, n_k=n_k, bd=bd, bk=bk,
                          cap=capacity),
        out_shape=[
            jax.ShapeDtypeStruct((E, capacity, d), out_dtype),
            jax.ShapeDtypeStruct((world - 1, E, capacity, d), out_dtype),
        ],
        grid_spec=grid_spec,
        compiler_params=common.compiler_params(
            common.collective_id_for("moe_reduce_rs")),
        cost_estimate=common.cost_estimate(
            flops=2 * world * E * capacity * f_local * d,
            bytes_accessed=(E * rows * f_local * act.dtype.itemsize
                            + E * f_local * d * w_down_local.dtype.itemsize
                            + 2 * world * E * capacity * d
                            * out_dtype.itemsize),
            remote_bytes=(world - 1) * E * capacity * d
            * out_dtype.itemsize),
        name="group_gemm_rs",
        interpret=resolve_interpret(interpret),
    )(me, act, w_down_local)
    return out


# ---------------------------------------------------------------------------
# Full MoE-TP MLP pipeline
# ---------------------------------------------------------------------------


def ag_moe_mlp_device(x_local, topk_ids_local, topk_weights_local, w_up_local,
                      w_down_local, *, n_experts: int, capacity: int,
                      activation=jax.nn.silu, axis: str = "tp",
                      config: MoEOverlapConfig | None = None, interpret=None):
    """Full MoE-TP MLP: route -> AG-GroupGEMM(up) -> act -> GroupGEMM-RS
    (down) -> local topk-combine (the reference's "AG MoE" pipeline).
    ``capacity`` bounds tokens per (source device, expert); m*k covers the
    worst case. Returns (out (m, d), n_dropped) — overflow zeroes the
    dropped pairs' contribution but is observable, never silent (ADVICE
    r1)."""
    up, state = ag_group_gemm_device(
        x_local, topk_ids_local, w_up_local, n_experts=n_experts,
        capacity=capacity, axis=axis, config=config, interpret=interpret)
    act = activation(up.astype(jnp.float32)).astype(up.dtype)
    down = group_gemm_rs_device(
        act, w_down_local, capacity=capacity, axis=axis, config=config,
        interpret=interpret)                                # (E, cap, d)
    out = moe_utils.combine_from_experts(
        down, topk_ids_local, topk_weights_local, state["slot"],
        state["kept"])
    return out, state["n_dropped"]


# ---------------------------------------------------------------------------
# Inter-slice (DCN) legs — slice-level ppermute rings around the intra-slice
# overlap kernels, the MoE analog of ag_gemm_2d_device / gemm_rs_2d_device
# (the reference's inter-node MoE paths: moe_reduce_rs.py:605 inter-node p2p).
# ---------------------------------------------------------------------------


def ag_group_gemm_2d_device(x_local, topk_ids_local, w_up_local, *,
                            n_experts: int, capacity: int,
                            ici_axis: str = "ici", dcn_axis: str = "dcn",
                            config: MoEOverlapConfig | None = None,
                            interpret=None):
    """AG-GroupGEMM over a (dcn, ici) mesh: tokens sharded over ALL devices
    (dcn-major), expert weights f-sharded over the full world. Intra-slice
    grids gather inside the Pallas overlap kernel; inter-slice token blocks
    ride a slice-level ppermute ring, re-routed locally per slice (routing
    is cheap jnp; the grid ships as raw tokens so the DCN payload is the
    same bytes the reference moves). Returns
    (up (E, n_slices*w_ici*cap, f_local), state-of-own-slice)."""
    from triton_distributed_tpu.kernels.collective_2d import dcn_ring_walk

    n_slices = _axis_size(dcn_axis)
    if n_slices == 1:
        return ag_group_gemm_device(
            x_local, topk_ids_local, w_up_local, n_experts=n_experts,
            capacity=capacity, axis=ici_axis, config=config,
            interpret=interpret)
    w_ici = _axis_size(ici_axis)
    E, _, f_local = w_up_local.shape
    out_dtype = jnp.promote_types(x_local.dtype, w_up_local.dtype)
    own_state = {}

    def block(step, cur, xb, idsb):
        blk, st = ag_group_gemm_device(
            xb, idsb, w_up_local, n_experts=n_experts, capacity=capacity,
            axis=ici_axis, config=config, interpret=interpret)
        if step == 0:
            # Own tokens' routing bookkeeping (the combine needs it).
            own_state["state"] = st
        return blk

    def place(acc, cur, blk):
        return jax.lax.dynamic_update_slice(
            acc, blk.astype(out_dtype), (0, cur * (w_ici * capacity), 0))

    up = dcn_ring_walk(
        block, place,
        jnp.zeros((E, n_slices * w_ici * capacity, f_local), out_dtype),
        (x_local, topk_ids_local), dcn_axis=dcn_axis)
    return up, own_state["state"]


def group_gemm_rs_2d_device(act, w_down_local, *, capacity: int,
                            ici_axis: str = "ici", dcn_axis: str = "dcn",
                            config: MoEOverlapConfig | None = None,
                            interpret=None):
    """GroupGEMM-reduce-RS over a (dcn, ici) mesh: ring reduce-scatter over
    the DCN axis at slice-block granularity (add-and-forward), intra-slice
    partials pushed-as-computed inside the Pallas kernel. ``act`` is
    (E, n_slices*w_ici*cap, f_local) in the 2D AG-GroupGEMM layout. Returns
    (E, cap, d): this device's own cap rows per expert, reduced over the
    FULL world's f shards."""
    from triton_distributed_tpu.kernels.collective_2d import (
        dcn_ring_reduce_scatter,
    )

    n_slices = _axis_size(dcn_axis)
    if n_slices == 1:
        return group_gemm_rs_device(act, w_down_local, capacity=capacity,
                                    axis=ici_axis, config=config,
                                    interpret=interpret)
    w_ici = _axis_size(ici_axis)
    E, rows, f_local = act.shape
    d = w_down_local.shape[2]
    if rows != n_slices * w_ici * capacity:
        raise ValueError(
            f"act rows {rows} != world*capacity {n_slices * w_ici * capacity}")
    out_dtype = jnp.promote_types(act.dtype, w_down_local.dtype)

    def part(blk):                                       # (E, cap, d) fp32
        act_blk = jax.lax.dynamic_slice(
            act, (0, blk * (w_ici * capacity), 0),
            (E, w_ici * capacity, f_local))
        return group_gemm_rs_device(
            act_blk, w_down_local, capacity=capacity, axis=ici_axis,
            config=config, interpret=interpret).astype(jnp.float32)

    acc = dcn_ring_reduce_scatter(
        part, jnp.zeros((E, capacity, d), jnp.float32), dcn_axis=dcn_axis)
    return acc.astype(out_dtype)


def ag_moe_mlp_2d_device(x_local, topk_ids_local, topk_weights_local,
                         w_up_local, w_down_local, *, n_experts: int,
                         capacity: int, activation=jax.nn.silu,
                         ici_axis: str = "ici", dcn_axis: str = "dcn",
                         config: MoEOverlapConfig | None = None,
                         interpret=None):
    """Full MoE-TP MLP over a (dcn, ici) mesh: 2D AG-GroupGEMM(up) -> act ->
    2D GroupGEMM-RS(down) -> local topk-combine. The inter-slice legs ride
    XLA DCN collectives under the intra-slice Pallas kernels (SURVEY §7
    hard-part 6)."""
    up, state = ag_group_gemm_2d_device(
        x_local, topk_ids_local, w_up_local, n_experts=n_experts,
        capacity=capacity, ici_axis=ici_axis, dcn_axis=dcn_axis,
        config=config, interpret=interpret)
    act = activation(up.astype(jnp.float32)).astype(up.dtype)
    down = group_gemm_rs_2d_device(
        act, w_down_local, capacity=capacity, ici_axis=ici_axis,
        dcn_axis=dcn_axis, config=config, interpret=interpret)
    out = moe_utils.combine_from_experts(
        down, topk_ids_local, topk_weights_local, state["slot"],
        state["kept"])
    return out, state["n_dropped"]


# ---------------------------------------------------------------------------
# Comm-safety analyzer registration (tools/comm_check.py; docs/analysis.md)
# ---------------------------------------------------------------------------

import numpy as _np  # noqa: E402

from triton_distributed_tpu.analysis import registry as _comm  # noqa: E402


@_comm.register("moe.ag_group_gemm")
def _comm_spec_ag_group_gemm(world: int) -> "_comm.TraceSpec":
    n_e, cap, d, f = 2, 8, 128, 128      # n_k = n_f = 1
    return _comm.TraceSpec(
        body=_ag_group_gemm_kernel,
        args=[
            _comm.Buf("me", (1,), _np.int32, space="smem",
                      init=lambda r, w: _np.array([r], _np.int32)),
            _comm.Buf("x", (n_e, cap, d)),
            _comm.Buf("w", (1, d, f)),
            _comm.Buf("o", (1, cap, f), covered=True),
            _comm.Buf("a_full", (world - 1, n_e, cap, d)),
            _comm.Buf("a_vmem", (cap, d), space="vmem"),
            _comm.Buf("acc", (cap, f), space="vmem"),
            _comm.Sem("send_sems", (world - 1,)),
            _comm.Sem("recv_sems", (world,)),
            _comm.Sem("copy_sem"),
        ],
        grid=(world, n_e, 1, 1),
        kwargs=dict(axis="tp", world=world, n_e=n_e, n_f=1, n_k=1, bk=d),
    )


@_comm.register("moe.group_gemm_rs")
def _comm_spec_group_gemm_rs(world: int) -> "_comm.TraceSpec":
    n_e, cap, f, bd = 2, 8, 128, 128     # n_k = n_d = 1; d = bd
    return _comm.TraceSpec(
        body=_group_gemm_rs_kernel,
        args=[
            _comm.Buf("me", (1,), _np.int32, space="smem",
                      init=lambda r, w: _np.array([r], _np.int32)),
            _comm.Buf("a", (n_e, world * cap, f)),
            _comm.Buf("w", (1, f, bd)),
            _comm.Buf("o", (n_e, cap, bd), covered=True),
            _comm.Buf("staging", (world - 1, n_e, cap, bd)),
            _comm.Buf("a_vmem", (cap, f), space="vmem"),
            _comm.Buf("send_tile", (2, cap, bd), space="vmem"),
            _comm.Buf("part", (cap, bd), space="vmem"),
            _comm.Buf("acc_tile", (cap, bd), space="vmem"),
            _comm.Buf("tmp_tile", (cap, bd), space="vmem"),
            _comm.Buf("out_tile", (cap, bd), space="vmem"),
            _comm.Sem("send_sems", (2,)),
            _comm.Sem("recv_sems", (world,)),
            _comm.Sem("copy_sem"),
        ],
        grid=(world, n_e, 1, 1),
        kwargs=dict(axis="tp", world=world, n_e=n_e, n_d=1, n_k=1,
                    bd=bd, bk=f, cap=cap),
    )
