"""Sequence-parallel attention: AG-overlap prefill + distributed flash decode.

TPU-native analogs of the reference's long-context pair (SURVEY.md §2.5 SP row):
- ``sp_ag_attention_intra_node.py`` (521 LoC: KV allgather producer :105,
  fused attn consumer :256, ``fused_sp_ag_attn_intra_node`` :432): Q sharded
  by sequence, K/V shards allgathered into symmetric buffers while the
  flash-attention consumer waits per-(batch, rank) barriers and processes KV
  segments as they arrive.
- ``flash_decode.py`` (1161 LoC: split-KV decode :130, inter-rank combine
  :482, ``gqa_fwd_batch_decode`` hosts :763+): decode with sequence-sharded
  KV cache — local partial (out, LSE) then ``fast_allgather`` of partials and
  a log-sum-exp merge.

TPU design:
- Prefill = ONE Pallas kernel per device: at grid start every device pushes
  its KV shard to all peers (async ICI DMAs); the grid walks (head, segment)
  with segments innermost in arrival-swizzled order (own shard first), doing
  streaming-softmax accumulation per arriving segment — the overlap is
  DMA-vs-MXU inside the kernel, exactly the AG-GEMM structure applied to
  attention. Causal masking skips segments right of the diagonal (their
  semaphores are still drained).
- Decode partials are exchanged with the ring allgather kernel; the local
  split-KV attention and the LSE merge are jnp (XLA fuses them well at decode
  shapes); LSE rides as an extra feature column of the gathered partials —
  the role of the reference's LL-packed (out, lse) buffers.
"""

from __future__ import annotations

import functools

import jax
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.language import primitives as dl
from triton_distributed_tpu.kernels import common
from triton_distributed_tpu.kernels.allgather import ring_all_gather
from triton_distributed_tpu.obs import comm_ledger as _ledger
from triton_distributed_tpu.runtime.platform import resolve_interpret

_NEG_INF = -1e30


def _sp_attn_kernel(*refs, axis: str, world: int, causal: bool, scale: float,
                    partials: bool):
    # scalars_ref = [me, row0, col0]: row0/col0 are this device's GLOBAL q /
    # current KV-block column offsets — the 1-D path passes (me*m, 0); the
    # inter-slice ring passes slice-level offsets so causal masking works on
    # global positions (reference sp_ag_attention_inter_node.py:115).
    if partials:
        (scalars_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, k_full, v_full,
         q_vmem, k_vmem, v_vmem, acc_ref, m_ref, l_ref,
         send_sems, recv_sems, copy_sem) = refs
    else:
        (scalars_ref, q_ref, k_ref, v_ref, o_ref, k_full, v_full,
         q_vmem, k_vmem, v_vmem, acc_ref, m_ref, l_ref,
         send_sems, recv_sems, copy_sem) = refs
    h = pl.program_id(0)
    s = pl.program_id(1)
    me = scalars_ref[0]
    row0 = scalars_ref[1]
    col0 = scalars_ref[2]
    src = jax.lax.rem(me + s, world)  # own shard first, then by distance

    @pl.when((h == 0) & (s == 0))
    def _startup():
        dl.barrier_all(axis)
        common.local_copy(k_ref, k_full.at[me], copy_sem)
        common.local_copy(v_ref, v_full.at[me], copy_sem)
        for i in range(world - 1):
            peer = jax.lax.rem(me + 1 + i, world)
            common.remote_copy(k_ref, k_full.at[me], send_sems.at[2 * i],
                               recv_sems.at[2 * me], axis, peer)
            common.remote_copy(v_ref, v_full.at[me], send_sems.at[2 * i + 1],
                               recv_sems.at[2 * me + 1], axis, peer)

    # First touch of a remote segment (h == 0 pass walks all segments).
    @pl.when((h == 0) & (s > 0))
    def _arrive():
        common.wait_recv(k_full.at[src], recv_sems.at[2 * src])
        common.wait_recv(v_full.at[src], recv_sems.at[2 * src + 1])

    @pl.when(s == 0)
    def _init_head():
        common.local_copy(q_ref.at[h], q_vmem, copy_sem)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: a segment whose first GLOBAL column is right of this device's
    # last global row contributes nothing (fully-masked rows inside needed
    # segments are handled by the `* valid` guard below).
    m_q = q_vmem.shape[0]
    m_kv = k_vmem.shape[0]
    needed = (col0 + src * m_kv <= row0 + m_q - 1) if causal else (src == src)

    @pl.when(needed)
    def _segment():
        common.local_copy(k_full.at[src, h], k_vmem, copy_sem)
        common.local_copy(v_full.at[src, h], v_vmem, copy_sem)
        q = q_vmem[...].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k_vmem[...].astype(jnp.float32),
            (((1,), (1,)), ((), ()))) * scale          # (m, m_kv)
        valid = None
        if causal:
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            cols = (col0 + src * m_kv
                    + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1))
            valid = rows >= cols
            scores = jnp.where(valid, scores, _NEG_INF)
        seg_max = jnp.max(scores, axis=1, keepdims=True)
        new_max = jnp.maximum(m_ref[...], seg_max)
        corr = jnp.exp(m_ref[...] - new_max)
        p = jnp.exp(scores - new_max)
        if valid is not None:
            # A FULLY-masked q row has scores == new_max == _NEG_INF and
            # exp(0) == 1 would poison the denominator (the decode kernel's
            # `* valid` guard) — keeps arbitrary, non-shard-aligned
            # row/col offsets safe, not just the aligned 1-D/2-D callers.
            p = p * valid.astype(jnp.float32)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_vmem[...].astype(jnp.float32), (((1,), (0,)), ((), ())))
        m_ref[...] = new_max

    @pl.when(s == world - 1)
    def _finish_head():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        if partials:
            # log-sum-exp, lane-broadcast (column 0 meaningful): a slice
            # with nothing to attend reports ~-1e30 -> zero merge weight.
            lse_ref[0] = jnp.broadcast_to(m_ref[...] + jnp.log(denom),
                                          lse_ref.shape[1:])

    @pl.when((h == pl.num_programs(0) - 1) & (s == world - 1))
    def _drain():
        for i in range(world - 1):
            common.wait_send(k_ref, send_sems.at[2 * i])
            common.wait_send(v_ref, send_sems.at[2 * i + 1])


def sp_ag_attention_device(q_local, k_local, v_local, *, axis: str = "sp",
                           causal: bool = True, scale: float | None = None,
                           row_offset=None, col_offset=None,
                           return_partials: bool = False, interpret=None):
    """Per-device SP prefill attention (composable inside shard_map).

    q/k/v_local: (H, m, dh) — the sequence dim sharded over ``axis``.
    Returns (H, m, dh): this device's Q rows attended over the FULL sequence,
    with the KV allgather overlapped into the attention.

    ``row_offset``/``col_offset``: GLOBAL position of this device's first q
    row / of the KV block's first column (default: the 1-D values
    ``me * m`` / 0). ``return_partials=True`` additionally returns the
    per-row log-sum-exp (H, m) — the mergeable-partial form consumed by the
    inter-slice ring (``sp_ag_attention_2d_device``)."""
    world = _axis_size(axis)
    H, m, dh = q_local.shape
    scale = dh ** -0.5 if scale is None else scale
    if world == 1 and not return_partials and row_offset is None \
            and col_offset is None:
        return _single_device_attn(q_local, k_local, v_local, causal=causal,
                                   scale=scale)
    m_kv = k_local.shape[1]

    if world > 1 and _ledger.recording():
        from triton_distributed_tpu.runtime import perf_model as pm

        shard = k_local.nbytes + v_local.nbytes  # the KV gather is the comm
        _ledger.record_traced(
            "sp_ag_attention", axis=axis, world=world,
            nbytes=pm.wire_bytes_all_gather(shard, world), method="overlap",
            est_s=pm.est_push_all_gather(shard, world))

    me = jax.lax.axis_index(axis).astype(jnp.int32)
    row0 = (me * m if row_offset is None
            else jnp.asarray(row_offset, jnp.int32))
    col0 = (jnp.zeros((), jnp.int32) if col_offset is None
            else jnp.asarray(col_offset, jnp.int32))
    scalars = jnp.stack([me, row0, col0])
    # Gathered-KV staging buffers are ANY-space OUTPUTS (discarded): Mosaic
    # has no HBM scratch; kernel arg order unchanged (leading-scratch ->
    # trailing-output positions).
    out_specs = [pl.BlockSpec((1, m, dh), lambda h, s, sc: (h, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((H, m, dh), q_local.dtype)]
    if return_partials:
        out_specs.append(
            pl.BlockSpec((1, m, 128), lambda h, s, sc: (h, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((H, m, 128), jnp.float32))
    out_specs += [common.hbm_spec(), common.hbm_spec()]
    out_shape += [
        jax.ShapeDtypeStruct((world, H, m_kv, dh), k_local.dtype),
        jax.ShapeDtypeStruct((world, H, m_kv, dh), v_local.dtype),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, world),
        in_specs=[common.any_spec()] * 3,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((m, dh), q_local.dtype),
            pltpu.VMEM((m_kv, dh), k_local.dtype),
            pltpu.VMEM((m_kv, dh), v_local.dtype),
            pltpu.VMEM((m, dh), jnp.float32),    # acc
            pltpu.VMEM((m, 1), jnp.float32),     # running max
            pltpu.VMEM((m, 1), jnp.float32),     # denominator
            common.dma_sems(2 * (world - 1)),
            common.dma_sems(2 * world),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    result = pl.pallas_call(
        functools.partial(_sp_attn_kernel, axis=axis, world=world,
                          causal=causal, scale=scale,
                          partials=return_partials),
        out_shape=out_shape,
        grid_spec=grid_spec,
        compiler_params=common.compiler_params(
            common.collective_id_for("sp_ag_attn")),
        cost_estimate=common.cost_estimate(
            flops=4 * H * m * world * m_kv * dh,
            bytes_accessed=(H * m * dh * q_local.dtype.itemsize
                            + 4 * world * H * m_kv * dh
                            * k_local.dtype.itemsize
                            + H * m * dh * q_local.dtype.itemsize),
            remote_bytes=2 * (world - 1) * H * m_kv * dh
            * k_local.dtype.itemsize),
        name="sp_ag_attention",
        interpret=resolve_interpret(interpret),
    )(scalars, q_local, k_local, v_local)
    if return_partials:
        return result[0], result[1][..., 0]
    return result[0]


def sp_ag_attention_2d_device(q_local, k_local, v_local, *,
                              ici_axis: str = "sp", dcn_axis: str = "dcn",
                              causal: bool = True, scale: float | None = None,
                              interpret=None):
    """Inter-slice SP prefill attention over a (dcn, ici) mesh — the analog
    of the reference's ``sp_ag_attention_inter_node.py`` (2D AG push :115,
    ``fused_sp_ag_attn_inter_node`` :504).

    The sequence is sharded over ALL devices (dcn-major). Intra-slice KV
    streams through the overlap kernel exactly as the 1-D path; INTER-slice
    KV arrives via the XLA DCN leg as a slice-level ring
    (``lax.ppermute`` over ``dcn_axis``) and each arriving slice block is
    processed immediately — its (out, lse) partial merged by log-sum-exp.
    XLA schedules the next ppermute concurrently with the current slice's
    attention kernel (async collective + custom call), so the DCN hop rides
    under intra-slice compute."""
    from triton_distributed_tpu.kernels.collective_2d import dcn_ring_walk

    w_ici = _axis_size(ici_axis)
    H, m, dh = q_local.shape
    m_kv = k_local.shape[1]
    scale = dh ** -0.5 if scale is None else scale
    sid = jax.lax.axis_index(dcn_axis)
    me = jax.lax.axis_index(ici_axis)
    row0 = (sid * w_ici + me) * m

    def block(step, cur, kb, vb):
        col0 = cur * w_ici * m_kv
        return sp_ag_attention_device(
            q_local, kb, vb, axis=ici_axis, causal=causal, scale=scale,
            row_offset=row0, col_offset=col0, return_partials=True,
            interpret=interpret)

    def merge(carry, cur, blk):
        acc, mx, den = carry
        out_p, lse_p = blk
        lse = lse_p[..., None]
        new_mx = jnp.maximum(mx, lse)
        c_old = jnp.exp(mx - new_mx)
        c_new = jnp.exp(lse - new_mx)
        return (acc * c_old + out_p.astype(jnp.float32) * c_new,
                new_mx, den * c_old + c_new)

    acc, _, den = dcn_ring_walk(
        block, merge,
        (jnp.zeros((H, m, dh), jnp.float32),
         jnp.full((H, m, 1), _NEG_INF, jnp.float32),
         jnp.zeros((H, m, 1), jnp.float32)),
        (k_local, v_local), dcn_axis=dcn_axis)
    return (acc / jnp.maximum(den, 1e-30)).astype(q_local.dtype)


def _single_device_attn(q, k, v, *, causal: bool, scale: float):
    scores = jnp.einsum("hmd,hnd->hmn", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        m, n = scores.shape[-2:]
        mask = jnp.arange(m)[:, None] >= jnp.arange(n)[None, :]
        scores = jnp.where(mask, scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hmn,hnd->hmd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Single-device flash prefill
# ---------------------------------------------------------------------------


def _flash_prefill_kernel(scalars_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                          m_ref, l_ref, *, n_chunks: int, ck: int, lb: int,
                          g: int, scale: float):
    """Causal GQA flash prefill for one (batch, kv-head, q-tile): the grid's
    innermost dim walks KV chunks with streaming-softmax accumulation. Q rows
    are (Lb query positions x g GQA heads) flattened li-major, so one MXU
    score block serves the whole GQA group (reference relies on the
    flash_attn library for this; here it is the flash-decode kernel
    generalized to q tiles, sharing its masking discipline).

    Per-ROW scalars (row = batch index, scalar-prefetched): offset, cache
    mask length, and valid query count — the varlen (cu_seqlens) machinery
    of the reference's SP attention (sp_ag_attention_intra_node.py:112-145)
    expressed TPU-style: padded batch + per-row lengths, with whole KV
    chunks AND whole q tiles skipped once they pass a row's length (zero
    extra FLOPs for short rows; padding rows emit zeros)."""
    b = pl.program_id(0)
    qb = pl.program_id(2)
    c = pl.program_id(3)
    offset = scalars_ref[0, b]
    kv_len = scalars_ref[1, b]
    q_len = scalars_ref[2, b]

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Skip chunks fully right of this q tile's last position (causal),
    # fully beyond the valid cache (kv_len), or belonging to a q tile
    # that is entirely padding (varlen short row).
    last_q_pos = offset + qb * lb + lb - 1
    needed = ((c * ck <= last_q_pos) & (c * ck < kv_len)
              & (qb * lb < q_len))

    @pl.when(needed)
    def _chunk():
        q = q_ref[0, 0].astype(jnp.float32)              # (lb*g, dh)
        k = k_ref[0, 0].astype(jnp.float32)              # (ck, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale      # (lb*g, ck)
        rows = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        q_pos = offset + qb * lb + rows // g
        key_pos = c * ck + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = ((key_pos <= q_pos) & (key_pos < kv_len)
                 & (qb * lb + rows // g < q_len))
        scores = jnp.where(valid, scores, _NEG_INF)
        seg_max = jnp.max(scores, axis=1, keepdims=True)
        new_max = jnp.maximum(m_ref[...], seg_max)
        corr = jnp.exp(m_ref[...] - new_max)
        # `* valid` guard: fully-masked rows otherwise poison the
        # denominator with exp(0) (same as the decode kernel).
        p = jnp.exp(scores - new_max) * valid.astype(jnp.float32)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = new_max

    @pl.when(c == n_chunks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def cu_seqlens_to_lens(cu_seqlens):
    """Reference cu_seqlens (B+1 cumulative offsets,
    sp_ag_attention_intra_node.py:112) -> per-row lengths (B,) for
    ``flash_prefill(seq_lens=...)`` — the padded-batch form is the
    TPU-native varlen representation (static shapes; XLA cannot trace
    token-packed dynamic rows)."""
    cu = jnp.asarray(cu_seqlens, jnp.int32)
    return cu[1:] - cu[:-1]


def prefill_alignment_issue(L: int, Hq: int, dh: int, Hkv: int,
                            S: int) -> str | None:
    """Why ``flash_prefill`` would return None for these shapes, as a
    human-readable string naming the offending dim — or None when the shapes
    tile fine. This IS ``flash_prefill``'s shape gate (single source of
    truth), phrased for the dense-fallback warning in layers/nn.py."""
    if Hq % Hkv:
        return f"Hq={Hq} not a multiple of Hkv={Hkv}"
    if dh % 128:
        return f"head_dim={dh} not a multiple of 128 (lane width)"
    if S % 8:
        return f"cache len S={S} not a multiple of 8 (sublane width)"
    if _q_tile(L, Hq // Hkv) == 0:
        return (f"q len L={L} admits no sublane-aligned tile "
                f"(need a divisor lb with lb*{Hq // Hkv} % 8 == 0)")
    return None


def _q_tile(L: int, g: int, preferred_rows: int = 1024) -> int:
    """Largest divisor Lb of L with Lb*g sublane-aligned and under the row
    preference; 0 when none exists (caller falls back to dense)."""
    best = 0
    for lb in range(1, L + 1):
        if L % lb == 0 and (lb * g) % 8 == 0 and lb * g <= preferred_rows:
            best = lb
    return best


def flash_prefill(q, k_cache, v_cache, *, offset=None, kv_len=None,
                  seq_lens=None, scale: float | None = None,
                  chunk: int = 512, kv_layout: str = "bshd", interpret=None):
    """Causal GQA prefill attention against a (possibly longer) KV cache via
    the streaming-softmax Pallas kernel — O(L_q * dh) memory per tile
    instead of the (B, L, Hq, S) fp32 score tensor of the dense path.

    q: (B, L, Hq, dh) new queries at positions [offset, offset + L);
    k/v_cache: (B, S, Hkv, dh) (``bshd``, the TP cache layout — transposed
    once internally; pass ``bhsd`` to skip it) already containing the new
    keys. ``kv_len`` masks cache positions >= it (default offset + L).
    Returns (B, L, Hq, dh) in q.dtype.

    ``seq_lens`` (B,) int32 enables VARLEN mode — the reference SP
    attention's cu_seqlens regime (sp_ag_attention_intra_node.py:112-145)
    in padded-batch form: row b's valid queries are its first
    ``seq_lens[b]`` rows (the rest is padding and returns zeros), its
    cache mask is ``offset + seq_lens[b]``, and KV chunks / q tiles past a
    row's length are skipped in-kernel (no FLOPs for short rows). Use
    ``cu_seqlens_to_lens`` to convert a reference-style cu_seqlens vector.

    Returns None when the shapes don't admit an aligned tiling (ragged L/dh)
    — callers fall back to the dense jnp path.
    """
    B, L, Hq, dh = q.shape
    if kv_layout == "bshd":
        k_cache = jnp.swapaxes(k_cache, 1, 2)
        v_cache = jnp.swapaxes(v_cache, 1, 2)
    elif kv_layout != "bhsd":
        raise ValueError(f"unknown kv_layout {kv_layout!r}")
    _, Hkv, S, _ = k_cache.shape
    if prefill_alignment_issue(L, Hq, dh, Hkv, S) is not None:
        return None
    g = Hq // Hkv
    lb = _q_tile(L, g)
    scale = dh ** -0.5 if scale is None else scale
    ck = _kv_chunk(S, chunk)
    n_chunks = S // ck
    offset = jnp.asarray(0 if offset is None else offset, jnp.int32)
    offsets = jnp.broadcast_to(offset, (B,))
    if seq_lens is not None:
        seq_lens = jnp.asarray(seq_lens, jnp.int32)
        if seq_lens.shape != (B,):
            raise ValueError(f"seq_lens {seq_lens.shape} != ({B},)")
        if kv_len is not None:
            raise ValueError("pass kv_len OR seq_lens, not both")
        kv_lens = offsets + seq_lens
        q_lens = seq_lens
    else:
        kv_len = jnp.asarray(offset + L if kv_len is None else kv_len,
                             jnp.int32)
        kv_lens = jnp.broadcast_to(kv_len, (B,))
        q_lens = jnp.full((B,), L, jnp.int32)
    scalars = jnp.stack([offsets, kv_lens, q_lens])

    # Rows li-major: row = li*g + gi -> contiguous q-position tiles.
    q_r = q.reshape(B, L, Hkv, g, dh).transpose(0, 2, 1, 3, 4
                                                ).reshape(B, Hkv, L * g, dh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, L // lb, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, lb * g, dh),
                         lambda b, h, qb, c, sc: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, ck, dh), lambda b, h, qb, c, sc: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ck, dh), lambda b, h, qb, c, sc: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, lb * g, dh),
                               lambda b, h, qb, c, sc: (b, h, qb, 0)),
        scratch_shapes=[
            pltpu.VMEM((lb * g, dh), jnp.float32),
            pltpu.VMEM((lb * g, 1), jnp.float32),
            pltpu.VMEM((lb * g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_prefill_kernel, n_chunks=n_chunks, ck=ck,
                          lb=lb, g=g, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, L * g, dh), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=common.cost_estimate(
            flops=4 * B * Hq * L * S * dh,
            bytes_accessed=(2 * B * Hq * L * dh * q.dtype.itemsize
                            + 2 * B * Hkv * S * dh
                            * k_cache.dtype.itemsize)),
        name="flash_prefill",
        interpret=resolve_interpret(interpret),
    )(scalars, q_r, k_cache, v_cache)
    return out.reshape(B, Hkv, L, g, dh).transpose(0, 2, 1, 3, 4
                                                   ).reshape(B, L, Hq, dh)


# ---------------------------------------------------------------------------
# Distributed flash decode
# ---------------------------------------------------------------------------


def _flash_decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                         acc_ref, m_ref, l_ref, *, n_chunks: int, ck: int,
                         scale: float, n_kv: int, bshd: bool):
    """Split-KV streaming-softmax decode step for one batch row: the grid
    walks KV chunks; per chunk, for each local kv head (static unroll — the
    per-block head dim must span the full array for Mosaic's last-two-dims
    block rule), the MXU computes the (g, ck) score block (g = GQA group of
    q heads sharing that kv head), rescales the running (acc, max, denom)
    triple, and the final chunk emits (out, LSE). The structure of the
    reference's split-KV kernel (flash_decode.py:130) with the chunk loop as
    the Pallas grid instead of persistent CTAs."""
    c = pl.program_id(1)
    kv_len = kvlen_ref[pl.program_id(0)]   # per-row: serving's slot offsets

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    for h in range(n_kv):
        # The f32 casts are deliberate: an all-bf16 variant (wire-dtype
        # operands straight to the MXU, p cast to v.dtype like the
        # reference's Triton kernel) measured 3.2x SLOWER at the bench
        # shape — the g-row (sub-16-sublane) bf16 operands hit Mosaic's
        # packed-tile relayout path on every op. f32 (8, 128) tiles don't.
        q = q_ref[0, h].astype(jnp.float32)                # (g, dh)
        if bshd:
            k = k_ref[0, :, h, :].astype(jnp.float32)      # (ck, dh)
            v = v_ref[0, :, h, :].astype(jnp.float32)
        else:
            k = k_ref[0, h].astype(jnp.float32)
            v = v_ref[0, h].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale        # (g, ck)
        pos = c * ck + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = pos < kv_len
        scores = jnp.where(valid, scores, _NEG_INF)
        seg_max = jnp.max(scores, axis=-1, keepdims=True)
        new_max = jnp.maximum(m_ref[h], seg_max)
        corr = jnp.exp(m_ref[h] - new_max)
        # ``* valid``: a fully-masked chunk has scores == new_max == _NEG_INF
        # and exp(0) == 1 would poison the denominator.
        p = jnp.exp(scores - new_max) * valid.astype(jnp.float32)
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))                # (g, dh)
        m_ref[h] = new_max

    @pl.when(c == n_chunks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)             # (n_kv, g, 1)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(denom))[..., 0]


def _flash_decode_bd_kernel(kvlen_ref, qbd_ref, k_ref, v_ref, o_ref, lse_ref,
                            acc_ref, m_ref, l_ref, *, n_chunks: int, ck: int,
                            scale: float, n_kv: int, g: int, dh: int):
    """Block-diagonal batched-head split-KV decode (bshd layout, round 5).

    The per-head kernel ran the WHOLE KV stream through f32 VPU converts
    (the bf16 operands' g-row sub-tiles hit Mosaic's relayout path, and the
    f32 variant converts 2M elements per step) — measured compute-DMA
    SERIALIZED at ~58% of HBM peak. Here all local heads fold into ONE pair
    of MXU dots per chunk: q arrives pre-arranged block-diagonal
    (rows = (head, q-in-group), cols = (head, feature) — zeros off-block),
    so ``q_bd @ K_flat^T`` computes every head's scores in one
    (Hkv*g, Hkv*dh) x (Hkv*dh, ck) bf16 dot with f32 accumulate: KV feeds
    the MXU in its wire dtype, operand rows are >= 16 (no relayouts), and
    the off-block FLOPs are free on an HBM-bound op. The PV dot computes
    (Hkv*g, ck) x (ck, Hkv*dh) and the per-row head block is selected with
    a mask-sum. Reference structure: flash_decode.py:130 split-KV with the
    chunk loop as the Pallas grid."""
    c = pl.program_id(1)
    kv_len = kvlen_ref[pl.program_id(0)]   # per-row: serving's slot offsets
    rows = n_kv * g

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_bd = qbd_ref[0]                                      # (rows, n_kv*dh)
    k_flat = k_ref[0].reshape(ck, n_kv * dh)               # wire dtype
    v_flat = v_ref[0].reshape(ck, n_kv * dh)
    scores = jax.lax.dot_general(
        q_bd, k_flat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (rows, ck) f32
    pos = c * ck + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    valid = pos < kv_len
    scores = jnp.where(valid, scores, _NEG_INF)
    seg_max = jnp.max(scores, axis=-1, keepdims=True)      # (rows, 1)
    new_max = jnp.maximum(m_ref[...], seg_max)
    corr = jnp.exp(m_ref[...] - new_max)
    p = jnp.exp(scores - new_max) * valid.astype(jnp.float32)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v_flat.dtype), v_flat, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (rows, n_kv*dh)
    # Keep each row's own head block: row r belongs to head r // g.
    row_head = jax.lax.broadcasted_iota(jnp.int32, (rows, n_kv, 1), 0) // g
    col_head = jax.lax.broadcasted_iota(jnp.int32, (rows, n_kv, 1), 1)
    own = (row_head == col_head).astype(jnp.float32)
    pv_own = jnp.sum(pv.reshape(rows, n_kv, dh) * own, axis=1)  # (rows, dh)
    acc_ref[...] = acc_ref[...] * corr + pv_own
    m_ref[...] = new_max

    @pl.when(c == n_chunks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)             # (rows, 1)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(denom)           # (rows, 1)


def _block_diag_q(q4):
    """(B, Hkv, g, dh) -> (B, Hkv*g, Hkv*dh) with q4[b, h, i] at rows
    h*g+i, cols h*dh..(h+1)*dh and zeros off-block — the one-dot-all-heads
    operand of the block-diagonal decode kernel."""
    B, Hkv, g, dh = q4.shape
    eye = jnp.eye(Hkv, dtype=q4.dtype)
    return jnp.einsum("bhgd,hH->bhgHd", q4, eye).reshape(
        B, Hkv * g, Hkv * dh)


def _kv_chunk(m_kv: int, preferred: int = 512) -> int:
    """Largest 8-aligned (sublane) divisor of the KV shard length <= the
    preference; the full length when none exists (always legal)."""
    for cand in range(min(preferred, m_kv), 7, -1):
        if m_kv % cand == 0 and cand % 8 == 0:
            return cand
    return m_kv


# KV staging budget for the decode kernel's double-buffered all-heads K+V
# blocks — larger than the generic collective staging budget on purpose:
# at B=128/Hkv=8/dh=128/16k the 1024-row chunk (8 MB staged) measured
# ~17% faster than the 512-row one (fewer grid steps to amortize
# per-step overhead against), and the kernel's other VMEM use is tiny.
_DECODE_KV_BUDGET = 8 * 2 ** 20


def flash_decode_local(q, k_cache, v_cache, *, kv_len=None,
                       scale: float | None = None, chunk: int = 1024,
                       kv_layout: str = "bhsd", interpret=None):
    """Single-device split-KV GQA decode partial via the Pallas kernel.

    q: (B, Hq, dh); k/v_cache: (B, Hkv, m_kv, dh) — or (B, m_kv, Hkv, dh)
    with ``kv_layout="bshd"`` (the TP cache layout; the BlockSpec index map
    absorbs the layout, no transpose materializes). Hq % Hkv == 0 (GQA stays
    native — no KV head expansion materializes). ``kv_len`` (int32 scalar
    or (B,) vector — the serving path's per-slot offsets) masks cache
    positions >= it per row (preallocated-cache decode); None = full.
    Returns (out (B, Hq, dh) fp32, lse (B, Hq) fp32) — the split-KV partial
    pair the inter-rank combine merges (reference flash_decode.py:130/:482).
    """
    B, Hq, dh = q.shape
    bshd = kv_layout == "bshd"
    if kv_layout == "bhsd":
        _, Hkv, m_kv, _ = k_cache.shape
    elif bshd:
        _, m_kv, Hkv, _ = k_cache.shape
    else:
        raise ValueError(f"unknown kv_layout {kv_layout!r}")
    if Hq % Hkv:
        raise ValueError(f"q heads {Hq} not divisible by kv heads {Hkv}")
    g = Hq // Hkv
    scale = dh ** -0.5 if scale is None else scale
    # Chunk preference bounded so the double-buffered all-heads K+V blocks
    # stay under the staging budget.
    per_pos = Hkv * dh * k_cache.dtype.itemsize * 4
    ck = _kv_chunk(m_kv, min(chunk, max(8, _DECODE_KV_BUDGET // per_pos)))
    n_chunks = m_kv // ck
    kv_len = jnp.broadcast_to(
        jnp.asarray(m_kv if kv_len is None else kv_len,
                    jnp.int32).reshape(-1), (B,))

    # Blocks span ALL local kv heads: Mosaic requires the last two block dims
    # be 8/128-divisible or equal to the full array dims; per-head blocks in
    # the bshd layout would put a size-1 block on the head dim (illegal).
    if bshd:
        kv_spec = pl.BlockSpec((1, ck, Hkv, dh), lambda b, c, kl: (b, c, 0, 0))
    else:
        kv_spec = pl.BlockSpec((1, Hkv, ck, dh), lambda b, c, kl: (b, 0, c, 0))

    qg = q.reshape(B, Hkv, g, dh)

    # Explicit scoped-VMEM grant when the double-buffered KV staging alone
    # approaches the 16MB default (chunk sweeps above 1024 rows): staged KV
    # + kernel temporaries (f32 conversion copies on the per-head path,
    # headroom on the bd path) + accumulators. One definition for both
    # decode paths.
    staged = 4 * ck * Hkv * dh * k_cache.dtype.itemsize
    vlim = None
    if staged > 8 * 2 ** 20:
        vlim = staged + 2 * ck * Hkv * dh * 4 + 8 * 2 ** 20

    # Block-diagonal batched-head path (see _flash_decode_bd_kernel): bshd
    # layout (K_flat/V_flat reshapes are free; bhsd would transpose) with
    # enough rows to dodge bf16 sub-tile relayouts. Measured 18.0 -> 11.1 ms
    # at the B=128/16k bench shape (58% -> ~93% of HBM peak).
    if bshd and Hkv * g >= 16:
        rows, feat = Hkv * g, Hkv * dh
        q_bd = _block_diag_q(qg)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_chunks),
            in_specs=[
                pl.BlockSpec((1, rows, feat), lambda b, c, kl: (b, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=[
                pl.BlockSpec((1, rows, dh), lambda b, c, kl: (b, 0, 0)),
                pl.BlockSpec((1, rows, 1), lambda b, c, kl: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((rows, dh), jnp.float32),   # acc
                pltpu.VMEM((rows, 1), jnp.float32),    # running max
                pltpu.VMEM((rows, 1), jnp.float32),    # denominator
            ],
        )
        out, lse = pl.pallas_call(
            functools.partial(_flash_decode_bd_kernel, n_chunks=n_chunks,
                              ck=ck, scale=scale, n_kv=Hkv, g=g, dh=dh),
            out_shape=[
                jax.ShapeDtypeStruct((B, rows, dh), jnp.float32),
                jax.ShapeDtypeStruct((B, rows, 1), jnp.float32),
            ],
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=vlim),
            cost_estimate=common.cost_estimate(
                flops=4 * B * Hkv * Hkv * g * m_kv * dh,
                bytes_accessed=(B * Hkv * g * Hkv * dh * q.dtype.itemsize
                                + 2 * B * Hkv * m_kv * dh
                                * k_cache.dtype.itemsize
                                + B * Hq * (dh + 1) * 4)),
            name="flash_decode_block_diag",
            interpret=resolve_interpret(interpret),
        )(kv_len, q_bd, k_cache, v_cache)
        return out.reshape(B, Hq, dh), lse.reshape(B, Hq)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_chunks),
        in_specs=[
            pl.BlockSpec((1, Hkv, g, dh), lambda b, c, kl: (b, 0, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, g, dh), lambda b, c, kl: (b, 0, 0, 0)),
            pl.BlockSpec((1, Hkv, g), lambda b, c, kl: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, g, dh), jnp.float32),   # acc
            pltpu.VMEM((Hkv, g, 1), jnp.float32),    # running max
            pltpu.VMEM((Hkv, g, 1), jnp.float32),    # denominator
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_flash_decode_kernel, n_chunks=n_chunks, ck=ck,
                          scale=scale, n_kv=Hkv, bshd=bshd),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, g, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, g), jnp.float32),
        ],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vlim),
        cost_estimate=common.cost_estimate(
            flops=4 * B * Hq * m_kv * dh,
            bytes_accessed=(B * Hq * dh * q.dtype.itemsize
                            + 2 * B * Hkv * m_kv * dh
                            * k_cache.dtype.itemsize
                            + B * Hq * (dh + 1) * 4)),
        name="flash_decode",
        interpret=resolve_interpret(interpret),
    )(kv_len, qg, k_cache, v_cache)
    return out.reshape(B, Hq, dh), lse.reshape(B, Hq)


# ---------------------------------------------------------------------------
# Paged (block-table) KV access — the serving subsystem's cache layout
# ---------------------------------------------------------------------------


def paged_gather_kv(pool, block_tables, *, slot_mask=None, plane=None):
    """Gather one layer's block-paged KV pool into the contiguous per-slot
    layout the attention paths consume (vLLM-style PagedAttention read).

    pool: (n_blocks, block_size, Hkv, dh) — this device's kv-head shard of
    one PLANE (the keys or the values) of one layer of
    ``serving.kv_pool.PagedKVState.kv``, or a latent pool's layer (n_blocks,
    block_size, row). block_tables:
    (B, max_blocks) int32 — slot b's sequence occupies blocks
    ``block_tables[b, :ceil(len/block_size)]`` in order; tail entries are
    allocator padding. Returns (B, max_blocks * block_size, Hkv, dh) — slot
    b's tokens contiguous in sequence order, exactly the ``KVCache`` row
    layout, so the flash/dense attention kernels run UNCHANGED on the
    gathered view with per-slot ``kv_len`` masking the tail.

    ``slot_mask`` (B,) bool routes inactive slots' reads at block 0: a
    freed slot's stale table entries may point at blocks since reallocated
    to other sequences — masked-out garbage either way (attention masks
    positions >= the slot offset), but the mask keeps a dead slot from
    touching live sequences' blocks at all.

    ``plane`` (0 the keys, 1 the values): ``pool`` is one layer of the K+V
    arena as it lies, (n_blocks, 2, block_size, Hkv, dh), and the view is of
    that plane alone: one gather of the plane's blocks, no slice of the
    layer first.

    This is now the REFERENCE path only: every step shape — decode,
    chunked prefill, ragged mixed — routes through the fused in-kernel
    block walk (``kernels.paged_attention.paged_attention`` — no
    materialized view, one pass over the pool bytes) via
    ``nn.paged_attn_with_cache``. The gather survives solely behind the
    explicit ``paged_attn="gather"`` escape hatch, the test oracle the
    fused kernel is verified token-identical against.
    """
    if block_tables.dtype != jnp.int32:
        raise TypeError(
            f"block_tables must be int32 (got {block_tables.dtype}): the "
            f"allocator emits int32 tables (KVPool.padded_tables) and a "
            f"float/int64 table silently cast here would gather the wrong "
            f"blocks")
    B, nb = block_tables.shape
    if slot_mask is not None:
        block_tables = jnp.where(slot_mask[:, None], block_tables, 0)
    # mode="clip" makes the OOB policy explicit (jnp.take's default today,
    # but the correctness of padded/stale table entries rests on it).
    ids = block_tables.reshape(-1)
    g = (jnp.take(pool, ids, axis=0, mode="clip") if plane is None
         else pool.at[ids, plane].get(mode="clip"))
    return g.reshape(B, nb * g.shape[1], *g.shape[2:])


def decode_partial_feat(dh: int) -> int:
    """Feature width of the packed (out, lse) decode-partial rows exchanged
    between ranks: dh + 1 rounded up to a lane multiple (128) — callers
    sizing LL staging for the partial exchange (``make_ll_staging``) must
    use this width."""
    return ((dh + 1 + 127) // 128) * 128


def _pack_decode_partial(out, lse, dh: int):
    """The decode-partial WIRE FORMAT: rows [out | lse | lane-pad] of width
    ``decode_partial_feat(dh)``. One definition — ll_allgather staging and
    both the 1D and 2D exchanges must agree on it byte-for-byte."""
    rows = out.shape[0] * out.shape[1]  # (B, H, dh) -> B*H rows
    feat = decode_partial_feat(dh)
    return jnp.concatenate(
        [out.reshape(rows, dh), lse.reshape(rows, 1),
         jnp.zeros((rows, feat - dh - 1), out.dtype)], axis=-1)


def flash_decode_device(q, k_cache_local, v_cache_local, *, axis: str = "sp",
                        kv_len=None, scale: float | None = None,
                        ll_staging=None, ll_epoch=None, interpret=None):
    """Per-device distributed decode attention (composable inside shard_map).

    q: (B, Hq, dh) replicated; k/v_cache_local: (B, Hkv, m_kv, dh) — the KV
    sequence dim sharded over ``axis``, GQA-native (Hq % Hkv == 0). Each
    device computes its split-KV partial (out, LSE) with the Pallas
    streaming-softmax kernel; partials are allgathered and LSE-merged
    (reference flash_decode.py:482 inter-rank combine). ``kv_len`` is this
    device's LOCAL valid cache length (callers with a global offset pass
    ``clip(offset - me*m_kv, 0, m_kv)``).

    Pass ``ll_staging``/``ll_epoch`` (see ``kernels.ll_allgather``) to ride
    the partial exchange on the low-latency allgather — the reference pairs
    flash-decode with its LL protocol for exactly this exchange
    (sp_flash_decode_layer.py:83). Returns (out, staging) in that case.
    """
    world = _axis_size(axis)
    B, H, dh = q.shape
    out_local, lse_local = flash_decode_local(
        q, k_cache_local, v_cache_local, kv_len=kv_len, scale=scale,
        interpret=interpret)

    if world == 1:
        out = out_local.astype(q.dtype)
        return (out, ll_staging) if ll_staging is not None else out

    # Pack (out, lse) rows; gather all ranks' partials over ICI. The packed
    # feature dim is padded to a lane multiple: Mosaic DMA slices must be
    # 128-aligned and dh+1 is not (the compiled ring kernel rejected 129).
    feat = decode_partial_feat(dh)
    if ll_staging is not None and ll_staging.shape[-1] != feat:
        raise ValueError(
            f"ll_staging feature width {ll_staging.shape[-1]} != "
            f"decode_partial_feat({dh}) = {feat}; size the staging as "
            f"make_ll_staging((B*H, decode_partial_feat(dh)), ...) — the "
            f"packed (out, lse) rows are lane-padded")
    packed = _pack_decode_partial(out_local, lse_local, dh)
    if _ledger.recording():
        from triton_distributed_tpu.runtime import perf_model as pm

        _ledger.record_traced(
            "flash_decode", axis=axis, world=world,
            nbytes=pm.wire_bytes_all_gather(packed.nbytes, world),
            method="ll" if ll_staging is not None else "ring",
            est_s=(pm.est_ll_all_gather if ll_staging is not None
                   else pm.est_ring_all_gather)(packed.nbytes, world))
    if ll_staging is not None:
        from triton_distributed_tpu.kernels.ll_allgather import (
            ll_all_gather_device,
        )

        gathered, ll_staging = ll_all_gather_device(
            packed, ll_staging, ll_epoch, axis=axis, interpret=interpret)
    else:
        gathered = ring_all_gather(packed, axis=axis, interpret=interpret)
    gathered = gathered.reshape(world, B, H, feat)
    outs, lses = gathered[..., :dh], gathered[..., dh]     # (w,B,H,dh), (w,B,H)

    # LSE merge: softmax over ranks weights each partial.
    w = jax.nn.softmax(lses, axis=0)[..., None]
    out = jnp.sum(w * outs, axis=0).astype(q.dtype)
    return (out, ll_staging) if ll_staging is not None else out


def flash_decode_2d_device(q, k_cache_local, v_cache_local, *,
                           ici_axis: str = "sp", dcn_axis: str = "dcn",
                           kv_len=None, scale: float | None = None,
                           interpret=None):
    """Inter-slice distributed decode over a (dcn, ici) mesh — the scale-out
    regime of the reference's flash-decode (its 1->32 GPU scaling crosses
    nodes, README.md:216-219). The KV sequence is sharded over ALL devices
    (dcn-major); ``kv_len`` is this device's LOCAL valid cache length.

    Each device computes its split-KV Pallas partial; partials exchange
    intra-slice through the ring kernel (``flash_decode_device``) producing
    a slice-level (out, lse) partial pair, which then merges across slices
    by log-sum-exp over one DCN allgather of the tiny packed rows (decode
    partials are KB-scale — latency-bound, exactly what the DCN hop wants).
    """
    n_slices = _axis_size(dcn_axis)
    if n_slices == 1:
        return flash_decode_device(q, k_cache_local, v_cache_local,
                                   axis=ici_axis, kv_len=kv_len, scale=scale,
                                   interpret=interpret)
    B, H, dh = q.shape
    # Intra-slice: local partial + ring exchange, but keep the SLICE partial
    # mergeable — recover (out_s, lse_s) for this slice by re-merging the
    # slice's rank partials with their LSEs.
    world = _axis_size(ici_axis)
    out_local, lse_local = flash_decode_local(
        q, k_cache_local, v_cache_local, kv_len=kv_len, scale=scale,
        interpret=interpret)
    feat = decode_partial_feat(dh)
    packed = _pack_decode_partial(out_local, lse_local, dh)
    gathered = ring_all_gather(packed, axis=ici_axis, interpret=interpret)
    gathered = gathered.reshape(world, B, H, feat)
    outs, lses = gathered[..., :dh], gathered[..., dh]

    # Slice-level partial: LSE-merged outputs + the slice's combined LSE.
    w = jax.nn.softmax(lses, axis=0)[..., None]
    out_s = jnp.sum(w * outs, axis=0)                      # (B, H, dh) fp32
    lse_s = jax.scipy.special.logsumexp(lses, axis=0)      # (B, H)

    # DCN hop: allgather the slice partials (XLA collective; KB payload).
    packed_s = _pack_decode_partial(out_s, lse_s, dh)
    all_s = jax.lax.all_gather(packed_s, dcn_axis)         # (n_slices, ...)
    all_s = all_s.reshape(n_slices, B, H, feat)
    outs2, lses2 = all_s[..., :dh], all_s[..., dh]
    w2 = jax.nn.softmax(lses2, axis=0)[..., None]
    return jnp.sum(w2 * outs2, axis=0).astype(q.dtype)


# ---------------------------------------------------------------------------
# Comm-safety analyzer registration (tools/comm_check.py; docs/analysis.md)
# ---------------------------------------------------------------------------

import numpy as _np  # noqa: E402

from triton_distributed_tpu.analysis import registry as _comm  # noqa: E402


@_comm.register("sp.ag_attn")
def _comm_spec_sp_ag_attn(world: int) -> "_comm.TraceSpec":
    H, m, m_kv, dh = 2, 8, 8, 128
    return _comm.TraceSpec(
        body=_sp_attn_kernel,
        args=[
            _comm.Buf("scalars", (3,), _np.int32, space="smem",
                      init=lambda r, w: _np.array([r, r * 8, 0], _np.int32)),
            _comm.Buf("q", (H, m, dh)),
            _comm.Buf("k", (H, m_kv, dh)),
            _comm.Buf("v", (H, m_kv, dh)),
            _comm.Buf("o", (1, m, dh), covered=True),
            _comm.Buf("k_full", (world, H, m_kv, dh)),
            _comm.Buf("v_full", (world, H, m_kv, dh)),
            _comm.Buf("q_vmem", (m, dh), space="vmem"),
            _comm.Buf("k_vmem", (m_kv, dh), space="vmem"),
            _comm.Buf("v_vmem", (m_kv, dh), space="vmem"),
            _comm.Buf("acc", (m, dh), space="vmem"),
            _comm.Buf("m_run", (m, 1), space="vmem"),
            _comm.Buf("l_run", (m, 1), space="vmem"),
            _comm.Sem("send_sems", (2 * (world - 1),)),
            _comm.Sem("recv_sems", (2 * world,)),
            _comm.Sem("copy_sem"),
        ],
        grid=(H, world),
        kwargs=dict(axis="sp", world=world, causal=True, scale=1.0,
                    partials=False),
    )
