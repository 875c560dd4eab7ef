"""Fused paged attention: walk the block table INSIDE the kernel — any L.

The serving path used to read the block-paged KV pool through
``sp_attention.paged_gather_kv``, which materializes a contiguous
``(B, max_blocks * block_size, Hkv, dh)`` copy of BOTH K and V every step,
every layer, before attention runs — the pool bytes are read once to build
the view, written once into it, and read again by the kernel: ~3x the KV
HBM traffic of a single pass. This module is the Pallas upgrade path the
gather docstring promised (and the move vLLM's PagedAttention / Flash-
Decoding make): the kernel receives the block table via scalar prefetch,
DMA-copies each sequence's pool blocks straight into VMEM staging, and runs
the streaming-softmax accumulation of ``_flash_decode_kernel`` over the
block grid — attention becomes HBM-bound on the VALID cache bytes only,
with no materialized dense view at all.

Scope: EVERY query length. Decode (L == 1) is the original hot loop; since
this kernel grew a query-tile grid dimension, chunked-prefill and ragged
mixed steps route here too (``layers.nn.paged_attn_with_cache`` no longer
falls back to the gather for L > 1 — ``paged_attn="gather"`` survives only
as the explicit escape hatch / test oracle). Each query tile applies
causal masking against the block table using the per-slot
(``kv_lens``, ``q_lens``) pair: query row j of slot b sits at absolute
position ``kv_lens[b] - q_lens[b] + j`` and attends keys up to itself, so
earlier query tiles skip the DMAs for blocks past their own causal
frontier — the fused prefill reads at most one causal pass of the prefix
where the gather always bills three full ones.

Grid: ``(B, n_q_tiles)`` with ``n_q_tiles = ceil(L / q_tile)``. The kv
tiles (``tile_blocks`` pool blocks each) are a loop INSIDE the grid step
whose trip count is the slot's own number of live tiles, read from the
scalar-prefetched lengths: the running (acc, max, denom) triple lives for
the step, and tiles past a slot's causal frontier are never visited — a
short sequence in a long-table batch costs only its own tiles. The loop is
a two-slot FETCH PIPELINE (one path for the K+V, the quantized and the
latent build): every live block of a tile is ONE DMA (the pool keeps a
block's K rows and V rows side by side, ``serving.kv_pool``: both planes are
one run of bytes and one copy; a quantized pool's scale planes are a second
arena and a second copy), all of a
tile's copies are started before any is waited for (a 32 KiB copy waited
for alone costs its latency, 0.46 us: 70 GB/s of 819), and tile ``n + 1``'s
copies — after a step's last tile, the next grid step's first — fly into
the other staging slot while tile ``n`` is multiplied. Dead slots are
routed to block 0 on the HOST (same semantics as the gather path) and their
outputs discarded by the caller; padding query rows (j >= q_lens[b]) emit
exact zeros, matching ``attn_with_cache``'s varlen contract.

Three things are static a call site, and each is one choice of the ONE
kernel body: the BUILD (one K+V arena of paired planes, its quantized form
with a scale arena, or one latent arena), the ARITHMETIC of a staged tile
(``tile_arithmetic``: folded at the decode shape, per head elsewhere) and,
since the model with
window layers, where the WALK STARTS: at block 0 with the causal frontier
its only limit, or (``window=w``) at the tile that holds the query tile's
oldest visible key, over a window layer's ring storage (``(layers, slots,
2 planes, ring blocks, ...)``, the table one slot id a row and a logical
block's place in the ring arithmetic), with a lower bound in the score-side select
and the V scrub. A block wholly behind the window costs no copy and no
wait, so a window layer's step reads ``window`` rows a sequence whatever
its context; the fetch pipeline and both arithmetics are shared. That build
is named ``window_paged_attention`` in a device trace (the latent one
``latent_paged_attention``).

The (kv-tile, q-tile) pair is a ``ContextualAutotuner`` config keyed on
(block_size, Hkv, dh, max_blocks, L, g, dtype) — ``tuned_paged_tile`` —
with a VMEM-bounded heuristic default off-TPU / under trace that covers
the whole chunk in ONE query tile whenever the staging fits (fewest
re-reads of the kv prefix: the entire point of fusing prefill).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.kernels import common
from triton_distributed_tpu.kernels import probes as _probes
from triton_distributed_tpu.runtime.platform import on_tpu, resolve_interpret

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# (kv-tile, q-tile) config autotuning
# ---------------------------------------------------------------------------

# Candidate kv tile sizes (pool blocks staged per loop turn). Preference
# order: the VMEM-bounded heuristic winner is inserted first by
# _feasible_tiles, so off-TPU and trace-time callers get it
# deterministically.
_TILE_CANDIDATES = (8, 16, 4, 2, 1, 32)

# Candidate query tile sizes (query TOKENS per grid step; each stages
# q_tile * g query rows). The L-covering tile is always considered too.
_QTILE_CANDIDATES = (64, 32, 16, 8, 4, 2, 1)


def _feasible_tiles(block_size: int, n_kv_heads: int, head_dim: int,
                    max_blocks: int, itemsize: int,
                    kv_scales: bool = False) -> list[int]:
    """Candidate kv tiles whose VMEM staging — the K and the V plane, TWO
    slots each, what the kernel allocates for its fetch pipeline — fits the
    collective staging budget, capped at the table width; heuristic default first
    (largest feasible tile staging <= 512 cache rows — enough copies in
    flight to hide a DMA's latency without hogging VMEM, the flash-decode
    chunk preference applied to blocks). ``kv_scales`` bills the quantized
    pool's extra f32 per-row scale staging (two more buffers a slot, one
    scale per staged (row, kv head)) — the wire tiles shrink with
    ``itemsize`` but the scale staging rides the same budget."""
    per_block = 2 * block_size * n_kv_heads * head_dim * itemsize
    if kv_scales:
        per_block += 2 * block_size * n_kv_heads * 4
    per_block *= 2                              # two staging slots
    ok = [t for t in _TILE_CANDIDATES
          if t <= max(1, max_blocks)
          and t * per_block <= common.VMEM_STAGE_BUDGET]
    if not ok:
        ok = [1]
    default = max((t for t in ok if t * block_size <= 512), default=min(ok))
    return [default] + [t for t in sorted(ok, reverse=True) if t != default]


def _feasible_qtiles(L: int, n_kv_heads: int, g: int, head_dim: int,
                     itemsize: int) -> list[int]:
    """Candidate query tiles for an L-token chunk. Every query tile
    re-walks the kv prefix up to its own causal frontier, so FEWER tiles
    means fewer prefix re-reads — the default (first) is the
    fewest-tiles feasible choice, ideally the whole chunk in one tile,
    which is what keeps the fused prefill at ~1x pool traffic where the
    gather bills 3x. Feasibility bounds the per-tile f32 accumulator +
    f32 out block + wire-dtype q block by the staging budget."""
    if L <= 1:
        return [1]
    per_tok = n_kv_heads * g * head_dim * (8 + itemsize)
    ok = [t for t in _QTILE_CANDIDATES
          if t <= L and t * per_tok <= common.VMEM_STAGE_BUDGET]
    if L * per_tok <= common.VMEM_STAGE_BUDGET:
        ok.append(L)
    if not ok:
        ok = [1]
    return sorted(set(ok), key=lambda t: (-(-L // t), -t))


def tuned_paged_tile(block_size: int, n_kv_heads: int, head_dim: int,
                     max_blocks: int, dtype_str: str = "bfloat16",
                     L: int = 1, g: int = 2) -> tuple[int, int]:
    """(tile_blocks, q_tile) config for ``paged_attention``, contextual-
    autotuner cached per (block_size, Hkv, dh, max_blocks, L, g, dtype).

    Off-TPU or under an active jax trace the tuner never times: a cached
    winner is used if one exists, else the VMEM-bounded heuristic default
    is returned UNCOMMITTED (the autotuner commit discipline —
    runtime/autotuner.py ``_tune_matmul_blocks``). On a real TPU an eager
    call tunes the candidates over a synthetic pool at the live geometry
    with the interleaved slope timer. The resource pruner evaluates each
    candidate pair against the registered ``paged.decode`` /
    ``paged.prefill`` trace spec so a VMEM-blowing (kv-tile, q-tile)
    staging combination is rejected before it ever compiles.
    """
    from triton_distributed_tpu.runtime.autotuner import (
        ContextualAutotuner,
        _memoized_blocks,
        _memory_cache,
        _trace_state_clean,
        interleaved_slope_timer,
    )

    wire_dt = jnp.dtype(dtype_str)
    itemsize = wire_dt.itemsize
    # Quantized pools (int8/fp8 wire dtype): wire tiles shrink, per-row f32
    # scale staging rides the budget, and queries stage in the COMPUTE
    # dtype (f32 accumulation — bill 4 bytes, conservative for bf16 q).
    quant = wire_dt in (jnp.dtype(jnp.int8), jnp.dtype(jnp.float8_e4m3fn))
    kv_cands = _feasible_tiles(block_size, n_kv_heads, head_dim, max_blocks,
                               itemsize, kv_scales=quant)
    q_cands = _feasible_qtiles(L, n_kv_heads, g, head_dim,
                               4 if quant else itemsize)
    cands = [(t, qt) for qt in q_cands for t in kv_cands]
    if len(cands) == 1:
        return cands[0]

    def resource_pruner(cfg):
        # Static VMEM/layout feasibility of one candidate pair, evaluated
        # against the registered trace spec at the live geometry — any
        # finding rejects the config before the tuner ever compiles it.
        # Lazy import: the analysis layer must stay optional on the
        # serving hot path.
        from triton_distributed_tpu.analysis import resources as _res

        tile, q_tile = cfg
        name = "paged.decode" if L == 1 else "paged.prefill"
        if quant:
            name += ".kvq"
        kw = dict(tile_blocks=int(tile), bs=block_size, n_kv=n_kv_heads,
                  dh=head_dim, max_blocks=max_blocks, dtype=dtype_str)
        if L > 1:
            kw.update(L=int(L), q_tile=int(q_tile), g=int(g))
        return _res.check_kernel(name, 1, kw, trace=False)

    tuner = ContextualAutotuner("paged_attn_cfg", cands,
                                multi_timer=interleaved_slope_timer,
                                pruner=resource_pruner)
    ctx = (f"bs{block_size}:h{n_kv_heads}:d{head_dim}:mb{max_blocks}"
           f":L{L}:g{g}:{dtype_str}")

    if not on_tpu() or not _trace_state_clean():
        cached = tuner.peek(ctx)
        return tuple(cached) if cached is not None else cands[0]

    def compute():
        B = 8
        dtype = jnp.dtype(dtype_str)
        n_blocks = B * max_blocks
        key = jax.random.PRNGKey(0)
        pool = jax.random.normal(
            key, (n_blocks, 2, block_size, n_kv_heads, head_dim))
        scales = None
        if quant:
            from triton_distributed_tpu.layers.nn import quantize_kv_rows

            pool, scales = quantize_kv_rows(pool, dtype)
        else:
            pool = pool.astype(dtype)
        q = jax.random.normal(
            jax.random.fold_in(key, 2),
            (B, L, n_kv_heads * g, head_dim)).astype(
                jnp.float32 if quant else dtype)
        tables = jnp.arange(B * max_blocks, dtype=jnp.int32).reshape(
            B, max_blocks)
        kv_lens = jnp.full((B,), max_blocks * block_size, jnp.int32)
        q_lens = jnp.full((B,), min(L, max_blocks * block_size), jnp.int32)

        def make_loop(cfg):
            tile, q_tile = cfg

            @jax.jit
            def loop(q, n_iter):
                def body(_, acc):
                    out = paged_attention(
                        acc.astype(q.dtype), pool, tables, kv_lens,
                        q_lens=q_lens, tile_blocks=tile, q_tile=q_tile,
                        scales=scales)
                    return out.astype(jnp.float32)
                return jax.lax.fori_loop(0, n_iter, body,
                                         q.astype(jnp.float32))

            loop(q, jnp.int32(2)).block_until_ready()
            return lambda n_iter: loop(q, jnp.int32(n_iter))

        cfg = tuner.tune(make_loop, ctx)
        # tune() returns config 0 UNCACHED when every candidate timed out —
        # the memoized result must mirror that so a later call re-tunes.
        return tuple(cfg), tuner._key(ctx) in _memory_cache

    return _memoized_blocks(("paged_cfg", block_size, n_kv_heads, head_dim,
                             max_blocks, dtype_str, int(L), int(g)), compute)


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------


def _paged_attn_kernel(*refs, n_arenas: int, n_tiles: int, tile_blocks: int,
                       bs: int, n_blocks: int, scale: float, n_kv: int,
                       g: int, q_tile: int, n_q_tiles: int,
                       probe_steps: int = 0, v_dim: int | None = None,
                       folded: bool = False, compiled: bool = False,
                       window: int | None = None, aligned: bool = False,
                       summary: tuple | None = None, stats: bool = False):
    """One (slot, query-tile) grid step of fused paged attention: the kv
    tiles of the slot are walked by a loop INSIDE the step, two staging
    slots deep.

    Refs, in ``pallas_call`` order: ``tbl_ref`` (B, max_blocks) int32,
    ``kvlen_ref`` (B,) int32, ``qlen_ref`` (B,) int32 and ``layer_ref``
    (1,) int32 arrive via scalar prefetch (SMEM — readable before any DMA
    is issued, which is the whole trick: the block ids ARE the gather,
    resolved in-kernel); the q block; ``n_arenas`` pool arenas in ANY/HBM;
    the out block (then the probe buffer of a probed build); one staging
    buffer an arena, ``(2 slots, 2 planes, tile_blocks * bs, ...)`` (a
    latent arena has no planes); the running (acc, max,
    denominator); the DMA semaphores ``(2, n_arenas)``; the walk's state
    across grid steps (then the probe's ordinal cell). The arenas stay
    STACKED ``(n_layers, n_blocks, 2, bs, ...)`` — the layer is one more DMA
    index, so the model's layer scan never slices (and so never
    materializes) a layer of the pool.

    THE FETCH PIPELINE — one path for every build. Block ``i`` of a kv tile
    is ONE copy an arena, ``arena.at[layer, blk] -> staging.at[slot, :, rows
    of i]`` on semaphore ``[slot, arena]``: the block's K plane and V plane
    lie side by side in HBM (``2 * bs * Hkv * dh`` items, one run) and land
    in the two planes of the staging slot, so ``staging[slot, 0]`` is the
    tile's keys and ``staging[slot, 1]`` its values. A copy costs 35-37 ns
    whatever it carries and a tile is bound by the NUMBER of its copies
    until a copy is about 25-32 KiB (PERF.md section 6, PR 42): a pair is
    half the starts and half the waits of a copy a plane, at twice the
    bytes (``copy_bytes`` / ``copies_per_tile`` of ``paged_attn_cost`` and
    of the comm ledger's ``paged_attn`` series). Turn ``j`` of the walk STARTS
    every live copy of tile ``j`` — none is waited for until all are in
    flight — and then waits for tile ``j - 1``'s copies in the other slot
    and computes from it: tile ``j``'s bytes fly while tile ``j - 1`` is
    multiplied. The walk's trip count is the slot's own number of live
    tiles (``cdiv(limit, tile_blocks * bs)`` from the prefetched lengths),
    so a short sequence in a long table costs its own tiles and nothing
    else, and a query tile past the slot's ``q_len`` costs none. After its
    last tile a step starts the NEXT grid step's first tile (the lengths
    and the table of every slot are in SMEM), so only the kernel's first
    tile is fetched cold; ``walk_ref`` (SMEM, two cells) hands the next
    step "your tile 0 is in flight" and the slot it went to, which is why
    both grid dimensions are ``arbitrary``: the steps run in order on one
    core (v5e has one TensorCore; a two-core chip would need a walk state a
    core). A semaphore belongs to one (slot, arena): its count is back at
    zero when the tile's waits are done, before the slot is started again
    two turns later, so a wait can only be met by its own tile's arrivals.
    Every started copy is waited for (same ``pl.when``) by the step that
    computes from it, the last one before the kernel ends.

    Blocks past this query tile's causal frontier are never copied; the
    row-liveness mask zeroes whatever stale staging rows the skipped fetch
    left behind — the score-side select scrubs stale K, a select over V
    before the PV dot scrubs stale V (``0 * NaN`` is NaN) — in EITHER slot,
    so the prefetch can stage nothing the mask does not scrub.

    THE ARITHMETIC OF A STAGED TILE is streaming softmax with float32
    scores, max, denominator and accumulator in every build; what differs
    is the operands of the two dots (``tile_arithmetic`` names the choice,
    static a call site). ``folded`` — the K+V build at one query token a
    grid step, the decode shape: ONE dot of all ``Hkv * g`` query rows
    against the tile read as ``(span * Hkv, dh)`` key-head rows, operands
    in the pool's dtype (``compute_folded``): with a bf16 pool nothing is
    cast, ``q . k`` sums the same exact products a float32 dot would, and
    ``p`` is rounded to bf16 for the PV dot (a relative 2^-8 a weight, what
    the latent build and every published bf16 attention do); a float32 pool
    keeps float32 operands. V is scrubbed in a slot's last tile only, the
    one place dead rows exist. ``per_head`` — every other shape: a pair of
    dots a kv head over that head's sublane-strided slice of the staging,
    cast to float32 (bf16 operands of a few query rows hit Mosaic's
    relayout path), V selected tile by tile.

    THE WINDOW BUILD (``window`` given; the K+V build only) is the third static
    choice of the walk, beside the build and the arithmetic: a query at
    position ``p`` sees the keys ``p - window < j <= p``. The walk STARTS at
    the tile that holds the query tile's oldest visible key, so a block
    wholly behind the window is neither copied nor waited for; the
    score-side select and the V scrub take that lower bound beside the
    causal one. The arena is a window layer's RING storage ``(layers,
    slots, 2, ring blocks, bs, Hkv, dh)`` (``serving.kv_pool``: the planes
    OUTSIDE a slot's lines), handed to the
    kernel as a slot's lines ``(layers, slots, 2, ring blocks * bs, Hkv,
    dh)`` (the same bytes): the table is ``(B, 1)``, the SLOT each row belongs
    to, and logical block ``j`` of that slot is ring block ``j % ring
    blocks`` of it: arithmetic, no table of blocks. So the blocks of a tile
    lie SIDE BY SIDE in HBM, which no paged pool's do, and the fetch takes
    its copy size from that: a WHOLE tile (every block live: none behind
    ``lo``, none past ``limit``) that does not wrap the ring is ONE copy
    (a run of lines a plane: two chunks of one DMA), started once and waited
    for once at the tile's size; a tile
    ragged at either end, or one that wraps, goes a copy a live block like
    any other build's. A copy costs about 37 ns whatever it carries
    (PERF.md section 6, PR 42), so a tile of 32 blocks of 16 KiB a plane is
    bound by its 32 copies, and by its bytes only as 1. No whole copy reads
    a block the walk by blocks would not.
    The pipeline's order, the semaphores and both arithmetics are the K+V
    build's own.

    THE TWO HALVES OF AN EVA LAYER (``layers/eva_attn.py``) are two more
    static choices of WHERE A QUERY'S KEYS BEGIN AND END, each over arenas
    this kernel already walks. ``aligned`` (with ``window``): the window
    does not slide: a query at ``p`` sees ``(p // window) * window <= j <=
    p``, its own window from its first position (one key at a boundary).
    ``summary=(window, rows)`` (the K+V build over the block arenas, whose
    row ``c`` stands for the ``window // rows`` positions of chunk ``c``): a
    query at ``p`` sees the rows ``c < rows * (p // window)``, every chunk
    of every EARLIER window and none of its own; ``kv_lens`` and ``q_lens``
    stay the token positions and the row limit is computed here, a query
    row at a time, so a query tile that straddles a boundary is exact.
    ``stats``: the call also returns each query row's running maximum and
    denominator, what one combine of the two halves needs
    (``nn.eva_attn_with_cache``).

    Builds: the K+V build is ONE arena of paired planes ``(..., 2, bs, Hkv,
    dh)``. ``n_arenas == 2`` — a QUANTIZED pool (int8/fp8 wire dtype): the
    per-row f32 scale arena ``(..., 2, bs, Hkv)`` rides the same pipeline
    (a second copy a block) and dequant happens HERE, right
    after staging — the wire cast to f32 times the staged scale column —
    so HBM only ever moves wire bytes while the arithmetic is the per-head
    float32 one. ``v_dim`` given — a LATENT pool: ONE arena ``(..., bs, W)``
    of rows shared by every query head (absorbed latent attention is
    multi-query attention with one key head); each block is copied ONCE and
    used twice: the whole staged row is the key, its first ``v_dim``
    columns the value, and the two dots take the rows in the pool's dtype
    with float32 accumulation.
    """
    latent = v_dim is not None
    tbl_ref, kvlen_ref, qlen_ref, layer_ref, q_ref = refs[:5]
    arenas = refs[5:5 + n_arenas]
    o_ref = refs[5 + n_arenas]
    rest = refs[6 + n_arenas:]
    if stats:
        (m_out, l_out), rest = rest[:2], rest[2:]
    probe = _probes.NULL
    if probe_steps:
        probe = _probes.Probe(rest[0], rest[-1], n_steps=probe_steps)
        rest = rest[1:-1]
    stages = rest[:n_arenas]
    acc_ref, m_ref, l_ref, sems, walk_ref = rest[n_arenas:]
    quant = n_arenas == 2
    kv = stages[0]      # (slot, plane, row, head, dh); latent: (slot, row, W)

    b = pl.program_id(0)
    qt = pl.program_id(1)
    layer = layer_ref[0]
    span = tile_blocks * bs
    if window is not None:
        ring = arenas[0].shape[3] // bs     # blocks of a slot's ring

    def frontier(b, qt):
        """(kv_len, q_len, fetch ceiling, live kv tiles) of a grid step.
        The ceiling is causal: the query tile's last live row (local index
        jmax_p1 - 1) sits at absolute position kv_len - q_len + jmax_p1 - 1
        and attends no key past itself, so later blocks are never copied —
        the causal half-read the byte model bills. No tile is live for a
        query tile past q_len, and never more than the table holds."""
        kv_len, q_len = kvlen_ref[b], qlen_ref[b]
        jmax_p1 = jnp.minimum((qt + 1) * q_tile, q_len)
        limit = jnp.minimum(kv_len, kv_len - q_len + jmax_p1)
        if summary is not None:
            # rows, not positions: the chunks of the windows before the
            # tile's last live query's own
            limit = summary[1] * (jnp.maximum(limit - 1, 0) // summary[0])
        n_live = jnp.where(
            qt * q_tile < q_len,
            jnp.clip(pl.cdiv(limit, span), 0, n_tiles), 0)
        return kv_len, q_len, limit, n_live

    def behind(b, qt, n_live):
        """The window build's lower end of a grid step: (the oldest key
        position any query of the tile sees, the tile that holds it, the
        tiles the walk visits from there). The tile's first query sits at
        ``kv_len - q_len + qt * q_tile`` and sees ``window`` keys up to
        itself. Without a window the walk starts at tile 0 and nothing
        here is traced."""
        if window is None:
            return 0, 0, n_live
        head = kvlen_ref[b] - qlen_ref[b] + qt * q_tile
        lo = ((jnp.maximum(head, 0) // window) * window if aligned
              else jnp.maximum(head - (window - 1), 0))
        first = lo // span
        return lo, first, jnp.maximum(n_live - first, 0)

    kv_len, q_len, limit, n_live = frontier(b, qt)
    lo, first, n_walk = behind(b, qt, n_live)
    # The grid step after this one: its first tile is started under this
    # step's last, so no step but the kernel's first waits for a cold fetch.
    step = b * n_q_tiles + qt
    wraps = qt + 1 == n_q_tiles
    b_nx = jnp.minimum(jnp.where(wraps, b + 1, b), pl.num_programs(0) - 1)
    qt_nx = jnp.where(wraps, 0, qt + 1)
    _, _, limit_nx, n_live_nx = frontier(b_nx, qt_nx)
    lo_nx, first_nx, n_walk_nx = behind(b_nx, qt_nx, n_live_nx)
    has_nx = (step + 1 < pl.num_programs(0) * n_q_tiles) & (n_walk_nx > 0)

    @pl.when(step == 0)
    def _cold():
        walk_ref[0] = 0
        walk_ref[1] = 0
    started = walk_ref[0]       # 1: the step before this started our tile 0
    slot0 = walk_ref[1]         # the staging slot our tile 0 goes to
    # Probe rows are absolute (slot, q-tile, kv-tile), so the decoder
    # labels rows per batch slot; single-device kernel: rank 0 / world 1.
    row0 = step * n_tiles

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def block_copies(b, tile, slot, i, n=1):
        """Blocks ``i .. i + n`` of slot ``b``'s kv tile ``tile``: one
        (source, staging rows, semaphore) copy an arena into staging slot
        ``slot``, BOTH planes in it. A paged pool scatters its blocks, so
        there ``n`` is 1 and the source is the block as it lies, planes
        side by side; a ring's blocks lie side by side a plane and ``n`` of
        them are one copy of two chunks. ``b`` None
        builds a copy to WAIT for: that needs its size and semaphore, not
        its source, so it reads no table entry."""
        rows = pl.ds(i * bs, n * bs)
        dst = (slot, rows) if latent else (slot, slice(None), rows)
        if window is None:
            # Same defensive clamp as the gather path's mode="clip".
            src = (layer, 0 if b is None else jnp.clip(
                tbl_ref[b, tile * tile_blocks + i], 0, n_blocks - 1))
        else:
            # Ring storage: the row's slot, then (a plane) the lines of the
            # logical block's place in the slot's ring.
            src = (layer, 0, slice(None), rows) if b is None else (
                layer, jnp.clip(tbl_ref[b, 0], 0, n_blocks - 1), slice(None),
                pl.ds(jax.lax.rem(tile * tile_blocks + i, ring) * bs,
                      n * bs))
        return [(arena.at[src], stage.at[dst], sems.at[slot, a])
                for a, (arena, stage) in enumerate(zip(arenas, stages))]

    def for_live_blocks(tile, limit, lo, fn):
        # Every tile but a slot's last is whole: no test a block there. (In
        # the window build the walk's first tile is ragged at its start
        # too: a block whose last row lies behind ``lo`` is not live.)
        whole = (tile + 1) * span <= limit

        def live(i):
            ok = tile * span + i * bs < limit
            if window is not None:
                ok &= tile * span + (i + 1) * bs > lo
            return ok

        if window is None:
            @pl.when(whole)
            def _all():
                for i in range(tile_blocks):
                    fn(i)
        else:
            # A whole tile whose blocks do not wrap the ring is ONE run of
            # lines a plane in HBM: one copy. One that wraps goes block by
            # block with the ragged ones.
            whole &= tile * span + bs > lo
            whole &= (jax.lax.rem(tile * tile_blocks, ring) + tile_blocks
                      <= ring)

            @pl.when(whole)
            def _one():
                fn(0, tile_blocks)

        @pl.when(jnp.logical_not(whole))
        def _ragged():
            for i in range(tile_blocks):
                @pl.when(live(i))
                def _(i=i):
                    fn(i)

    def start_tile(b, tile, limit, lo, slot):
        def start(i, n=1):
            for src, dst, sem in block_copies(b, tile, slot, i, n):
                probe.dma_issue(src)
                pltpu.make_async_copy(src, dst, sem).start()
        for_live_blocks(tile, limit, lo, start)

    def wait_tile(tile, slot):
        def wait(i, n=1):
            for src, dst, sem in block_copies(None, tile, slot, i, n):
                pltpu.make_async_copy(src, dst, sem).wait()
                probe.dma_wait(src)
        for_live_blocks(tile, limit, lo, wait)

    def accumulate(h, q, k, v, valid, guard):
        """One streaming-softmax turn of accumulator row ``h``: the scores
        of ``q`` against the staged rows ``k``, masked by ``valid``, into
        the running (max, denominator, acc) with the values ``v``. The two
        dots take their operands as handed (the caller chooses the dtype)
        and accumulate in float32; ``p`` is cast to the values' dtype for
        the PV dot — with a bf16 pool the one rounding this kernel adds."""
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(valid, scores, _NEG_INF)
        seg_max = jnp.max(scores, axis=-1, keepdims=True)
        new_max = jnp.maximum(m_ref[h], seg_max)
        corr = jnp.exp(m_ref[h] - new_max)
        p = jnp.exp(scores - new_max)
        if guard:
            # A fully-masked row has scores == new_max == _NEG_INF and
            # exp(0) == 1 would poison the denominator.
            p = p * valid.astype(jnp.float32)
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = new_max

    def compute_folded(tile, slot):
        """The K+V build's decode shape (``q_tile == 1``): ALL ``n_kv * g``
        query rows in one left operand against the staged tile as it lies.
        The tile's ``(span, n_kv, dh)`` rows are read as ``(span * n_kv,
        dh)`` key-head rows — the same bytes — so column ``c`` of the
        scores is key ``c // n_kv`` of head ``c % n_kv``; a static mask
        keeps each query row to its own head's columns, ``p`` is zero on
        the others, and the PV dot over all ``span * n_kv`` value rows is
        already each head's own sum. No slice a head, no cast of a staged
        row: the operands stay in the pool's dtype."""
        base = tile * span
        width, dh = span * n_kv, q_ref.shape[3]

        # Compiled, a 16-bit tile is read as the 32-bit words it is stored in
        # (two rows to a word, both of one key while n_kv is even) and
        # bitcast back: the load lands in the packed layout the MXU takes,
        # where the load of (n_kv, dh) sub-tiles is relaid out vreg by vreg
        # (twice the kernel's code, 2.2 x the time at n_kv 4). The
        # interpreter has no view of a ref, nor has the analyzer's tracer:
        # they reshape the value, the same rows.
        if compiled and kv.dtype.itemsize == 2 and n_kv % 2 == 0:
            words = kv.bitcast(jnp.uint32).reshape(2, 2, width // 2, dh)

            def rows_of(plane):
                return pltpu.bitcast(words[slot, plane], kv.dtype)

            def scrub_v():
                r = jax.lax.broadcasted_iota(jnp.int32, (width // 2, dh), 0)
                w = words[slot, 1]
                keep = r < (limit - base) * (n_kv // 2)
                if window is not None:
                    keep &= r >= (lo - base) * (n_kv // 2)
                words[slot, 1] = jnp.where(keep, w, jnp.zeros_like(w))
        else:
            def rows_of(plane):
                return kv[slot, plane].reshape(width, dh)

            def scrub_v():
                row_pos = base + jax.lax.broadcasted_iota(
                    jnp.int32, (span, 1, 1), 0)
                v = kv[slot, 1]
                keep = row_pos < limit
                if window is not None:
                    keep &= row_pos >= lo
                kv[slot, 1] = jnp.where(keep, v, jnp.zeros_like(v))

        # Rows past the causal frontier hold whatever the pool or a skipped
        # fetch left there, and ``0 * NaN`` is NaN in the PV dot. They exist
        # only in a slot's last, ragged tile (and, behind a window, in the
        # walk's first): scrub V there and nowhere else.
        ragged = base + span > limit
        if window is not None:
            ragged |= base < lo
        pl.when(ragged)(scrub_v)

        q = q_ref[0, 0]                                  # (n_kv*g, dh)
        k, v = rows_of(0), rows_of(1)
        dt = jnp.promote_types(q.dtype, k.dtype)
        col = jax.lax.broadcasted_iota(jnp.int32, (n_kv * g, width), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (n_kv * g, width), 0)
        # One query token a step (q_tile == 1): it attends keys up to
        # itself, and ``limit`` is one past its own position, so the live
        # columns of this tile are those below (limit - base) * n_kv. A
        # walked tile has a live key for every row (base < limit), so no
        # row is fully masked and exp(_NEG_INF - max) is an exact zero: no
        # guard on p.
        valid = (col % n_kv == row // g) & (col < (limit - base) * n_kv)
        if window is not None:
            valid &= col >= (lo - base) * n_kv
        accumulate(0, q.astype(dt), k.astype(dt), v.astype(dt), valid,
                   guard=False)
        probe.compute(4 * n_kv * g * width * dh)

    def compute_tile(tile, slot):
        if folded:
            return compute_folded(tile, slot)
        base = tile * span
        # Staging rows whose block was never fetched hold garbage (NaN in
        # interpret mode, stale VMEM on hardware). The score-side causal
        # mask scrubs stale K (a masked score is overwritten), but stale V
        # flows through the PV dot where ``0 * NaN = NaN`` — zero the dead
        # rows explicitly before contracting.
        row_pos = base + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
        row_live = row_pos < limit                           # (T*bs, 1) bool
        if window is not None:
            row_live &= row_pos >= lo

        rows = q_ref.shape[2]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
        # Row r of the q block is query token j = qt*q_tile + r//g (the
        # g query heads of one token share a kv head group); it may
        # attend keys up to its own absolute position
        # kv_len - q_len + j. Padding rows (j >= q_len) mask every key
        # and emit exact zeros at the end — the varlen contract.
        j = (qt * q_tile
             + jax.lax.broadcasted_iota(jnp.int32, (rows, span), 0) // g)
        if summary is not None:
            valid = (j < q_len) & (pos < summary[1] * (
                (kv_len - q_len + j) // summary[0]))
        else:
            valid = (j < q_len) & (pos <= kv_len - q_len + j)
        if aligned:
            valid &= pos >= ((kv_len - q_len + j) // window) * window
        elif window is not None:
            valid &= pos > kv_len - q_len + j - window
        for h in range(n_kv):
            if latent:
                q = q_ref[0, 0]                              # (q_tile*g, W)
                k = kv[slot]                                 # (T*bs, W)
                v = kv[slot, :, :v_dim]
            else:
                # A head's rows are a sublane-strided slice of the staging;
                # the f32 casts are deliberate — see _flash_decode_kernel:
                # bf16 g-row sub-tiles hit Mosaic's relayout path and
                # measured slower. (The decode shape takes compute_folded
                # and casts nothing.)
                q = q_ref[0, h].astype(jnp.float32)          # (q_tile*g, dh)
                k = kv[slot, 0, :, h, :].astype(jnp.float32)  # (T*bs, dh)
                v = kv[slot, 1, :, h, :].astype(jnp.float32)
            if quant:
                # In-staging dequant: one f32 scale per staged (row, kv
                # head), broadcast over head_dim. Stale (unfetched) rows'
                # garbage products are scrubbed exactly like the
                # unquantized build: K by the score-side causal mask, V by
                # the row_live select below.
                k = k * stages[1][slot, 0, :, h:h + 1]
                v = v * stages[1][slot, 1, :, h:h + 1]
            # where, not multiply: 0 * NaN is still NaN.
            v = jnp.where(row_live, v, jnp.zeros_like(v))
            accumulate(h, q, k, v, valid, guard=True)
        # QK^T + PV dots over the staged rows, all kv heads this tile.
        probe.compute(4 * n_kv * (q_ref.shape[2]) * span * q_ref.shape[3])

    def walk(j, carry):
        # Turn j starts a tile into slot (slot0 + j) % 2 — this step's tile
        # j, or after its last the next step's tile 0 — then waits for and
        # computes tile j - 1 from the other slot. Probe row t holds tile
        # t's waits and compute and the starts issued beside them.
        # (The window build's tiles are numbered from ``first``: turn j
        # starts tile first + j and works on tile first + j - 1.)
        tile = j - 1
        probe.enter(row0 + jnp.maximum(tile, 0), 0, 1,
                    fresh=(j != 1) | (started == 1))
        own = j < n_walk

        @pl.when(own | has_nx)
        def _start():
            whose = jnp.where(own, b, b_nx)
            if window is None:
                nth, lo_j = jnp.where(own, j, 0), 0
            else:
                nth = jnp.where(own, first + j, first_nx)
                lo_j = jnp.where(own, lo, lo_nx)
            start_tile(whose, nth, jnp.where(own, limit, limit_nx), lo_j,
                       jax.lax.rem(slot0 + j, 2))

        @pl.when(j > 0)
        def _work():
            slot = jax.lax.rem(slot0 + tile, 2)
            nth = tile if window is None else first + tile
            wait_tile(nth, slot)
            compute_tile(nth, slot)
        return carry

    jax.lax.fori_loop(started, n_walk + 1, walk, 0)
    walk_ref[0] = has_nx.astype(jnp.int32)
    walk_ref[1] = jax.lax.rem(slot0 + n_walk, 2)
    if probe_steps:
        # Rows of the tiles the walk never reached (outputs start
        # uninitialized): open them empty.
        def open_row(t, carry):
            probe.enter(row0 + t, 0, 1)
            return carry
        jax.lax.fori_loop(jnp.maximum(n_live, 1), n_tiles, open_row, 0)

    denom = jnp.maximum(l_ref[...], 1e-30)           # (n_kv, q_tile*g, 1)
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
    if stats:
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


def tile_arithmetic(n_kv_heads: int, q_tile: int, *, latent: bool = False,
                    quant: bool = False) -> str:
    """Which arithmetic a staged kv tile gets, from what a call site can
    see: ``"folded"`` — the K+V build at one query token a grid step
    (the decode shape), every kv head's query rows in ONE operand against
    the tile as it lies, operands in the pool's dtype — or ``"per_head"``:
    a pair of dots a kv head over that head's slice of the staging (the
    chunk shape, whose folded scores would not fit; the quantized build,
    whose dequantization is a head's; the latent build, which has one head
    and nothing to fold)."""
    if latent or quant or q_tile != 1 or n_kv_heads < 2:
        return "per_head"
    return "folded"


def copy_size(block_size: int, n_kv_heads: int, head_dim: int,
              itemsize: int, tile_blocks: int, *, latent: bool = False,
              kv_scales: bool = False) -> dict:
    """The size of the walk's fetch, what the comm ledger's ``paged_attn``
    series carries: ``copy_bytes``, the bytes ONE DMA of the row arena
    moves (a block's K plane and V plane together, ``2 * block_size * Hkv *
    dh`` items; a latent block's rows once), and ``copies_per_tile``, the
    copies a whole kv tile of ``tile_blocks`` blocks starts and waits for
    (one a block; a quantized pool's scale planes are one more each). A
    copy costs 35-37 ns whatever it carries: under about 25-32 KiB a copy
    a tile is bound by this count, above by its bytes (PERF.md section 6,
    PR 42). A window layer's whole tile is one copy whatever this says."""
    planes = 1 if latent else 2
    return {"copy_bytes": planes * block_size * n_kv_heads * head_dim
            * itemsize,
            "copies_per_tile": tile_blocks * (2 if kv_scales else 1)}


def paged_attn_cost(B: int, max_blocks: int, block_size: int,
                    n_kv_heads: int, head_dim: int, *, n_q_heads: int,
                    itemsize: int = 2, L: int = 1,
                    q_tile: int | None = None,
                    kv_itemsize: int | None = None,
                    kv_scales: bool = False):
    """The fused kernel's cost estimate — the causal per-q-tile pass over
    the (worst-case full-table) pool bytes plus q in wire dtype and the f32
    out, delegated to ``runtime.perf_model.paged_attn_bytes`` so the
    estimate, the comm-ledger series, and the bench byte-ratio gate are one
    arithmetic. ``kv_itemsize``/``kv_scales``: quantized-pool wire bytes
    (+ per-row scale reads) — the FLOPs are unchanged because dequant
    rides the same f32 pipeline. The bytes come in copies of
    ``copy_size(...)["copy_bytes"]``, ``copies_per_tile`` a kv tile."""
    from triton_distributed_tpu.runtime import perf_model as _pm

    return common.cost_estimate(
        flops=4 * B * L * n_q_heads * max_blocks * block_size * head_dim,
        bytes_accessed=_pm.paged_attn_bytes(
            B, max_blocks, block_size, n_kv_heads, head_dim,
            n_q_heads=n_q_heads, itemsize=itemsize,
            kv_itemsize=kv_itemsize, kv_scales=kv_scales, method="fused",
            L=L, q_tile=q_tile))


def paged_attention(q, pool, block_tables, kv_lens, *,
                    q_lens=None, slot_mask=None, scale: float | None = None,
                    tile_blocks: int | None = None,
                    q_tile: int | None = None, interpret=None,
                    probes: bool = False, scales=None,
                    layer=None, v_dim: int | None = None,
                    resolved: dict | None = None,
                    window: int | None = None, aligned: bool = False,
                    summary: tuple | None = None, stats: bool = False):
    """GQA attention of an L-token query block per slot directly over a
    block-paged KV pool — decode (L=1), chunked prefill, and ragged mixed
    steps all through ONE kernel.

    WINDOW form (``window`` given): a query at position ``p`` sees the keys
    ``p - window < j <= p``. ``pool`` is a window layer's
    ring storage ``(n_layers, n_slots, 2, ring_blocks, block_size, Hkv,
    dh)`` (``serving.kv_pool``: token ``p`` of the sequence in slot ``s``
    lies in ring block ``(p // block_size) % ring_blocks`` of ``s``, its key
    in plane 0 and its value in plane 1) with ``layer``,
    and ``block_tables`` is ``(B, 1)`` int32: the SLOT each row reads. The
    walk starts at the tile that holds the oldest visible key; what lies
    behind the window costs no copy. The call is named
    ``window_paged_attention`` in a device trace. No quantized or latent
    build, no probes. ``aligned``: the window does not slide, a query sees
    ``(p // window) * window <= j <= p`` (an EVA layer's exact set; the call
    is then named ``eva_attn_window``).

    SUMMARY form (``summary=(window, rows)``, the K+V build over a stacked
    block arena whose row ``c`` stands for chunk ``c`` of ``window // rows``
    positions): a query at ``p`` sees the rows ``c < rows * (p // window)``;
    ``kv_lens`` / ``q_lens`` stay TOKEN positions. Named
    ``eva_attn_summary``. ``stats=True`` (either EVA half): returns ``(out,
    running maximum, denominator)``, the last two (B, L, Hq) float32
    (``-1e30`` and 0 for a row that saw no key), for the caller's one
    combine. No quantized, latent or probed build of either.

    LATENT form (``v_dim`` given): ``pool`` is the one
    latent arena ``(n_blocks, block_size, W)`` — stacked ``(n_layers,
    n_blocks, block_size, W)`` with ``layer`` — whose rows every query head
    shares: the keys are the whole rows, the values their first ``v_dim``
    columns, so each block is read once and used as both. q is
    ``(B, L, Hq, W)`` and the result ``(B, L, Hq, v_dim)``. The call is
    named ``latent_paged_attention`` in a device trace, the K+V build's
    ``paged_attention``.

    q:            (B, L, Hq, dh) new (rope'd) query rows per slot; the new
                  tokens' K/V are already in the pool
                  (``nn.paged_cache_update`` runs first).
    pool:         (n_blocks, 2, block_size, Hkv, dh) — ONE layer of this
                  device's kv-head shard of ``serving.kv_pool.PagedKVState``
                  ``.kv``, a block's K plane then its V plane — or the
                  whole stacked arena (n_layers, n_blocks, 2, block_size,
                  Hkv, dh) with ``layer`` naming the layer to
                  read. One kernel either way: the 5-D form is viewed as a
                  one-layer arena (a free reshape) and read at layer 0.
    layer:        () int32 (traced or static) — which layer of a stacked
                  arena this call attends over; required with 6-D pools,
                  refused with 5-D ones. The model's layer scan carries
                  the arenas whole and passes its layer index here, so no
                  per-layer slice of the pool is ever materialized.
    block_tables: (B, max_blocks) int32 — slot b's sequence occupies blocks
                  ``block_tables[b, :ceil(kv_lens[b]/block_size)]`` in
                  order; tail entries are allocator padding (never read:
                  their tiles skip the DMA).
    kv_lens:      () or (B,) int32 — valid cache length per slot INCLUDING
                  this step's live tokens (``offset + q_lens``; decode:
                  ``offset + 1``).
    q_lens:       (B,) int32 or None — live query rows per slot (ragged
                  mixed steps); None means all L rows are live. Query row
                  j of slot b sits at absolute position
                  ``kv_lens[b] - q_lens[b] + j`` and attends causally up to
                  itself; rows past ``q_lens[b]`` emit exact zeros (the
                  ``attn_with_cache`` varlen contract).
    slot_mask:    (B,) bool or None — dead slots' table rows are routed to
                  block 0 (the gather path's semantics: stale table entries
                  may point at blocks since reallocated to live sequences;
                  the mask keeps a dead slot from touching them at all).
                  The dead rows' outputs are garbage the caller discards.
    tile_blocks / q_tile: pool blocks staged per turn of the in-kernel
                  walk, and query tokens per grid step (None = autotuned /
                  heuristic, ``tuned_paged_tile``).
    scales:       (n_blocks, 2, block_size, Hkv) f32 — stacked like the
                  pool when it is — or None: per-row dequant
                  scales of a QUANTIZED pool (int8/fp8 wire dtype, written
                  by ``nn.paged_cache_update``'s quantizing append). Given,
                  each staged block's scale planes DMA with it and the kernel
                  dequantizes in VMEM before the per-head float32
                  arithmetic — storage precision is the ONLY thing that
                  changes against a float32 pool.
    probes:       device-telemetry build (a separate compile): returns
                  ``(out, probe_buf)`` with one record row per (slot,
                  q-tile, kv-tile), decoded by ``obs.kprobe`` —
                  stall attribution covers prefill steps exactly like
                  decode ones. Every grid dimension is ``arbitrary``, so
                  record ordinals are deterministic.
    resolved:     a dict to fill, or None: what this call chose statically
                  from its operands — ``tile_blocks``, ``q_tile`` and the
                  ``arithmetic`` of a staged tile (``tile_arithmetic``),
                  and the size of the fetch (``copy_size``) —
                  for a caller that keeps a record of it.

    Returns (B, L, Hq, dh) in q.dtype: streaming softmax over the same
    masked positions as the reference ``paged_gather_kv`` + dense/flash
    composition, float32 scores and accumulators. With float32 operands it
    equals the reference to summation order (1e-5, every shape); with a
    bf16 pool the decode shape's folded arithmetic adds one rounding of
    ``p`` to bf16 before the PV dot (at most 2^-8 of the softmax-weighted
    mean of |v|), as the gather path's own decode shape does; both bounds
    are held in tests/test_paged_attention.py.
    """
    B, L, Hq, dh = q.shape
    quant = scales is not None
    latent = v_dim is not None
    if (aligned and window is None) or (summary is not None and (
            window is not None or pool.ndim != 6)):
        raise ValueError("aligned goes with a window over ring storage, "
                         "summary with a stacked block arena and no window")
    if (summary is not None or stats) and (latent or quant or probes):
        raise NotImplementedError(
            "the summary build and the returned maximum and denominator are "
            "the K+V build's in the model dtype: no latent, quantized or "
            "probed build")
    if window is not None:
        if latent or quant or probes:
            raise NotImplementedError(
                "the window build walks a K+V ring in the model dtype: "
                "no latent, quantized or probed build")
        if pool.ndim != 7 or layer is None or window < 1:
            raise ValueError(
                "the window build reads ring storage (n_layers, n_slots, 2, "
                "ring_blocks, block_size, Hkv, dh) at a layer, over a "
                "window of at least one key")
        block_tables = block_tables.reshape(B, 1)
        _, n_blocks, _, ring, bs, Hkv, _ = pool.shape   # n_blocks: slots
        # The kernel reads a slot's ring as its LINES, (layers, slots, 2,
        # ring blocks * bs, Hkv, dh) (the same bytes): blocks side by side
        # are one run of lines a plane, which is what one copy can take.
        pool = pool.reshape(*pool.shape[:3], ring * bs, *pool.shape[5:])
    elif latent:
        if quant:
            raise NotImplementedError("the latent pool has no quantized "
                                      "build")
        # One key head, shared by every query head; the arena keeps its
        # rank (a unit head axis would change its tiled layout).
        if pool.ndim == 3:
            if layer is not None:
                raise ValueError("layer indexes a stacked arena; this "
                                 "latent pool is one layer")
            layer, pool = 0, pool[None]
        elif layer is None:
            raise ValueError("a stacked latent arena needs the layer to "
                             "read")
        _, n_blocks, bs, _ = pool.shape
        Hkv = 1
    elif pool.ndim == 5:
        if layer is not None:
            raise ValueError("layer indexes a stacked (n_layers, n_blocks, "
                             "...) arena; this pool is one layer")
        layer = 0
        pool = pool[None]
        if quant:
            scales = scales[None]
    elif layer is None:
        raise ValueError("a stacked (n_layers, n_blocks, ...) arena needs "
                         "the layer to read")
    if not latent and window is None:
        if pool.ndim != 6 or pool.shape[2] != 2:
            raise ValueError(
                f"a K+V pool keeps a block's K plane and V plane side by "
                f"side, (n_blocks, 2, block_size, Hkv, dh) a layer: got "
                f"{pool.shape}")
        _, n_blocks, _, bs, Hkv, _ = pool.shape
    if pool.shape[-1] != dh:
        raise ValueError(f"pool rows are {pool.shape[-1]} wide, queries "
                         f"{dh}")
    if Hq % Hkv:
        raise ValueError(f"q heads {Hq} not divisible by kv heads {Hkv}")
    if block_tables.dtype != jnp.int32:
        raise TypeError(
            f"block_tables must be int32 (got {block_tables.dtype}): the "
            f"scalar-prefetch index path does no implicit cast, and a "
            f"float/int64 table silently truncating would read the wrong "
            f"blocks")
    # The window build's table names slots, not blocks: what bounds its walk
    # is the window and the step's take, and its tiles span about a window.
    max_blocks = (block_tables.shape[1] if window is None
                  else min(ring, pl.cdiv(window + L - 1, bs) + 1))
    g = Hq // Hkv
    scale = dh ** -0.5 if scale is None else scale
    if quant:
        if scales.shape != pool.shape[:-1]:
            raise ValueError(
                f"scales shape {scales.shape} != pool rows "
                f"{pool.shape[:-1]}")
        if scales.dtype != jnp.float32:
            raise TypeError(f"scales must be f32, got {scales.dtype}")
    if slot_mask is not None:
        block_tables = jnp.where(slot_mask[:, None], block_tables, 0)
    kv_lens = jnp.broadcast_to(
        jnp.asarray(kv_lens, jnp.int32).reshape(-1), (B,))
    if q_lens is None:
        q_lens = jnp.full((B,), L, jnp.int32)
    else:
        q_lens = jnp.broadcast_to(
            jnp.asarray(q_lens, jnp.int32).reshape(-1), (B,))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    interpret = resolve_interpret(interpret)
    if tile_blocks is None or q_tile is None:
        t_cfg, qt_cfg = tuned_paged_tile(bs, Hkv, dh, max_blocks,
                                         str(pool.dtype), L=L, g=g)
        tile_blocks = t_cfg if tile_blocks is None else tile_blocks
        q_tile = qt_cfg if q_tile is None else q_tile
    tile_blocks = max(1, min(int(tile_blocks), max_blocks))
    q_tile = max(1, min(int(q_tile), L))
    n_tiles = pl.cdiv(max_blocks, tile_blocks)
    n_q_tiles = pl.cdiv(L, q_tile)
    if window is not None:
        # Logical tiles run as far as the longest sequence: the lengths
        # bound the walk, no table does.
        tile_blocks = max(1, min(tile_blocks, window // bs))
        n_tiles = jnp.iinfo(jnp.int32).max // (tile_blocks * bs)
    # Pad the table on the right so the last tile's static fetch loop can
    # index it; padded entries sit past every kv_len and never DMA.
    pad = n_tiles * tile_blocks - max_blocks if window is None else 0
    if pad:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))

    L_pad = n_q_tiles * q_tile
    arithmetic = tile_arithmetic(Hkv, q_tile, latent=latent, quant=quant)
    folded = arithmetic == "folded"
    if resolved is not None:
        resolved.update(tile_blocks=tile_blocks, q_tile=q_tile,
                        arithmetic=arithmetic, window=window,
                        **copy_size(bs, Hkv, dh, pool.dtype.itemsize,
                                    tile_blocks, latent=latent,
                                    kv_scales=quant))
    if folded:
        # One token a grid step, every query head in one operand: q as it
        # arrives, (B, L, Hq, dh) with head h * g + j in kv head h's group,
        # is already one (1, 1, Hq, dh) block a (slot, token).
        heads, rows, qh = 1, Hq, q

        def q_index(b, qt, tbl, kl, ql, ly):
            return (b, qt, 0, 0)
    else:
        heads, rows = Hkv, q_tile * g
        qh = q.reshape(B, L, Hkv, g, dh)
        if L_pad != L:
            qh = jnp.pad(qh,
                         ((0, 0), (0, L_pad - L), (0, 0), (0, 0), (0, 0)))
        # (B, Hkv, L_pad*g, dh): kv-head major so one (1, Hkv, q_tile*g,
        # dh) block serves each (slot, q-tile) grid step; row r of a block
        # is query token r // g, head group r % g — the layout the
        # in-kernel GQA causal mask assumes.
        qh = qh.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, L_pad * g, dh)

        def q_index(b, qt, tbl, kl, ql, ly):
            return (b, 0, qt, 0)

    arenas = (pool, scales) if quant else (pool,)
    planes = () if latent else (2,)       # of a block, and of a staging slot
    n_steps = B * n_q_tiles * n_tiles     # probe rows: (slot, q-tile, kv-tile)
    kernel = functools.partial(
        _paged_attn_kernel, n_arenas=len(arenas), n_tiles=n_tiles,
        tile_blocks=tile_blocks, bs=bs, n_blocks=n_blocks, scale=scale,
        n_kv=Hkv, g=g, q_tile=q_tile, n_q_tiles=n_q_tiles, v_dim=v_dim,
        probe_steps=n_steps if probes else 0, folded=folded,
        compiled=not interpret, window=window, aligned=aligned,
        summary=summary, stats=stats)
    dv = v_dim if latent else dh          # width of a value row
    out_specs = pl.BlockSpec((1, heads, rows, dv), q_index)
    out_shape = jax.ShapeDtypeStruct((*qh.shape[:3], dv), jnp.float32)
    scratch_shapes = [
        # Staging, two slots an arena (the K and the V plane in each): tile
        # j + 1 lands in one while tile j is computed from the other.
        *(pltpu.VMEM((2, *planes, tile_blocks * bs,
                      *a.shape[3 + len(planes):]), a.dtype)
          for a in arenas),
        pltpu.VMEM((heads, rows, dv), jnp.float32),  # acc
        pltpu.VMEM((heads, rows, 1), jnp.float32),   # running max
        pltpu.VMEM((heads, rows, 1), jnp.float32),   # denominator
        common.dma_sems((2, len(arenas))),          # one a (slot, arena)
        pltpu.SMEM((2,), jnp.int32),                # walk state across steps
    ]
    if probes:
        out_specs = [out_specs, _probes.out_spec()]
        scratch_shapes = [*scratch_shapes, _probes.ord_scratch()]
        out_shape = [out_shape, _probes.out_shape(n_steps)]
    if stats:
        # each query row's running maximum and denominator, as the scratch
        # holds them
        out_specs = [out_specs] + [pl.BlockSpec((1, heads, rows, 1),
                                                q_index)] * 2
        out_shape = [out_shape] + [jax.ShapeDtypeStruct(
            (*qh.shape[:3], 1), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # The block table stays the FIRST operand (the benchmark's trace
        # reader knows this kernel by it); the layer index goes last.
        num_scalar_prefetch=4,
        grid=(B, n_q_tiles),
        in_specs=[
            pl.BlockSpec((1, heads, rows, dh), q_index),
            # the arenas: manual per-(layer, block) DMA
            *(common.any_spec() for _ in arenas),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            # In grid order on one core: a step starts the next step's
            # first tile (and the probed build's ordinal counter ticks in
            # that order).
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=paged_attn_cost(
            B, max_blocks, bs, Hkv, dh, n_q_heads=Hq,
            itemsize=(q.dtype.itemsize if quant
                      else pool.dtype.itemsize),
            kv_itemsize=pool.dtype.itemsize, kv_scales=quant,
            L=L, q_tile=q_tile),
        interpret=interpret,
        # What the device trace calls the kernel's events.
        name=("latent_paged_attention" if latent else
              "eva_attn_summary" if summary is not None else
              "paged_attention" if window is None else
              "eva_attn_window" if aligned else
              "window_paged_attention"),
    )(block_tables, kv_lens, q_lens, layer, qh, *arenas)
    o = outs[0] if probes or stats else outs

    def token_major(a):
        # (B, Hkv, L_pad * g, w) -> (B, L, Hq, w): what the q layout undid
        if folded:
            return a
        w = a.shape[-1]
        a = a.reshape(B, Hkv, L_pad, g, w).transpose(0, 2, 1, 3, 4)
        return a.reshape(B, L_pad, Hq, w)[:, :L]

    o = token_major(o).astype(q.dtype)
    if stats:
        return o, token_major(outs[1])[..., 0], token_major(outs[2])[..., 0]
    if probes:
        return o, outs[1]
    return o


def paged_decode_attention(q, pool, block_tables, kv_lens, *,
                           slot_mask=None, scale: float | None = None,
                           tile_blocks: int | None = None, interpret=None,
                           probes: bool = False):
    """Single-token (L == 1) entry point over ``paged_attention`` — the
    decode hot loop's shape, kept for the callers that think in one query
    row per slot (bench's probe arm, tools/profile_decode, the autotuner
    loop, tests). q (B, Hq, dh) -> (B, Hq, dh) in q.dtype; ``kv_lens`` is
    the valid cache length INCLUDING the token just written
    (``offset + 1``). Semantics otherwise identical to ``paged_attention``
    with L = 1 (one query tile, causal mask degenerate to
    ``pos < kv_len``)."""
    B, Hq, dh = q.shape
    out = paged_attention(q[:, None], pool, block_tables, kv_lens,
                          slot_mask=slot_mask, scale=scale,
                          tile_blocks=tile_blocks, q_tile=1,
                          interpret=interpret, probes=probes)
    if probes:
        o, pbuf = out
        return o.reshape(B, Hq, dh), pbuf
    return out.reshape(B, Hq, dh)


# ---------------------------------------------------------------------------
# Analyzer registration (analysis/registry.py).
#
# Single-device kernel (ranks=1; the sweep's world sizes are slot counts
# elsewhere and ignored here, like ar.oneshot_loopback). The build accepts
# the autotuner's config as kwargs — ``tile_blocks``/``q_tile`` plus the
# live pool geometry — which is what lets
# ``analysis.resources.check_resources`` evaluate a candidate config's VMEM
# staging footprint, tile legality, and grid×block coverage of the output
# BEFORE the tuner ever compiles it (``tuned_paged_tile`` wires it in as
# the ContextualAutotuner pruner). ``paged.decode`` is the L = 1 shape,
# ``paged.prefill`` the L > 1 / multi-q-tile one; both carry ``+probe``
# variants proving the instrumented choreography stays as clean as the
# base.
# ---------------------------------------------------------------------------

from triton_distributed_tpu.analysis import registry as _comm  # noqa: E402
import numpy as _np  # noqa: E402


def _paged_trace_body(*refs, **kw):
    # Apply the (1, Hkv, q_tile*g, dh) q/o BlockSpec windows by hand — the
    # tracer passes whole buffers, the real grid_spec passes per-(slot,
    # q-tile) blocks. Every build's refs arrive in ``pallas_call`` order,
    # so the one body serves them all.
    b = int(pl.program_id(0))
    qt = int(pl.program_id(1))
    rows = kw["q_tile"] * kw["g"]
    refs = list(refs)
    for at in (4, 5 + kw["n_arenas"]):                  # q, o
        if kw["folded"]:            # (B, L, Hq, dh): one token a window
            refs[at] = refs[at].at[pl.ds(b, 1), pl.ds(qt, 1)]
        else:
            refs[at] = refs[at].at[pl.ds(b, 1), :, pl.ds(qt * rows, rows)]
    _paged_attn_kernel(*refs, **kw)


def _paged_spec(world: int, *, tile_blocks: int = 2, bs: int = 16,
                n_kv: int = 2, g: int = 2, dh: int = 128,
                max_blocks: int = 4, dtype: str = "float32", L: int = 1,
                q_tile: int = 1, kvq: bool = False,
                v_dim: int | None = None,
                window: int | None = None,
                kv_len=None) -> "_comm.TraceSpec":
    B = 2
    dt = _np.dtype(jnp.dtype(dtype))
    n_blocks = B * max_blocks
    n_tiles = -(-max_blocks // tile_blocks)
    n_q_tiles = -(-L // q_tile)
    tbl_w = n_tiles * tile_blocks     # host-side right padding, never read
    # Queries/outputs stay in the COMPUTE dtype on a quantized pool (the
    # wire dtype only ever holds stored KV rows).
    qdt = _np.dtype(_np.float32) if kvq else dt
    latent = v_dim is not None
    # The arenas, (name, row shape, dtype), in operand order. Two layers,
    # the second one read: the layer index is live in every DMA source the
    # analyzer sees.
    if latent:
        n_kv = 1
        arenas = [("kvp", (dh,), dt)]
    else:
        arenas = [("kvp", (n_kv, dh), dt)]
    if kvq:
        arenas += [("ksp", (n_kv,), _np.float32)]
    # a block's (or, in a ring, a slot's) planes: K then V; a latent row
    # is both and has none
    planes = () if latent else (2,)
    # The q/o blocks and the accumulators, as the wrapper lays them out for
    # the arithmetic this shape takes.
    folded = tile_arithmetic(n_kv, q_tile, latent=latent,
                             quant=kvq) == "folded"
    heads, rows = (1, n_kv * g) if folded else (n_kv, q_tile * g)
    qo = ((B, n_q_tiles, rows) if folded
          else (B, n_kv, n_q_tiles * rows))

    def tables(r, w):
        t = _np.zeros((B, tbl_w), _np.int32)
        t[:, :max_blocks] = _np.arange(n_blocks, dtype=_np.int32).reshape(
            B, max_blocks)
        return t

    # The window build: ring storage (layers, slots, ring blocks, ...) read
    # by slot, the table one slot id a row; ``max_blocks`` is the ring.
    pool = (2, n_blocks, *planes, bs)
    if window is not None:
        pool, tbl_w, n_blocks = (2, B, 2, max_blocks * bs), 1, B
        n_tiles = 1 << 20

        def tables(r, w):                                   # noqa: F811
            return _np.arange(B, dtype=_np.int32).reshape(B, 1)

    return _comm.TraceSpec(
        body=_paged_trace_body,
        ranks=1,
        grid=(B, n_q_tiles),
        args=[
            _comm.Buf("tbl", (B, tbl_w), _np.int32, space="smem",
                      init=tables),
            # Contexts of the whole table unless given (one, or one a slot:
            # the window build's may run past its ring).
            _comm.Buf("kvlen", (B,), _np.int32, space="smem",
                      init=lambda r, w: _np.broadcast_to(_np.asarray(
                          max_blocks * bs if kv_len is None else kv_len,
                          _np.int32), (B,)).copy()),
            _comm.Buf("qlen", (B,), _np.int32, space="smem",
                      init=lambda r, w: _np.full((B,), L, _np.int32)),
            _comm.Buf("layer", (1,), _np.int32, space="smem",
                      init=lambda r, w: _np.ones((1,), _np.int32)),
            _comm.Buf("q", (*qo, dh), qdt),
            *(_comm.Buf(name, (*pool, *row), adt)
              for name, row, adt in arenas),
            # One (1, Hkv, q_tile*g, dh) window of q and o is VMEM-resident
            # per grid step; billing the full B=2 buffers stays within a
            # few KiB of that and keeps the declaration honest.
            _comm.Buf("o", (*qo, v_dim or dh), _np.float32, space="vmem",
                      covered=True),
            # Staging: two slots an arena, as the kernel allocates them.
            *(_comm.Buf(f"{name}_stage",
                        (2, *planes, tile_blocks * bs, *row), adt,
                        space="vmem")
              for name, row, adt in arenas),
            _comm.Buf("acc", (heads, rows, v_dim or dh), _np.float32,
                      space="vmem"),
            _comm.Buf("m_run", (heads, rows, 1), _np.float32, space="vmem"),
            _comm.Buf("l_run", (heads, rows, 1), _np.float32, space="vmem"),
            _comm.Sem("sems", (2, len(arenas))),
            _comm.Buf("walk", (2,), _np.int32, space="smem"),
        ],
        kwargs=dict(n_arenas=len(arenas), n_tiles=n_tiles,
                    tile_blocks=tile_blocks, bs=bs, n_blocks=n_blocks,
                    scale=1.0, n_kv=n_kv, g=g, q_tile=q_tile,
                    n_q_tiles=n_q_tiles, v_dim=v_dim, folded=folded,
                    window=window),
    )


_comm.register("paged.decode")(_paged_spec)


@_comm.register("paged.decode.kvq")
def _paged_spec_kvq(world: int, *, dtype: str = "int8",
                    **kw) -> "_comm.TraceSpec":
    """The QUANTIZED pool decode shape: int8 (or fp8) wire-dtype K/V
    arena plus the per-row f32 scale arena and its VMEM staging —
    proving the dequant-in-staging choreography (one extra DMA on
    semaphore 1 per staged block) and the shrunken wire footprint the
    autotuner's bigger quantized tiles rely on."""
    return _paged_spec(world, dtype=dtype, kvq=True, **kw)


@_comm.register("paged.prefill")
def _paged_spec_prefill(world: int, *, L: int = 8, q_tile: int = 4,
                        **kw) -> "_comm.TraceSpec":
    """The L > 1 (chunked-prefill / mixed step) shape: two query tiles by
    default so the (B, n_q_tiles) grid, the per-tile causal frontier, and
    the walk that stops at it are all exercised; same config kwargs as
    ``paged.decode`` plus (L, q_tile) — the space the (tile_blocks, q_tile)
    autotuner pruner feeds."""
    return _paged_spec(world, L=L, q_tile=q_tile, **kw)


@_comm.register("paged.prefill.kvq")
def _paged_spec_prefill_kvq(world: int, *, L: int = 8, q_tile: int = 4,
                            dtype: str = "int8", **kw) -> "_comm.TraceSpec":
    """Quantized chunked-prefill/mixed shape: the ``paged.prefill`` grid
    over int8/fp8 wire pools + scale staging (see ``paged.decode.kvq``)."""
    return _paged_spec(world, L=L, q_tile=q_tile, dtype=dtype, kvq=True,
                       **kw)


@_comm.register("paged.latent")
def _paged_spec_latent(world: int, *, L: int = 8, q_tile: int = 4,
                       g: int = 4, dh: int = 256, v_dim: int = 128,
                       **kw) -> "_comm.TraceSpec":
    """The LATENT build (one arena of rows every query head shares, read
    once and used as keys and as values), chunk shape: the same walk with
    one copy a block."""
    return _paged_spec(world, L=L, q_tile=q_tile, g=g, dh=dh, v_dim=v_dim,
                       **kw)


@_comm.register("paged.window")
def _paged_spec_window(world: int, *, window: int = 24, bs: int = 8,
                       tile_blocks: int = 2, max_blocks: int = 6,
                       **kw) -> "_comm.TraceSpec":
    """The WINDOW build (ring storage read by slot, the walk started at the
    window's first tile; ``max_blocks`` is the ring's blocks), decode shape:
    contexts of the whole ring, so the first tile lies behind the window
    and is neither copied nor waited for, the second is ragged at its start
    (a copy a live block) and the third whole: ONE copy, both planes.
    ``kv_len`` (one, or one a slot) takes the contexts round the ring."""
    return _paged_spec(world, window=window, bs=bs, tile_blocks=tile_blocks,
                       max_blocks=max_blocks, **kw)


def _register_paged_probe(base_name: str) -> None:
    # The real probed build places probe_buf right after the o output and
    # probe_ord after the scratch refs — mirrored here so the analyzer
    # proves the choreography the hardware actually runs.
    @_comm.register(f"{base_name}+probe")
    def _build(world: int, _base=base_name, **cfg) -> "_comm.TraceSpec":
        spec = _comm.get(_base).build(world, **cfg)
        n_steps = (spec.grid[0] * spec.kwargs["n_q_tiles"]
                   * spec.kwargs["n_tiles"])
        args = list(spec.args)
        args.insert(6 + spec.kwargs["n_arenas"], _comm.Buf(
            "probe_buf", (_probes.n_rows(n_steps), _probes.N_FIELDS),
            _np.int32, space="smem"))
        args.append(_comm.Buf("probe_ord", (1,), _np.int32, space="smem"))
        return _comm.TraceSpec(body=spec.body, args=args, grid=spec.grid,
                               kwargs=dict(spec.kwargs, probe_steps=n_steps),
                               ranks=spec.ranks, axes=spec.axes)


for _base in ("paged.decode", "paged.prefill", "paged.decode.kvq",
              "paged.prefill.kvq"):
    _register_paged_probe(_base)
del _base
