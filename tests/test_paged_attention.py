"""Fused paged-attention kernel, any query length
(kernels/paged_attention.py).

The load-bearing guarantees:
  1. the fused in-kernel block walk is numerically identical (interpret
     mode, f32) to the reference gather-then-dense composition across block
     sizes (including a misaligned 128), query lengths (decode L=1,
     chunked prefill, ragged mixed), ragged per-slot kv_lens, shuffled
     block tables, dead slots, GQA ratios, q-tile splits (causal-boundary
     straddles included), and every feasible kv tile size;
  2. ``nn.paged_attn_with_cache`` routes EVERY step — decode, prefill, and
     ragged mixed — to the fused kernel (the automatic gather fallback is
     retired; ``paged_attn="gather"`` is the explicit oracle), records a
     method-labelled (``fused_decode`` / ``fused_prefill`` / ``gather``)
     ``paged_attn`` comm-ledger series, and rejects bad flags/dtypes;
  3. end to end, a ``BatchEngine(paged_attn="fused")`` emits bit-identical
     greedy tokens to both the gather engine and the single-sequence golden
     Engine over >= 64 decode steps with pool churn and preemption, still
     with ONE compile per step shape;
  4. the fused path's byte accounting (perf_model / cost_estimate) is
     <= ~55% of the gather path's on decode AND prefill/mixed shapes, and
     the perf gate treats the ratio as lower-is-better.

The chunk-shape matrix of guarantee 1 (L > 1 against the gather reference,
the ragged mixed step, the causal-boundary straddle) lives in
``tests/test_paged_attention_chunk.py``: a file of its own so that
``--dist loadfile`` hands the two halves to two workers (they share no
fixture).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.kernels.paged_attention import (
    _feasible_qtiles,
    _feasible_tiles,
    paged_attention,
    paged_attn_cost,
    paged_decode_attention,
    tuned_paged_tile,
)
from triton_distributed_tpu.kernels.sp_attention import paged_gather_kv
from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.obs import comm_ledger, roofline
from triton_distributed_tpu.obs.perfdb import metric_direction
from triton_distributed_tpu.runtime import perf_model as pm
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import BatchEngine, KVPool

from conftest import pair_planes


def _ref_attn(q, kp, vp, tables, kv_lens, slot_mask=None):
    """Gather + masked dense softmax — the reference composition."""
    B, Hq, dh = q.shape
    Hkv = kp.shape[2]
    g = Hq // Hkv
    kv = paged_gather_kv(kp, tables, slot_mask=slot_mask)
    vv = paged_gather_kv(vp, tables, slot_mask=slot_mask)
    S = kv.shape[1]
    qr = q.reshape(B, Hkv, g, dh).astype(jnp.float32)
    scores = (jnp.einsum("bhgd,bshd->bhgs", qr, kv.astype(jnp.float32))
              * dh ** -0.5)
    mask = jnp.arange(S)[None, :] < kv_lens[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, vv.astype(jnp.float32))
    return out.reshape(B, Hq, dh).astype(q.dtype)


def _ref_attn_chunk(q, kp, vp, tables, kv_lens, q_lens):
    """L-token causal reference: gather + per-row masked dense softmax.
    Query row j of slot b sits at position kv_lens[b] - q_lens[b] + j;
    rows past q_lens[b] are zeros (the varlen contract)."""
    B, L, Hq, dh = q.shape
    Hkv = kp.shape[2]
    g = Hq // Hkv
    kg = np.asarray(paged_gather_kv(kp, tables), np.float32)
    vg = np.asarray(paged_gather_kv(vp, tables), np.float32)
    qn = np.asarray(q, np.float32)
    kv_lens = np.asarray(kv_lens)
    q_lens = np.asarray(q_lens)
    out = np.zeros((B, L, Hq, vg.shape[-1]), np.float32)
    for b in range(B):
        for j in range(L):
            if j >= q_lens[b]:
                continue
            hi = kv_lens[b] - q_lens[b] + j + 1        # exclusive causal end
            for hq in range(Hq):
                h = hq // g
                s = (qn[b, j, hq] @ kg[b, :hi, h].T) * dh ** -0.5
                p = np.exp(s - s.max())
                out[b, j, hq] = (p / p.sum()) @ vg[b, :hi, h]
    return out.astype(np.asarray(q).dtype)


def _pool_case(rng, B, bs, Hkv, g, dh, max_blocks, ragged=True):
    Hq = Hkv * g
    n_blocks = B * max_blocks + 3
    kp = jnp.asarray(rng.normal(size=(n_blocks, bs, Hkv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_blocks, bs, Hkv, dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, Hq, dh)), jnp.float32)
    # shuffled, non-identity table: slot order != pool order
    tables = jnp.asarray(
        rng.permutation(n_blocks)[:B * max_blocks].reshape(B, max_blocks),
        jnp.int32)
    if ragged:
        kv_lens = jnp.asarray(
            rng.integers(1, max_blocks * bs + 1, size=B), jnp.int32)
    else:
        kv_lens = jnp.full((B,), max_blocks * bs, jnp.int32)
    return q, kp, vp, tables, kv_lens


# -- 1. kernel vs gather reference ------------------------------------------

@pytest.mark.parametrize("bs,max_blocks", [(8, 4), (16, 3), (128, 2)])
@pytest.mark.parametrize("g", [1, 4])
def test_fused_matches_gather_reference(rng, bs, max_blocks, g):
    B, Hkv, dh = 4, 2, 16
    q, kp, vp, tables, kv_lens = _pool_case(rng, B, bs, Hkv, g, dh,
                                            max_blocks)
    if bs == 128:
        # the misaligned case: lengths that end mid-block / mid-lane-tile
        kv_lens = jnp.asarray([1, 100, 129, 2 * 128 - 1], jnp.int32)
    ref = _ref_attn(q, kp, vp, tables, kv_lens)
    for tile in (None, 1, max_blocks):
        out = paged_decode_attention(q, pair_planes(kp, vp), tables, kv_lens,
                                     tile_blocks=tile, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"tile_blocks={tile}")


def test_fused_dead_slots_and_scalar_kvlen(rng):
    B, bs, Hkv, g, dh, max_blocks = 4, 8, 2, 2, 16, 4
    q, kp, vp, tables, kv_lens = _pool_case(rng, B, bs, Hkv, g, dh,
                                            max_blocks)
    slot_mask = jnp.asarray([True, False, True, False])
    out = paged_decode_attention(q, pair_planes(kp, vp), tables, kv_lens,
                                 slot_mask=slot_mask, interpret=True)
    ref = _ref_attn(q, kp, vp, tables, kv_lens, slot_mask=slot_mask)
    live = np.asarray(slot_mask)
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live], atol=1e-5)
    assert np.isfinite(np.asarray(out)).all(), \
        "dead slots must emit finite garbage, not NaN"
    # scalar kv_len broadcasts over the batch
    out_s = paged_decode_attention(q, pair_planes(kp, vp), tables, 7,
                                   interpret=True)
    ref_s = _ref_attn(q, kp, vp, tables, jnp.full((B,), 7, jnp.int32))
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(ref_s),
                               atol=1e-5)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("shape", ["decode", "chunk"])
def test_stacked_arena_layer_equals_per_layer_call(rng, shape, kv_dtype):
    """The model's layer scan carries the STACKED arena and hands the
    kernel a layer index: ``paged_attention(..., layer=li)`` over
    (n_layers, n_blocks, 2, bs, Hkv, dh) must equal the per-layer call on
    ``pool[li]`` bit for bit, for a traced index as for a static one —
    decode shape (L=1) and chunk shape (L>1, ragged q_lens), bf16 and
    quantized int8 pools (the scale arena stacked the same way)."""
    n_layers, B, bs, Hkv, g, dh, max_blocks = 3, 3, 8, 2, 2, 16, 4
    n_blocks = B * max_blocks + 2
    L = 1 if shape == "decode" else 6
    raw = [rng.normal(size=(n_layers, n_blocks, bs, Hkv, dh)) for _ in "kv"]
    quant = kv_dtype == "int8"
    if quant:
        (kp, ks), (vp, vs) = (nn.quantize_kv_rows(jnp.asarray(x), jnp.int8)
                              for x in raw)
    else:
        kp, vp = (jnp.asarray(x, jnp.bfloat16) for x in raw)
    q_dtype = jnp.float32 if quant else jnp.bfloat16

    def scales(li=None):
        if not quant:
            return {}
        return dict(scales=pair_planes(ks, vs, 1) if li is None
                    else pair_planes(ks[li], vs[li], 1))

    q = jnp.asarray(rng.normal(size=(B, L, Hkv * g, dh)), q_dtype)
    tables = jnp.asarray(
        rng.permutation(n_blocks)[:B * max_blocks].reshape(B, max_blocks),
        jnp.int32)
    q_lens = jnp.asarray([L, max(1, L - 2), max(1, L // 2)], jnp.int32)
    kv_lens = jnp.asarray([max_blocks * bs, 13, 9], jnp.int32) + q_lens - L
    kw = dict(q_lens=q_lens, q_tile=min(L, 4), tile_blocks=2,
              interpret=True)

    @jax.jit
    def traced(li):
        return paged_attention(q, pair_planes(kp, vp), tables, kv_lens,
                               layer=li, **scales(), **kw)

    outs = []
    for li in range(n_layers):
        per_layer = paged_attention(q, pair_planes(kp[li], vp[li]), tables,
                                    kv_lens, **scales(li), **kw)
        stacked = paged_attention(q, pair_planes(kp, vp), tables, kv_lens,
                                  layer=li, **scales(), **kw)
        np.testing.assert_array_equal(np.asarray(stacked, np.float32),
                                      np.asarray(per_layer, np.float32))
        np.testing.assert_array_equal(
            np.asarray(traced(jnp.int32(li)), np.float32),
            np.asarray(per_layer, np.float32))
        outs.append(np.asarray(per_layer, np.float32))
    # distinct data per layer: the index is live, not ignored
    assert not np.array_equal(outs[0], outs[1])
    with pytest.raises(ValueError, match="layer"):
        paged_attention(q, pair_planes(kp, vp), tables, kv_lens, **scales(),
                        **kw)
    with pytest.raises(ValueError, match="layer"):
        paged_attention(q, pair_planes(kp[0], vp[0]), tables, kv_lens,
                        layer=0, **scales(0), **kw)


# -- the fetch pipeline: two staging slots, a walk as long as the slot -------

def _walk_case(rng, shape, tile_blocks, poison=False):
    """One batch that holds every length the walk has an edge at — 1 row,
    one block, exactly one tile, one tile + 1 row, an odd number of tiles,
    the full table (its last tile ragged, the table padded) — and a dead
    slot between live ones, over a shuffled table. ``shape``: ``decode``
    (L = 1), ``chunk`` (ragged ``q_lens``, two query tiles), ``latent``
    (one arena, chunk shape), ``bf16-8x2`` / ``bf16-4x8`` / ``bf16-4x7``
    (the decode shape over a bf16 pool at ``Hkv`` 8, ``g`` 2, at ``Hkv`` 4,
    ``g`` 8 and at a group that is no power of two, 28 query rows in the
    folded operand: the FOLDED arithmetic with bf16 operands, as the chip
    runs it), ``chunk-g7`` (the chunk shape at a group of 7). Returns the
    call's arguments, the oracle, the live mask and the tolerance. ``poison``
    writes NaN over every pool row that no live slot owns, so a prefetch
    that stages what the mask does not scrub shows."""
    bs, max_blocks, n_layers, li = 8, 7, 2, 1
    span = tile_blocks * bs
    latent = shape == "latent"
    bf16 = shape.startswith("bf16-")
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    if bf16:
        Hkv, g = (int(x) for x in shape[len("bf16-"):].split("x"))
        dh, v_dim = 16, None
    else:
        Hkv, g, dh, v_dim = (1, 4, 32, 16) if latent else (
            2, 7 if shape == "chunk-g7" else 2, 16, None)
    L = 6 if shape in ("chunk", "latent", "chunk-g7") else 1
    kv = [1, bs, span, span + 1, min(3 * span, max_blocks * bs),
          max_blocks * bs, 5 * bs, 2 * bs + 3]
    slot_mask = np.array([True] * 6 + [False, True])
    B = len(kv)
    q_lens = np.minimum(np.array([1, L, 1, L // 2, L, 1, L, 2])[:B], L)
    kv_lens = np.maximum(np.array(kv), q_lens)
    n_blocks = B * max_blocks + 5
    # the pool's own values (rounded to its dtype) are what the oracle reads
    rows, vrows = (np.array(jnp.asarray(
        rng.normal(size=(n_layers, n_blocks, bs, Hkv, dh)), dtype),
        np.float32) for _ in "kv")
    tables = rng.permutation(n_blocks)[:B * max_blocks].reshape(
        B, max_blocks).astype(np.int32)
    if poison:
        live = np.zeros((n_blocks, bs), bool)
        for b in np.flatnonzero(slot_mask):
            for t in range(int(kv_lens[b])):
                live[tables[b, t // bs], t % bs] = True
        # a dead slot walks block 0 (``slot_mask``): its output is thrown
        # away, NaN and all
        rows[:, ~live] = np.nan
        vrows[:, ~live] = np.nan
    q = jnp.asarray(rng.normal(size=(B, L, Hkv * g, dh)), dtype)
    kp, vp = jnp.asarray(rows, dtype), jnp.asarray(vrows, dtype)
    if latent:
        pool = kp[:, :, :, 0]
        ref_k, ref_v = rows[li], rows[li][..., :v_dim]
    else:
        pool = pair_planes(kp, vp)
        ref_k, ref_v = rows[li], vrows[li]

    def oracle(values):
        return _ref_attn_chunk(q.astype(jnp.float32),
                               jnp.asarray(ref_k, jnp.float32),
                               jnp.asarray(values, jnp.float32),
                               jnp.asarray(tables), kv_lens, q_lens)

    ref = oracle(ref_v)
    # float32 operands: summation order. bf16 operands: q . k is a sum of
    # exact products either way, so what the folded arithmetic adds is ONE
    # rounding of p to bf16 before the PV dot: a relative 2^-8 on each
    # weight (bf16 keeps 8 significant bits; the denominator sums the
    # unrounded p), hence at most 2^-8 of the softmax-weighted mean of |v|.
    # The output's own rounding to bf16 is 2^-8 of itself. Nothing wider.
    tol = 1e-5 + 1e-5 * np.abs(ref)
    if bf16:
        tol += 2.0 ** -8 * (oracle(np.abs(ref_v)) + np.abs(ref))
    kw = dict(q_lens=jnp.asarray(q_lens, jnp.int32),
              slot_mask=jnp.asarray(slot_mask), tile_blocks=tile_blocks,
              q_tile=min(L, 4), interpret=True, v_dim=v_dim)
    args = (q, pool, jnp.asarray(tables), jnp.asarray(kv_lens, jnp.int32))
    return args, kw, li, ref, slot_mask, tol


def _assert_within(out, ref, tol):
    """|out - ref| <= tol elementwise (``tol`` an array: ``atol`` and
    ``rtol`` 1e-5 for float32 operands, plus the stated bf16 bound of
    ``_walk_case`` otherwise)."""
    err = np.abs(np.asarray(out, np.float32) - ref)
    worst = np.unravel_index(np.argmax(err - tol), err.shape)
    assert (err <= tol).all(), (
        f"at {worst}: |out - ref| = {err[worst]:.3g} > {tol[worst]:.3g}")


WALK_SHAPES = ["decode", "chunk", "latent", "bf16-8x2", "bf16-4x8"]


# A group of 7 (28 query heads over 4 key heads: the first served group that
# is no power of two), the decode shape's folded operand and the chunk shape.
GROUP_OF_7 = [("bf16-4x7", 2), ("chunk-g7", 2)]


@pytest.mark.parametrize(
    "shape,tile_blocks",
    [(s, t) for s in WALK_SHAPES for t in (1, 2, 3)] + GROUP_OF_7)
def test_pipelined_walk_matches_gather_reference(rng, shape, tile_blocks):
    """The walk's trip count is each slot's own: every edge length in one
    batch, dead slot included, through the stacked arena with a TRACED
    layer index, equals the gather oracle — for the K+V build in the decode
    and the chunk shape, for the latent build, and for the folded
    arithmetic over a bf16 pool within the rounding of ``p``."""
    (q, pool, tables, kv_lens), kw, li, ref, live, tol = _walk_case(
        rng, shape, tile_blocks)

    @jax.jit
    def traced(layer):
        return paged_attention(q, pool, tables, kv_lens, layer=layer, **kw)

    out = np.asarray(traced(jnp.int32(li)), np.float32)
    _assert_within(out[live], ref[live], tol[live])
    assert np.isfinite(out).all(), \
        "dead slots must emit finite garbage, not NaN"
    static = paged_attention(q, pool, tables, kv_lens, layer=li, **kw)
    np.testing.assert_array_equal(np.asarray(static, np.float32), out)


@pytest.mark.parametrize("shape", WALK_SHAPES)
def test_prefetch_stages_nothing_the_mask_does_not_scrub(rng, shape):
    """NaN in every pool block that no live slot owns and in every row past
    a slot's frontier: whatever either staging slot held — this tile's dead
    rows, the last tile's leftovers, a neighbour's blocks — the output of
    the live slots is finite and the oracle's."""
    (q, pool, tables, kv_lens), kw, li, ref, live, tol = _walk_case(
        rng, shape, 2, poison=True)
    out = np.asarray(paged_attention(q, pool, tables, kv_lens, layer=li,
                                     **kw), np.float32)
    assert np.isfinite(out[live]).all()
    _assert_within(out[live], ref[live], tol[live])


@pytest.mark.parametrize("kernel", ["paged.decode", "paged.prefill",
                                    "paged.latent"])
def test_fetch_pipeline_structure(kernel):
    """The analyzer's event log of the walk (three kv tiles a grid step):
    in every tile all copies start before the first is waited for; the
    next tile's copies — the next grid step's first tile after a step's
    last — start before this tile's staging is read; every started copy is
    waited for before the kernel ends; and a semaphore serves one tile at
    a time."""
    from triton_distributed_tpu.analysis import events
    from triton_distributed_tpu.analysis import registry as reg

    n_tiles = 3
    spec = reg.get(kernel).build(1, tile_blocks=2, max_blocks=2 * n_tiles)
    log = events.trace_kernel(spec, 1).logs[0]
    stage = {a.name for a in spec.args if a.name.endswith("_stage")}
    slot_bytes = {a.name: np.zeros(a.shape[1:], a.dtype).nbytes
                  for a in spec.args if a.name in stage}

    # Tiles in program order: a tile is opened by its first start; starts
    # and waits name its slot through the semaphore ("sems", slot, arena).
    tiles, by_slot, count = [], {}, {}
    for e in log:
        if e.kind == "inc":
            slot = e.sem[1]
            t = by_slot.get(slot)
            if t is None or t["waits"]:
                assert not count.get(e.sem), \
                    f"{e.sem} restarted with {count[e.sem]} bytes in flight"
                t = dict(slot=slot, starts=[], waits=[], reads=[])
                by_slot[slot] = t
                tiles.append(t)
            t["starts"].append(e.seq)
            count[e.sem] = count.get(e.sem, 0) + e.amount
        elif e.kind == "wait":
            t = by_slot[e.sem[1]]
            t["waits"].append(e.seq)
            count[e.sem] -= e.amount
            assert count[e.sem] >= 0, f"{e.sem} waited for more than started"
        elif e.kind == "read" and e.buf in stage and e.dma is None:
            by_slot[e.lo // slot_bytes[e.buf]]["reads"].append(e.seq)
    assert len(tiles) == int(np.prod(spec.grid)) * n_tiles
    assert not any(count.values()), f"copies never waited for: {count}"
    for n, t in enumerate(tiles):
        assert len(t["starts"]) == len(t["waits"])
        assert max(t["starts"]) < min(t["waits"]) < min(t["reads"])
        assert max(t["waits"]) < min(t["reads"])
        for nxt in tiles[n + 1:n + 2]:
            assert nxt["slot"] != t["slot"]
            assert max(nxt["starts"]) < min(t["reads"]), \
                f"tile {n + 1}'s copies must fly while tile {n} is computed"


def test_fused_rejects_non_int32_tables(rng):
    q, kp, vp, tables, kv_lens = _pool_case(rng, 2, 8, 2, 1, 16, 2)
    with pytest.raises(TypeError, match="int32"):
        paged_decode_attention(q, pair_planes(kp, vp),
                               tables.astype(jnp.float32), kv_lens,
                               interpret=True)
    with pytest.raises(TypeError, match="int32"):
        paged_gather_kv(kp, tables.astype(jnp.float32))


def test_gather_clips_out_of_range_blocks(rng):
    _, kp, _, _, _ = _pool_case(rng, 2, 8, 2, 1, 16, 2)
    tables = jnp.asarray([[0, 10 ** 6], [-5, 1]], jnp.int32)
    g = paged_gather_kv(kp, tables)                  # mode="clip": no crash
    assert g.shape == (2, 2 * kp.shape[1], *kp.shape[2:])
    assert np.isfinite(np.asarray(g)).all()


# -- autotuner tile config ---------------------------------------------------

def test_feasible_tiles_vmem_bounded():
    tiles = _feasible_tiles(16, 8, 128, 64, 2)
    per_block = 2 * 2 * 16 * 8 * 128 * 2     # K and V, two staging slots
    from triton_distributed_tpu.kernels import common
    assert all(t * per_block <= common.VMEM_STAGE_BUDGET for t in tiles)
    assert max(tiles) * per_block == common.VMEM_STAGE_BUDGET   # 32 blocks
    assert all(t <= 64 for t in tiles)
    # heuristic default first, staging <= 512 cache rows
    assert tiles[0] * 16 <= 512
    # degenerate geometry still yields a tile
    assert _feasible_tiles(8192, 64, 256, 1, 4) == [1]


def test_tuned_paged_tile_deterministic_off_tpu():
    a = tuned_paged_tile(16, 2, 64, 8, "float32")
    assert a == tuned_paged_tile(16, 2, 64, 8, "float32")
    tile, q_tile = a
    assert tile in _feasible_tiles(16, 2, 64, 8, 4)
    assert q_tile == 1                       # decode: single query row
    # L > 1 gets its own cache key and a q tile covering the chunk when
    # the staging buffers fit — one pool pass instead of one per q tile.
    b = tuned_paged_tile(16, 2, 64, 8, "float32", L=8, g=2)
    assert b == tuned_paged_tile(16, 2, 64, 8, "float32", L=8, g=2)
    assert b[1] in _feasible_qtiles(8, 2, 2, 64, 4)
    assert b[1] == 8
    assert b != a or b[1] == 1               # distinct keys, no bleed-through


def test_feasible_qtiles_vmem_bounded():
    from triton_distributed_tpu.kernels import common
    qts = _feasible_qtiles(64, 8, 2, 128, 2)
    per_tok = 8 * 2 * 128 * (8 + 2)          # acc f32 + m/l f32 + q + out
    assert qts and all(t * per_tok <= common.VMEM_STAGE_BUDGET for t in qts)
    assert all(1 <= t <= 64 for t in qts)
    assert _feasible_qtiles(1, 8, 2, 128, 2) == [1]
    # huge heads: still returns a tile (degenerate geometry -> 1)
    assert 1 in _feasible_qtiles(64, 64, 8, 256, 4) or \
        _feasible_qtiles(64, 64, 8, 256, 4)


# -- 2. layer entry point routing -------------------------------------------

def test_paged_attn_with_cache_fused_equals_gather(rng):
    B, bs, Hkv, g, dh, max_blocks = 4, 8, 2, 2, 16, 4
    q3, kp, vp, tables, kv_lens = _pool_case(rng, B, bs, Hkv, g, dh,
                                             max_blocks)
    q = q3[:, None]                                  # (B, 1, Hq, dh)
    offset = kv_lens - 1                             # decode: len = off + 1
    slot_mask = jnp.asarray([True, True, True, False])
    outs = {}
    with comm_ledger.ledger(reset_first=True):
        for method in ("fused", "gather"):
            outs[method] = nn.paged_attn_with_cache(
                q, pair_planes(kp, vp), tables, offset, scale=dh ** -0.5,
                slot_mask=slot_mask, paged_attn=method)
        snap = comm_ledger.snapshot()
    np.testing.assert_allclose(np.asarray(outs["fused"])[:3],
                               np.asarray(outs["gather"])[:3], atol=1e-5)
    # method-labelled ledger series with the analytic byte accounting
    series = {d["method"]: d for d in snap.values()
              if isinstance(d, dict) and d.get("collective") == "paged_attn"}
    assert set(series) == {"fused_decode", "gather"}
    for method, entry in series.items():
        expect = pm.paged_attn_bytes(B, max_blocks, bs, Hkv, dh,
                                     n_q_heads=Hkv * g,
                                     itemsize=kp.dtype.itemsize,
                                     method=method)
        assert entry["bytes_total"] == expect, method
    # the size of the walk's fetch rides the fused series: ONE copy carries
    # a block's K plane and V plane, and a whole tile starts one a block
    fused = series["fused_decode"]
    assert fused["copy_bytes"] == 2 * bs * Hkv * dh * kp.dtype.itemsize
    assert fused["copies_per_tile"] == min(
        max_blocks, tuned_paged_tile(bs, Hkv, dh, max_blocks,
                                     str(kp.dtype), L=1, g=g)[0])
    assert "copy_bytes" not in series["gather"]


@pytest.mark.parametrize("Hkv", [2, 8])
@pytest.mark.parametrize("build", ["decode", "chunk", "window"])
def test_one_copy_a_block_over_the_paired_pool_equals_the_gather_oracle(
        rng, build, Hkv):
    """The pool's ONE arena (a block's K plane and V plane side by side; a
    ring's planes outside its lines), walked with one copy a block: at the
    decode shape, the chunk shape (ragged ``seq_lens``) and the window
    build, at two key heads (the four-chip cell's chip: 16 KB a copy) and
    at eight, the fused kernel reads what the gather oracle reads, and a
    head that projects either onto a vocabulary picks the same tokens."""
    B, bs, g, dh, max_blocks, n_layers, li = 3, 8, 2, 16, 5, 2, 1
    L = 1 if build == "decode" else 6
    seq_lens = jnp.asarray([L, max(1, L - 2), max(1, L // 2)], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, L, Hkv * g, dh)), jnp.float32)
    kw = dict(scale=dh ** -0.5, layer=jnp.int32(li),
              slot_mask=jnp.asarray([True, True, True]),
              seq_lens=None if L == 1 else seq_lens)   # ragged chunk rows
    outs = {}
    if build == "window":
        window, ring_blocks = 12, 4                 # 32 lines a slot
        ring = jnp.asarray(rng.normal(
            size=(n_layers, B + 1, 2, ring_blocks, bs, Hkv, dh)), jnp.float32)
        offset = jnp.asarray([3, 21, 70], jnp.int32)    # one round the ring
        for m in ("fused", "gather"):
            outs[m] = nn.window_attn_with_cache(
                q, ring, jnp.asarray([2, 0, 3], jnp.int32), offset,
                window=window, paged_attn=m, **kw)
    else:
        n_blocks = B * max_blocks + 3
        pool = jnp.asarray(rng.normal(
            size=(n_layers, n_blocks, 2, bs, Hkv, dh)), jnp.float32)
        tables = jnp.asarray(rng.permutation(n_blocks)[:B * max_blocks]
                             .reshape(B, max_blocks), jnp.int32)
        offset = jnp.asarray([0, 17, max_blocks * bs - L], jnp.int32)
        for m in ("fused", "gather"):
            outs[m] = nn.paged_attn_with_cache(q, pool, tables, offset,
                                               paged_attn=m, **kw)
    fused, oracle = (np.asarray(outs[m], np.float32)
                     for m in ("fused", "gather"))
    np.testing.assert_allclose(fused, oracle, atol=1e-5, rtol=1e-5)
    head = rng.normal(size=(Hkv * g * dh, 64)).astype(np.float32)
    live = np.arange(L)[None] < np.asarray(seq_lens)[:, None]
    tokens = {m: (o.reshape(B, L, -1) @ head).argmax(-1)[live]
              for m, o in (("fused", fused), ("gather", oracle))}
    np.testing.assert_array_equal(tokens["fused"], tokens["gather"])


@pytest.mark.parametrize("arena", ["blocks", "stacked", "ring"])
def test_one_append_writes_both_planes_of_its_line_and_a_masked_row_neither(
        rng, arena):
    """``nn.paged_cache_update`` / ``nn.window_cache_update`` over the one
    arena: a token's K row lands in plane 0 and its V row in plane 1 of the
    (block, line) its position names (one layer of the pool, the stacked
    arena at a layer, a slot's ring round its end), a masked token and a
    dead row write NEITHER plane, and every other byte is the input's."""
    B, L, bs, H, dh, n_blocks, max_blocks = 3, 5, 4, 2, 8, 14, 4
    new = rng.normal(size=(B, L, 2, H, dh)).astype(np.float32)
    offsets = np.asarray([0, 10, 11], np.int32)   # row 1 wraps a ring of 12
    mask = np.arange(L)[None] < np.asarray([5, 3, 0])[:, None]  # row 2 dead
    tables = rng.permutation(n_blocks)[:B * max_blocks].reshape(
        B, max_blocks).astype(np.int32)
    slots, ring_blocks = np.asarray([2, 0, 1], np.int32), 3     # 12 lines
    if arena == "ring":
        before = rng.normal(size=(2, 3, 2, ring_blocks, bs, H, dh))
        got = nn.window_cache_update(
            jnp.asarray(before, jnp.float32), jnp.asarray(new),
            jnp.asarray(slots), jnp.asarray(offsets), jnp.asarray(mask),
            jnp.int32(1))
    else:
        before = rng.normal(size=(2, n_blocks, 2, bs, H, dh))
        pool = jnp.asarray(before, jnp.float32)
        got = (nn.paged_cache_update(pool, jnp.asarray(new),
                                     jnp.asarray(tables),
                                     jnp.asarray(offsets), jnp.asarray(mask),
                                     layer=jnp.int32(1))
               if arena == "stacked" else
               jnp.asarray(before, jnp.float32).at[1].set(
                   nn.paged_cache_update(pool[1], jnp.asarray(new),
                                         jnp.asarray(tables),
                                         jnp.asarray(offsets),
                                         jnp.asarray(mask))))
    want = before.astype(np.float32)
    for b in range(B):
        for l in range(L):
            if not mask[b, l]:
                continue
            p = int(offsets[b]) + l
            for plane in (0, 1):
                if arena == "ring":
                    want[1, slots[b], plane, (p // bs) % ring_blocks,
                         p % bs] = new[b, l, plane]
                else:
                    want[1, tables[b, p // bs], plane, p % bs] = \
                        new[b, l, plane]
    np.testing.assert_array_equal(np.asarray(got), want)
    changed = np.any(want != before.astype(np.float32), axis=(-1, -2))
    assert changed.sum() == 2 * mask.sum()      # a K line and a V line each


def test_the_kernel_refuses_a_pool_of_single_planes(rng):
    """K and V as two arenas is no pool any more: an arena without the two
    planes of a block is refused by name, as is a K+V append without both
    rows."""
    q, kp, vp, tables, kv_lens = _pool_case(rng, 2, 8, 2, 1, 16, 2)
    with pytest.raises(ValueError, match="side by side"):
        paged_attention(q[:, None], kp[None, :, None], tables, kv_lens,
                        layer=0, interpret=True)
    with pytest.raises(ValueError, match="K row and V row together"):
        nn.paged_cache_update(
            pair_planes(kp, vp), jnp.zeros((2, 1, 1, 2, 16)), tables,
            kv_lens - 1)


@pytest.mark.parametrize("shape,want", [
    ("decode", "folded"),           # one token a slot: every head, one dot
    ("chunk", "per_head"),          # folded scores would not fit
    ("decode-int8", "per_head"),    # dequantization is a head's
    ("decode-1kv", "per_head"),     # one kv head: nothing to fold
    ("latent", "per_head"),
])
def test_trace_record_names_the_arithmetic(rng, shape, want):
    """The arithmetic of a staged tile is static a call site;
    ``nn._fused_paged_attention`` keeps it beside each shape's trace and
    ``nn.fused_paged_arithmetic`` reads it back, keyed by the query's shape
    and the pool's dtype."""
    B, bs, max_blocks, dh = 5, 8, 2, 16      # B = 5: no other test's shape
    Hkv, g = (1, 4) if shape in ("decode-1kv", "latent") else (2, 2)
    L = 3 if shape in ("chunk", "latent") else 1
    q3, kp, vp, tables, kv_lens = _pool_case(rng, B, bs, Hkv, g, dh,
                                             max_blocks)
    q = jnp.broadcast_to(q3[:, None], (B, L, Hkv * g, dh))
    offset = jnp.maximum(kv_lens - L, 0)
    if shape == "latent":
        nn.latent_attn_with_cache(q, kp[:, :, 0], tables, offset, v_dim=8,
                                  scale=1.0, interpret=True)
        key = f"q{B}x{L}x{Hkv * g}x{dh}:float32"
    elif shape == "decode-int8":
        (kq, ks), (vq, vs) = (nn.quantize_kv_rows(x, jnp.int8)
                              for x in (kp, vp))
        nn.paged_attn_with_cache(q, pair_planes(kq, vq), tables, offset,
                                 scale=1.0, kv_scales=pair_planes(ks, vs, 1),
                                 interpret=True)
        key = f"q{B}x{L}x{Hkv * g}x{dh}:int8"
    else:
        nn.paged_attn_with_cache(q, pair_planes(kp, vp), tables, offset,
                                 scale=1.0, interpret=True)
        key = f"q{B}x{L}x{Hkv * g}x{dh}:float32"
    assert nn.fused_paged_arithmetic()[key] == want


def test_paged_attn_with_cache_prefill_routes_fused(rng):
    """L > 1 (chunked prefill, ragged seq_lens, nonzero offsets) routes to
    the fused kernel — the automatic gather fallback is retired — and the
    ledger labels it fused_prefill with the analytic L>1 byte bill."""
    B, bs, Hkv, dh, max_blocks = 2, 8, 2, 16, 2
    _, kp, vp, tables, _ = _pool_case(rng, B, bs, Hkv, 1, dh, max_blocks)
    L = 4
    q = jnp.asarray(rng.normal(size=(B, L, Hkv, dh)), jnp.float32)
    offset = jnp.asarray([3, 0], jnp.int32)          # mixed warm/cold starts
    seq_lens = jnp.asarray([L, 2], jnp.int32)        # ragged chunk lengths
    with comm_ledger.ledger(reset_first=True):
        out = nn.paged_attn_with_cache(q, pair_planes(kp, vp), tables, offset,
                                       scale=dh ** -0.5, seq_lens=seq_lens,
                                       paged_attn="fused", interpret=True)
        snap = comm_ledger.snapshot()
    assert out.shape == (B, L, Hkv, dh)
    methods = {d["method"] for d in snap.values()
               if isinstance(d, dict) and d.get("collective") == "paged_attn"}
    assert methods == {"fused_prefill"}
    # the explicit escape hatch is the oracle
    oracle = nn.paged_attn_with_cache(q, pair_planes(kp, vp), tables, offset,
                                      scale=dh ** -0.5, seq_lens=seq_lens,
                                      paged_attn="gather")
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=1e-5, rtol=1e-5)
    # ledger == analytic with the tuned q tile
    _, q_tile = tuned_paged_tile(bs, Hkv, dh, max_blocks,
                                 str(kp.dtype), L=L, g=1)
    entry = next(d for d in snap.values()
                 if isinstance(d, dict)
                 and d.get("collective") == "paged_attn")
    expect = pm.paged_attn_bytes(B, max_blocks, bs, Hkv, dh,
                                 n_q_heads=Hkv,
                                 itemsize=kp.dtype.itemsize,
                                 method="fused_prefill", L=L, q_tile=q_tile)
    assert entry["bytes_total"] == expect


def test_paged_attn_flag_validation(rng):
    _, kp, vp, tables, kv_lens = _pool_case(rng, 2, 8, 2, 1, 16, 2)
    q = jnp.zeros((2, 1, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="paged_attn"):
        nn.paged_attn_with_cache(q, pair_planes(kp, vp), tables, kv_lens - 1,
                                 scale=0.25, paged_attn="turbo")
    # BatchEngine rejects the flag before building anything
    with pytest.raises(ValueError, match="paged_attn"):
        BatchEngine(object(), paged_attn="turbo")


# -- 4. byte accounting ------------------------------------------------------

def test_fused_bytes_under_55_percent_of_gather():
    for shape in [(8, 64, 16, 8, 128, 32), (4, 4, 8, 2, 16, 4)]:
        B, max_blocks, bs, Hkv, dh, Hq = shape
        fused = pm.paged_attn_bytes(B, max_blocks, bs, Hkv, dh,
                                    n_q_heads=Hq, method="fused")
        gather = pm.paged_attn_bytes(B, max_blocks, bs, Hkv, dh,
                                     n_q_heads=Hq, method="gather")
        assert fused <= 0.55 * gather, shape
        # the kernel's own cost estimate carries the same fused bill
        cost = paged_attn_cost(B, max_blocks, bs, Hkv, dh, n_q_heads=Hq,
                               itemsize=2)
        assert cost.bytes_accessed == fused
    with pytest.raises(ValueError):
        pm.paged_attn_bytes(1, 1, 1, 1, 1, n_q_heads=1, method="dense")


def test_bytes_ratio_gates_lower_is_better():
    assert metric_direction("paged_attn_bytes_ratio") == -1
    assert metric_direction("pool_frag_frac") == -1
    assert roofline.metric_class("paged_attn_bytes_ratio") == "hbm"


# -- pool fragmentation stat -------------------------------------------------

def test_pool_fragmentation_stat():
    config = ModelConfig.from_name("tiny")
    pool = KVPool(config, n_blocks=8, block_size=4, max_seq_len=32)
    f = pool.fragmentation()
    assert f == {"free_blocks": 8, "largest_free_run": 8, "frag_frac": 0.0,
                 "cached_blocks": 0}
    # checkerboard the pool: a/b interleave, release a -> shredded free set
    assert pool.ensure("a", 4 * 4) and pool.ensure("b", 4 * 4)
    a_blocks = sorted(pool.table("a"))
    pool.release("b")
    pool.release("a")
    for i, blk in enumerate(a_blocks):       # re-own a's exact block ids
        assert pool.ensure(f"h{i}", 1)
    # free set is b's old blocks; contiguity depends on the LIFO order, the
    # invariant is the accounting:
    f = pool.fragmentation()
    assert f["free_blocks"] == 4
    assert 1 <= f["largest_free_run"] <= 4
    assert f["frag_frac"] == round(1 - f["largest_free_run"] / 4, 4)


# -- 3. BatchEngine end to end ----------------------------------------------

@pytest.fixture(scope="module")
def engine():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1],
                     set_default=False)
    config = ModelConfig.from_name("tiny")
    return Engine(config, mesh=mesh, mode="xla", block_n=8)


def test_batch_engine_fused_matches_gather_and_golden(engine):
    """>= 64 greedy decode steps through an oversubscribed pool (churn +
    preemption): the fused engine's tokens must equal BOTH the gather
    engine's and the single-sequence golden runs, with one compile per
    step shape, and the perfdb sample must carry the pool fragmentation
    stats."""
    config = engine.config
    rng = np.random.default_rng(7)
    n_req, gen = 8, 8                        # 64 decode steps total
    prompts = [rng.integers(0, config.vocab_size, size=7).tolist()
               for _ in range(n_req)]
    outs = {}
    for method in ("fused", "gather"):
        be = BatchEngine(engine, n_slots=3, n_blocks=6, block_size=4,
                         prefill_chunk=8, paged_attn=method)
        assert be.paged_attn == method
        rids = [be.submit(p, max_new_tokens=gen) for p in prompts]
        done = be.run(max_steps=800)
        assert len(done) == n_req
        assert be.metrics.as_dict()["preemptions"] > 0, \
            "pool was sized to force preemption"
        assert be.trace_counts == {"decode": 1, "prefill": 1}
        snap = be.stats_snapshot()
        assert snap["trace_counts"] == be.trace_counts
        if method == "fused":
            # the decode step's and the mixed step's decode block are ONE
            # shape, folded; the prefill block's chunk shape is per head
            Hq, dh = config.n_heads, config.head_dim
            took = snap["paged_arithmetic"]
            assert took[f"q3x1x{Hq}x{dh}:float32"] == "folded"
            assert took[f"q3x8x{Hq}x{dh}:float32"] == "per_head"
        be.pool.check_invariants()
        sample = be.perfdb_sample()
        for key in ("pool_free_blocks", "pool_largest_free_run",
                    "pool_frag_frac", "pool_cached_blocks"):
            assert key in sample
        # drained: free + cache-parked (all unreferenced) covers the pool
        assert (sample["pool_free_blocks"] + sample["pool_cached_blocks"]
                == float(be.pool.n_blocks))
        assert be.pool.n_reclaimable == be.pool.n_cached
        outs[method] = [np.asarray(done[r], np.int32) for r in rids]
    for f, g_, p in zip(outs["fused"], outs["gather"], prompts):
        np.testing.assert_array_equal(f, g_, err_msg="fused != gather")
        golden = np.asarray(
            engine.serve(np.asarray([p], np.int32), gen_len=gen))[0]
        np.testing.assert_array_equal(f, golden, err_msg="fused != golden")
