"""Fused paged-attention kernel, the chunk shape (L > 1) against the gather
reference: the second half of ``tests/test_paged_attention.py``'s guarantee
1, in a file of its own so that ``--dist loadfile`` hands the two halves to
two workers (they share no fixture; the reference and the pool builder are
that file's).
"""

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import pair_planes
from test_paged_attention import _pool_case, _ref_attn_chunk

from triton_distributed_tpu.kernels.paged_attention import paged_attention


@pytest.mark.parametrize("bs,max_blocks", [(8, 4), (16, 3), (128, 2)])
@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("L", [2, 7, 8])
def test_fused_prefill_matches_gather_reference(rng, bs, max_blocks, g, L):
    """The tentpole matrix: L > 1 chunked prefill through the fused kernel
    equals the gather reference across block sizes (128 misaligned
    included), GQA ratios, ragged kv_lens, and q-tile splits."""
    B, Hkv, dh = 4, 2, 16
    _, kp, vp, tables, _ = _pool_case(rng, B, bs, Hkv, g, dh, max_blocks)
    Hq = Hkv * g
    S = max_blocks * bs
    q = jnp.asarray(rng.normal(size=(B, L, Hq, dh)), jnp.float32)
    if bs == 128:
        # the misaligned case: lengths that end mid-block / mid-lane-tile
        kv_lens = jnp.asarray([L, 100, 129, 2 * 128 - 1], jnp.int32)
    else:
        kv_lens = jnp.asarray(rng.integers(L, S + 1, size=B), jnp.int32)
    ref = _ref_attn_chunk(q, kp, vp, tables, kv_lens,
                          jnp.full((B,), L, jnp.int32))
    for q_tile in (None, 1, 4, L):
        out = paged_attention(q, pair_planes(kp, vp), tables, kv_lens, q_tile=q_tile,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"q_tile={q_tile}")


def test_fused_ragged_mixed_step_and_dead_slots(rng):
    """One kernel call serving decode rows (q_len 1), partial-chunk rows,
    and a dead slot — the ragged mixed step the engine actually runs."""
    B, bs, Hkv, g, dh, max_blocks = 4, 8, 2, 2, 16, 4
    _, kp, vp, tables, _ = _pool_case(rng, B, bs, Hkv, g, dh, max_blocks)
    L = 8
    q = jnp.asarray(rng.normal(size=(B, L, Hkv * g, dh)), jnp.float32)
    q_lens = jnp.asarray([1, 8, 5, 3], jnp.int32)       # decode + chunks
    offs = jnp.asarray([16, 0, 9, 2], jnp.int32)        # warm + cold starts
    kv_lens = offs + q_lens
    slot_mask = jnp.asarray([True, True, True, False])
    out = paged_attention(q, pair_planes(kp, vp), tables, kv_lens, q_lens=q_lens,
                          slot_mask=slot_mask, interpret=True)
    masked_tables = jnp.where(slot_mask[:, None], tables, 0)
    ref = _ref_attn_chunk(q, kp, vp, masked_tables, kv_lens, q_lens)
    live = np.asarray(slot_mask)
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live], atol=1e-5)
    assert np.isfinite(np.asarray(out)).all(), \
        "dead slots must emit finite garbage, not NaN"
    # padding rows past q_lens[b] are exact zeros (the varlen contract)
    np.testing.assert_array_equal(np.asarray(out)[0, 1:], 0.0)
    np.testing.assert_array_equal(np.asarray(out)[2, 5:], 0.0)


def test_fused_prefill_causal_boundary_straddle(rng):
    """A query tile straddling kv_len: with q_tile=4 and L=6 the second
    tile holds live rows [4, 6) plus padding, and its causal frontier ends
    mid-block — the DMA-skip limit, the per-row mask, and the padded tail
    must all agree with the reference."""
    B, bs, Hkv, g, dh, max_blocks = 2, 8, 2, 1, 16, 4
    _, kp, vp, tables, _ = _pool_case(rng, B, bs, Hkv, g, dh, max_blocks)
    L = 6
    q = jnp.asarray(rng.normal(size=(B, L, Hkv * g, dh)), jnp.float32)
    # slot 0: the whole sequence IS the chunk (kv_len == L < block_size);
    # slot 1: frontier crosses a block edge inside the second q tile.
    kv_lens = jnp.asarray([L, 19], jnp.int32)
    ref = _ref_attn_chunk(q, kp, vp, tables, kv_lens,
                          jnp.full((B,), L, jnp.int32))
    for tile_blocks in (1, 2):
        out = paged_attention(q, pair_planes(kp, vp), tables, kv_lens, q_tile=4,
                              tile_blocks=tile_blocks, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"tile_blocks={tile_blocks}")
