"""The EvaByte block (``models/evabyte.py``, ``layers/eva_attn.py``: EVA
attention in every layer, the query's own ALIGNED window read key by key out
of a ring a slot and every earlier window through one pooled key and value a
chunk out of the block arenas, whose rows stand for a chunk of tokens each)
against the benchmark's plain reference (``perfbench/families/evabyte.py``)
at tiny float32 sizes on the CPU: three layers, four heads of 16, a window
of 32 positions in chunks of 4, eight prediction heads over a vocabulary of
40. ``paged_attn="gather"`` wherever the fused kernel is not the thing
tested.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import weights
from perfbench.families import evabyte as family
from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.models.config import (
    DeepseekV3Config,
    EvaByteConfig,
    ExaoneMoeConfig,
    GraniteHybridConfig,
    Lfm2MoeConfig,
    ModelConfig,
    NemotronHConfig,
)
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.evabyte import EVA_STATS, EvaByte
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving.batch_engine import BatchEngine
from triton_distributed_tpu.serving.kv_pool import (
    KVPool,
    blocks_needed,
    paged_state_shapes,
    row_tokens,
)
from triton_distributed_tpu.serving.scheduler import Request, Scheduler

from conftest import pair_planes

WINDOW, CHUNK, N_LAYERS, HEADS, VOCAB = 32, 4, 3, 8, 40
SIZES = family.Sizes(
    vocab_size=VOCAB, d_model=64, n_layers=N_LAYERS, heads=4, head_dim=16,
    d_ff=96, window=WINDOW, chunk=CHUNK, pred_heads=HEADS, theta=1e4,
    eps=1e-5, max_length=160, dtype="float32")
SEED = 7
P_ROWS = 3                   # the hand-driven steps' prefill block: 3 rows of 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)


@pytest.fixture(scope="module")
def served(mesh):
    mcfg, params = family.program({"source": "t"}, SIZES, SEED, mesh, {})
    return Engine(mcfg, mesh=mesh, params=params, mode="dist")


def reference_logits(tokens, sizes=SIZES, precision="float32"):
    """Every prediction head's logits at every position of ``tokens``, by
    the family's full forward: ``(len, heads, vocab)``."""
    w = weights.Weights(family, sizes, SEED)
    with jax.default_matmul_precision("highest"):
        g = w.globals_()
        h = jnp.take(g["embed"], jnp.asarray(tokens),
                     axis=0).astype(jnp.float32)
        for i in range(sizes.n_layers):
            lw = jax.tree.map(lambda a: a.astype(jnp.float32), w.layer(i))
            h = family.layer_forward(h, lw, sizes, i, precision)
        return np.asarray(family.all_heads_logits(sizes, g, h))


TOKENS = np.random.default_rng(0).integers(0, VOCAB, 134).tolist()
# the takes of the hand-driven mixed steps (tokens a step, in rows of 8):
# 24 (positions 0-23), 24 (24-47: ROWS OF ONE SLOT STRADDLE position 32),
# 19 (48-66: the last row's last chunk is RAGGED, position 66 opens one),
# 24 (67-90: rows that start inside a chunk and cross 64), 5 (91-95: ends
# one short of the boundary at 96), then decode from 96 to 133 (across 96
# and 128: five windows in all)
TAKES = (24, 24, 19, 24, 5)


def steps(engine, paged_attn="gather", n_slots=2):
    pool = KVPool(engine.config, n_blocks=32, block_size=CHUNK,
                  max_seq_len=160, mesh=engine.mesh, n_slots=n_slots,
                  max_take=P_ROWS * 8)
    kw = dict(paged_attn=paged_attn, state_specs=pool.specs)
    return (pool, jax.jit(engine._make_sm("dist", paged="prefill", **kw)),
            jax.jit(engine._make_sm("dist", paged="decode", **kw)))


def mixed_call(engine, pre, state, tables, slot, off, toks, n_slots=2):
    """One mixed step that gives ``slot`` the tokens ``toks`` from cache
    length ``off`` in consecutive rows of 8 of the prefill block."""
    take = len(toks)
    chunk = np.zeros((P_ROWS, 8), np.int32)
    dealt = np.tile(np.int32([-1, 0, 0]), (P_ROWS, 1))
    for k in range(-(-take // 8)):
        part = toks[8 * k:8 * k + 8]
        chunk[k, :len(part)] = part
        dealt[k] = slot, off + 8 * k, len(part)
    live = np.arange(n_slots) == slot
    return pre(engine.params,
               (jnp.zeros((n_slots,), jnp.int32), jnp.asarray(chunk),
                jnp.asarray(dealt)), state,
               jnp.asarray(np.where(live, off, 0), jnp.int32), tables,
               jnp.asarray(live), jnp.asarray(np.where(live, take, 0),
                                              jnp.int32))


def logits_through_the_pool(engine, paged_attn="gather", until=None,
                            slot=1, state=None, pool_steps=None):
    """One sequence through the step functions ``BatchEngine`` compiles, in
    slot 1 of two: the mixed steps of ``TAKES``, then one token a decode
    step. Returns ``{position: (heads, vocab) logits}`` for the last
    position of every mixed step and every decode step, the counters of
    every step, and the state."""
    pool, pre, dec = pool_steps or steps(engine, paged_attn)
    if pool.owned("a") == 0:
        assert pool.ensure("a", len(TOKENS) + 1)
    pool.check_invariants()
    ids = [None, None]
    ids[slot] = "a"
    tables = jnp.asarray(pool.padded_tables(ids))
    state = pool.state if state is None else state
    out, stats, off = {}, [], 0
    for take in TAKES:
        logits, aux, state = mixed_call(engine, pre, state, tables, slot, off,
                                        TOKENS[off:off + take])
        off += take
        out[off - 1] = np.asarray(aux["pred_logits"][slot]).reshape(
            HEADS, VOCAB)
        np.testing.assert_array_equal(np.asarray(logits[slot]),
                                      out[off - 1][0])
        stats.append(dict(zip(EVA_STATS, aux["stats"].tolist())))
    live = np.arange(2) == slot
    while off < (until or len(TOKENS)):
        logits, aux, state = dec(
            engine.params,
            jnp.asarray(np.where(live, TOKENS[off], 0)[:, None], jnp.int32),
            state, jnp.asarray(np.where(live, off, 0), jnp.int32), tables,
            jnp.asarray(live))
        out[off] = np.asarray(aux["pred_logits"][slot]).reshape(HEADS, VOCAB)
        np.testing.assert_array_equal(np.asarray(logits[slot]), out[off][0])
        stats.append(dict(zip(EVA_STATS, aux["stats"].tolist())))
        off += 1
    return out, stats, state


def test_the_class_the_pool_and_the_parameters_are_read_from_the_configuration(
        served):
    model = served.model
    assert isinstance(model, EvaByte) and model.step_stats == EVA_STATS
    assert model.layer_counts == {"eva": N_LAYERS, "dense": N_LAYERS}
    c = served.config
    assert (c.n_cache_layers, c.n_window_layers, c.kv_row_tokens) == \
        (N_LAYERS, N_LAYERS, CHUNK)
    assert sorted(served.params) == ["embed", "final_norm", "layers",
                                     "lm_head"]
    layers = served.params["layers"]
    assert sorted(layers["attn"]) == ["mu", "phi", "w_o", "w_qkv"]
    assert all(a.shape[0] == N_LAYERS for a in jax.tree.leaves(layers))
    assert served.params["lm_head"].shape == (64, HEADS * VOCAB)
    assert layers["attn"]["mu"].dtype == jnp.float32
    # the published configuration: 32 layers, 6.49 B parameters
    pub = EvaByte(EvaByteConfig())
    shapes = jax.tree.leaves(pub.param_shapes(),
                             is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s, _ in shapes) == \
        32 * 202_391_552 + 11_800_576
    assert (pub.config.window, pub.config.chunk_size,
            pub.config.kv_row_tokens, pub.config.n_pred_heads) == \
        (2048, 16, 16, 8)
    with pytest.raises(ValueError, match="as many key heads"):
        EvaByteConfig.tiny(n_kv_heads=2)
    with pytest.raises(ValueError, match="not whole chunks"):
        EvaByteConfig.tiny(window=30)
    # a ring block IS a chunk: another pairing is refused, as is the ring a
    # step's take does not fit
    with pytest.raises(ValueError, match="block_size == 4"):
        paged_state_shapes(c, n_blocks=8, block_size=8, n_slots=2,
                           max_take=8)
    with pytest.raises(NotImplementedError, match="speculative verify"):
        pool = KVPool(c, n_blocks=8, block_size=CHUNK, n_slots=2, max_take=8,
                      mesh=served.mesh)
        jax.eval_shape(
            served._make_sm("dist", paged="prefill", paged_attn="gather",
                            spec_verify=True, state_specs=pool.specs),
            served.params, jnp.zeros((2, 8), jnp.int32), pool.state,
            jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, pool.max_blocks_per_seq), jnp.int32),
            jnp.ones((2,), bool), jnp.full((2,), 8, jnp.int32))


def test_prefill_then_decode_across_five_windows_agrees_on_every_heads_logits(
        served):
    """Float32 on both sides, so what separates them is the order of the
    sums (a gathered ring and gathered summaries against a blocked mask, a
    pooled row written once against pooled anew, three layers deep): 2e-5 on
    a logit of spread 1. Any sub-layer in bfloat16 (relative 4e-3) fails it
    by two orders, a key or a summary more or fewer by three."""
    got, stats, _ = logits_through_the_pool(served)
    ref = reference_logits(TOKENS)
    assert len(got) == len(TAKES) + 134 - 96 and max(got) == 133
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, ref[pos], atol=2e-5, rtol=0)
    assert ref.std() == pytest.approx(1.0, rel=0.1)
    # the counters of the steps: chunks closed x layers, boundaries crossed,
    # what the decoding rows had to read, rows appended
    assert [s["eva_summaries_written"] // N_LAYERS for s in stats[:5]] == \
        [6, 6, 4, 6, 2]
    assert [s["eva_windows_opened"] for s in stats[:5]] == [0, 1, 1, 0, 0]
    assert [s["kv_rows_appended"] for s in stats[:5]] == \
        [N_LAYERS * t for t in TAKES]
    assert all(s["eva_exact_rows"] == s["eva_summary_rows"] == 0
               for s in stats[:5])            # no row decoded in them
    at = {96 + i: s for i, s in enumerate(stats[5:])}
    # position 96 opens the fourth window: one exact key, 3 x 8 summaries
    assert at[96] == {"eva_summaries_written": 0, "eva_windows_opened": 1,
                      "eva_exact_rows": N_LAYERS * 1,
                      "eva_summary_rows": N_LAYERS * 24,
                      "kv_rows_appended": N_LAYERS}
    assert at[127]["eva_exact_rows"] == N_LAYERS * 32
    assert at[127]["eva_summaries_written"] == N_LAYERS
    assert at[128]["eva_exact_rows"] == N_LAYERS * 1
    assert at[128]["eva_summary_rows"] == N_LAYERS * 32
    assert sum(s["eva_summaries_written"] for s in stats) == \
        N_LAYERS * (134 // CHUNK)
    for pos, s in at.items():
        assert (s["eva_exact_rows"], s["eva_summary_rows"]) == tuple(
            N_LAYERS * n for n in family.rows_needed(SIZES, pos))


def test_the_fused_walks_two_builds_and_their_combine_agree_with_the_reference(
        served):
    """The same sequence through ``paged_attn="fused"`` (the interpreted
    kernel): the aligned window build over the ring, the summary build over
    the block table, each with its running maximum and denominator, and the
    one combine. Held to the reference at the plain path's tolerance, up to
    position 99 (past the boundary at 96, with 24 summaries in reach)."""
    got, _, _ = logits_through_the_pool(served, "fused", until=100)
    ref = reference_logits(TOKENS)
    assert max(got) == 99
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, ref[pos], atol=2e-5, rtol=0)
    assert set(nn.fused_paged_arithmetic().values()) <= {"folded",
                                                         "per_head"}
    assert any(k.endswith(":window32:aligned") for k in
               nn.fused_paged_arithmetic())
    assert any(k.endswith(":summary") for k in nn.fused_paged_arithmetic())


def test_a_recompute_in_a_used_slot_gives_the_same_logits(served):
    """Preemption and recompute: the sequence is run to position 99 in slot
    1, its blocks released and given to another, and then run AGAIN from
    position 0 in slot 0 of the same state, whose rings and arenas hold what
    the first run and the other sequence left: the same logits to the bit
    (ring and summaries are rebuilt from position 0; nothing is cleared and
    nothing stale is visible)."""
    pool_steps = steps(served)
    pool = pool_steps[0]
    first, _, state = logits_through_the_pool(served, until=100,
                                              pool_steps=pool_steps)
    pool.release("a")
    assert pool.ensure("other", 77)
    pool.check_invariants()
    # the other sequence dirties slot 0's ring and the freed blocks
    _, pre, _ = pool_steps
    tables = jnp.asarray(pool.padded_tables(["other", None]))
    junk = np.random.default_rng(5).integers(0, VOCAB, 72).tolist()
    for off in range(0, 72, 24):
        _, _, state = mixed_call(served, pre, state, tables, 0, off,
                                 junk[off:off + 24])
    pool.release("other")
    again, _, _ = logits_through_the_pool(served, until=100, slot=0,
                                          state=state, pool_steps=pool_steps)
    pool.check_invariants()
    assert sorted(again) == sorted(first)
    for pos in first:
        np.testing.assert_array_equal(again[pos], first[pos])


def test_preemption_under_the_batch_engine_serves_the_same_tokens(served):
    """Through ``BatchEngine``: a pool too small for every request at once
    preempts, recomputes from position 0 and serves the tokens of a pool
    that holds them all; the pool's invariants hold after the run and the
    counters and the snapshot name the operator."""
    rng = np.random.default_rng(1)
    work = [(rng.integers(0, VOCAB, n).tolist(), m)
            for n, m in [(50, 60), (70, 40), (33, 80), (20, 30), (90, 30)]]

    def run(n_blocks):
        be = BatchEngine(served, n_slots=4, n_blocks=n_blocks,
                         block_size=CHUNK, prefill_chunk=8,
                         paged_attn="gather")
        rids = [be.submit(p, m) for p, m in work]
        be.run()
        be.pool.check_invariants()
        assert not be.failed and be.trace_counts == {"decode": 1,
                                                     "prefill": 1}
        return be, [be.finished[r].output for r in rids]

    roomy, want = run(64)
    tight, got = run(22)
    assert got == want
    m = tight.metrics.as_dict()
    assert m["preemptions"] >= 1 and not roomy.metrics.as_dict().get(
        "preemptions")
    snap = tight.stats_snapshot()
    assert snap["layers"] == {"eva": N_LAYERS, "dense": N_LAYERS}
    assert snap["pool"]["row_tokens"] == CHUNK
    assert snap["pool"]["summary_rows_held"] == 0      # nothing is live
    assert tight.pool.geometry()["row_tokens"] == CHUNK
    assert tight.pool.kv_fingerprint().endswith(":paired:window32x3:row4")
    assert not tight.pool.prefix_cacheable and tight.prefix_cache is None
    # every token served was appended in every layer; the recompute's again
    assert m["kv_rows_appended"] > N_LAYERS * sum(
        len(p) + n - 1 for p, n in work)
    assert m["eva_summaries_written"] > 0 and m["eva_windows_opened"] > 0
    assert m["eva_exact_rows"] > 0 and m["eva_summary_rows"] > 0


def test_a_ring_too_small_for_the_steps_take_is_refused(served):
    pool = KVPool(served.config, n_blocks=16, block_size=CHUNK,
                  max_seq_len=160, mesh=served.mesh, n_slots=2, max_take=8)
    assert pool.state.wkv.shape[3] == blocks_needed(WINDOW - 1 + 8, CHUNK)
    pre = served._make_sm("dist", paged="prefill", paged_attn="gather",
                          state_specs=pool.specs)
    with pytest.raises(ValueError, match="max_take >= 24"):
        mixed_call(served, pre, pool.state,
                   jnp.zeros((2, pool.max_blocks_per_seq), jnp.int32), 1, 0,
                   TOKENS[:24])


# -- what a query sees: its own aligned window exactly, earlier windows'
#    summaries, and nothing else -------------------------------------------------

def _eva_case(rng, offsets, lens, L, paged_attn):
    """Random rings and summary arenas with the rows of each sequence at
    their places, and ``eva_attn_with_cache`` over them; the queries' last
    positions are ``offsets + lens - 1``."""
    H, dh, B = 2, 8, len(offsets)
    ring_blocks, n_blocks, table = 12, 40, 10
    lines = ring_blocks * CHUNK
    k_ring = rng.normal(size=(1, B, ring_blocks, CHUNK, H, dh))
    v_ring = rng.normal(size=k_ring.shape)
    k_sum = rng.normal(size=(1, n_blocks, CHUNK, H, dh))
    v_sum = rng.normal(size=k_sum.shape)
    tables = rng.permutation(n_blocks)[:B * table].reshape(B, table)
    q = rng.normal(size=(B, L, H, dh))
    # the pool's arenas: a ring's planes outside its lines, a block's two
    # planes side by side
    args = [jnp.asarray(a, jnp.float32) for a in (
        q, pair_planes(k_ring, v_ring, 3), pair_planes(k_sum, v_sum))]
    out = nn.eva_attn_with_cache(
        *args, jnp.arange(B), jnp.asarray(tables, jnp.int32),
        jnp.asarray(offsets, jnp.int32), window=WINDOW, chunk=CHUNK,
        layer=jnp.int32(0), scale=dh ** -0.5,
        seq_lens=jnp.asarray(lens, jnp.int32), paged_attn=paged_attn)
    return np.asarray(out), (q, k_ring, v_ring, k_sum, v_sum, tables, lines)


def _dense(case, b, p, j, *, lo, n_rows):
    """Query j of row b at position p against ring positions lo..p and the
    first ``n_rows`` summary rows of its sequence, one softmax."""
    q, k_ring, v_ring, k_sum, v_sum, tables, lines = case
    ks = [k_ring[0, b].reshape(lines, *k_ring.shape[-2:])[i % lines]
          for i in range(lo, p + 1)]
    vs = [v_ring[0, b].reshape(lines, *v_ring.shape[-2:])[i % lines]
          for i in range(lo, p + 1)]
    for c in range(n_rows):
        ks.append(k_sum[0, tables[b, c // CHUNK], c % CHUNK])
        vs.append(v_sum[0, tables[b, c // CHUNK], c % CHUNK])
    k, v = np.stack(ks), np.stack(vs)                      # (S, H, dh)
    s = np.einsum("hd,shd->hs", q[b, j], k) * q.shape[-1] ** -0.5
    w = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hs,shd->hd", w / w.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_a_query_sees_its_own_window_exactly_and_only_earlier_windows_summaries(
        paged_attn):
    """``nn.eva_attn_with_cache`` against a dense softmax written out a
    query: decode rows at a boundary (one exact key), one short of it (the
    whole window), mid-window; a chunk row of 8 that STRADDLES a boundary
    (its first queries in window 1, its last in window 2, so the summary
    limit differs WITHIN the row); a dead row. And the two faults it must
    not have: a window that slides (``p - w < j``) and the whole chunks of
    the query's OWN window read as summaries each give another result (by
    ten times the tolerance at the least: one key more among thirty)."""
    rng = np.random.default_rng(11)
    per_window = WINDOW // CHUNK
    for offsets, lens, L in (
            ([64, 95, 40, 0], [1, 1, 1, 0], 1),
            ([60, 24, 33, 90], [8, 8, 3, 8], 8)):
        out, case = _eva_case(rng, offsets, lens, L, paged_attn)
        for b, (o, n) in enumerate(zip(offsets, lens)):
            for j in range(L):
                if j >= n:
                    assert not out[b, j].any()      # padding rows: zeros
                    continue
                p = o + j
                lo, seen = (p // WINDOW) * WINDOW, per_window * (p // WINDOW)
                want = _dense(case, b, p, j, lo=lo, n_rows=seen)
                np.testing.assert_allclose(out[b, j], want, atol=2e-5)
                if p >= WINDOW and 0 < p % WINDOW < WINDOW - 1:
                    slides = _dense(case, b, p, j, lo=p - WINDOW + 1,
                                    n_rows=seen)
                    assert np.abs(slides - want).max() > 2e-4
                if p % WINDOW >= CHUNK:
                    own = _dense(case, b, p, j, lo=lo,
                                 n_rows=seen + (p % WINDOW) // CHUNK)
                    assert np.abs(own - want).max() > 2e-4


def test_the_producer_pools_the_chunks_a_step_closed_and_no_other():
    """``nn.eva_summary_update``: rows whose new tokens close no chunk, one
    (a decode row at ``p % chunk == chunk - 1``), or several with a ragged
    last one; the pooled rows land at row ``c`` of the sequence's blocks and
    every other row of the arenas keeps its bits."""
    rng = np.random.default_rng(2)
    H, dh, B = 2, 8, 4
    k_ring = jnp.asarray(rng.normal(size=(2, B, 12, CHUNK, H, dh)),
                         jnp.float32)
    v_ring = jnp.asarray(rng.normal(size=k_ring.shape), jnp.float32)
    k_sum = jnp.asarray(rng.normal(size=(2, 40, CHUNK, H, dh)), jnp.float32)
    v_sum = jnp.asarray(rng.normal(size=k_sum.shape), jnp.float32)
    mu, phi = (jnp.asarray(rng.normal(size=(H, dh)), jnp.float32)
               for _ in range(2))
    tables = rng.permutation(40)[:B * 10].reshape(B, 10).astype(np.int32)
    # rows: closes none (6, 7: one token), closes chunk 7 (position 31),
    # 8 tokens from 58: closes chunks 14 and 15 and leaves 64-65 ragged, dead
    offsets, lengths = [6, 31, 58, 20], [1, 1, 8, 0]
    ring = pair_planes(k_ring, v_ring, 3)
    new = nn.eva_summary_update(
        pair_planes(k_sum, v_sum), ring, mu, phi, jnp.arange(B),
        jnp.asarray(tables), jnp.asarray(offsets), jnp.asarray(lengths),
        jnp.int32(1), chunk=CHUNK, scale=dh ** -0.5, max_len=8)
    want_k, want_v = np.array(k_sum), np.array(v_sum)
    for b, c in ((1, 7), (2, 14), (2, 15)):
        kc = np.asarray(k_ring[1, b, c % 12])              # (chunk, H, dh)
        vc = np.asarray(v_ring[1, b, c % 12])
        for h in range(H):
            for rows, by, dst in ((kc, mu, want_k), (vc, phi, want_v)):
                s = kc[:, h] @ np.asarray(by[h]) * dh ** -0.5
                w = np.exp(s - s.max())
                dst[1, tables[b, c // CHUNK], c % CHUNK, h] = \
                    (w / w.sum()) @ rows[:, h]
    new_k, new_v = new[:, :, 0], new[:, :, 1]   # ONE scatter, both planes
    np.testing.assert_allclose(new_k, want_k, atol=1e-5)
    np.testing.assert_allclose(new_v, want_v, atol=1e-5)
    changed = np.any(np.asarray(new_k) != np.asarray(k_sum), axis=(-1, -2))
    assert changed.sum() == 3 and not changed[0].any()
    with pytest.raises(ValueError, match="ONE ring block"):
        nn.eva_summary_update(
            pair_planes(k_sum, v_sum), ring, mu, phi, jnp.arange(B),
            jnp.asarray(tables), jnp.asarray(offsets), jnp.asarray(lengths),
            jnp.int32(1), chunk=8, scale=1.0, max_len=8)


# -- the pool: rows that stand for several tokens ----------------------------------

def test_blocks_for_rows_that_stand_for_several_tokens_and_for_all_others():
    """``blocks_needed`` at 16 tokens a row, and unchanged for every model
    that states nothing: the rule ``KVPool.blocks_for`` and
    ``Scheduler.admit`` share."""
    for n, want in ((1, 1), (16, 1), (256, 1), (257, 2), (4096, 16),
                    (4097, 17), (28_672, 112), (32_768, 128)):
        assert blocks_needed(n, 16, 16) == want == -(-(-(-n // 16)) // 16)
    for n in (0, 1, 15, 16, 17, 255, 4097):
        for bs in (1, 4, 16):
            assert blocks_needed(n, bs) == blocks_needed(n, bs, 1) == \
                -(-n // bs)
    stated = {"EvaByteConfig": 16}
    for cfg in (ModelConfig.from_name("tiny"), DeepseekV3Config.tiny(),
                GraniteHybridConfig.tiny(), NemotronHConfig.tiny(),
                ExaoneMoeConfig.tiny(), ExaoneMoeConfig.smallthinker(),
                Lfm2MoeConfig.tiny(), EvaByteConfig()):
        assert row_tokens(cfg) == stated.get(type(cfg).__name__, 1)
    # a model that states nothing keeps its geometry and fingerprint
    plain = KVPool(ModelConfig.from_name("tiny"), n_blocks=8, block_size=4)
    assert plain.row_tokens == 1 and "row_tokens" not in plain.geometry()
    assert plain.max_blocks_per_seq == 8 and plain.blocks_for(9) == 3
    assert plain.kv_fingerprint() == "float32:none:paired"


def test_the_pools_tables_follow_the_tokens_a_row_stands_for(mesh):
    """``ensure``, ``truncate``, ``padded_tables``, ``release`` and the
    scheduler's admission on a pool whose rows stand for 4 tokens in blocks
    of 4 rows (a block: 16 tokens), ``check_invariants`` after every
    mutation."""
    cfg = EvaByteConfig.tiny()
    pool = KVPool(cfg, n_blocks=12, block_size=CHUNK, max_seq_len=160,
                  mesh=mesh, n_slots=2, max_take=8)
    assert (pool.row_tokens, pool.max_blocks_per_seq) == (4, 10)
    assert pool.state.kv.shape == (3, 12, 2, 4, 4, 16)
    assert pool.state.wkv.shape == (3, 2, 2, blocks_needed(31 + 8, 4), 4, 4,
                                    16)
    assert [pool.blocks_for(n) for n in (1, 16, 17, 64, 65, 160)] == \
        [1, 1, 2, 4, 5, 10]
    assert pool.ensure("a", 17) and pool.owned("a") == 2
    pool.check_invariants()
    assert pool.ensure("a", 32) and pool.owned("a") == 2    # same blocks
    assert pool.ensure("a", 33) and pool.owned("a") == 3
    pool.check_invariants()
    assert pool.ensure("b", 160) is False and pool.owned("b") == 0
    pool.check_invariants()
    assert pool.ensure("b", 144) and pool.n_free == 0
    pool.check_invariants()
    with pytest.raises(ValueError, match="exceeds pool max_seq_len"):
        pool.ensure("a", 161)
    t = pool.padded_tables(["a", "b"])
    assert t.shape == (2, 10) and (t[0, 3:] == 0).all()
    assert sorted(t[0, :3].tolist() + t[1, :9].tolist()) == list(range(12))
    assert pool.truncate("a", 16) == 2 and pool.owned("a") == 1
    pool.check_invariants()
    with pytest.raises(ValueError, match="cannot grow"):
        pool.truncate("a", 17)
    pool.release("b")
    pool.check_invariants()
    assert pool.n_free == 11
    # admission charges what allocation will: a context of 100 (+1) tokens
    # is 26 rows, 7 blocks
    sched = Scheduler()
    for n in (100, 60, 60):
        sched.submit(Request(f"r{n}", [1] * n, 4))
    got = sched.admit(free_slots=3, free_blocks=pool.n_free, blocks_for=pool)
    assert [len(r.prompt) for r in got] == [100, 60]       # 7 + 4 of 11
    pool.release("a")
    pool.check_invariants()


# -- bfloat16 --------------------------------------------------------------------

def test_the_bfloat16_path_is_held_to_the_float32_reference(mesh):
    """The served dtype: weights, the ring, the summaries and the products
    in bfloat16 (float32 accumulation, residual stream and pooling) against
    the float32 reference over the SAME bfloat16-rounded weights, 100
    positions across three boundaries. bfloat16 rounds to a relative 2^-8 an
    operand; through three layers the worst logit of a position (spread 1,
    2,560 logits a position) moves by 0.011-0.026, mean 0.020: held to 0.06
    at the worst position and 0.04 in the mean. The reference with every
    linear layer in float8 (the precision below) moves them by 0.24-0.4
    (asserted: over 0.12 at EVERY position, twice the worst-position limit),
    so a float8 product anywhere on the path fails."""
    sizes = dataclasses.replace(SIZES, dtype="bfloat16")
    mcfg, params = family.program({"source": "t"}, sizes, SEED, mesh, {})
    engine = Engine(mcfg, mesh=mesh, params=params, mode="dist")
    got, _, _ = logits_through_the_pool(engine, until=100)
    ref = reference_logits(TOKENS, sizes)
    err = np.array([np.abs(got[p] - ref[p]).max() for p in got])
    assert err.max() < 0.06 and err.mean() < 0.04, (err.max(), err.mean())
    fp8 = reference_logits(TOKENS, sizes, "fp8")
    low = np.array([np.abs(fp8[p] - ref[p]).max() for p in got])
    assert low.mean() > 0.2 and low.min() > 0.12, (low.mean(), low.min())
