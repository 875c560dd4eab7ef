"""Radix-tree prefix cache tests (serving/prefix_cache.py).

The load-bearing guarantees (docs/serving.md, "Prefix caching"):
  1. radix soundness — match returns exactly the longest cached prefix,
     block-granular with a CoW tail; insert/promote/release/evict keep the
     pool partition (free ∪ private ∪ cached) and every refcount exact;
  2. LRU policy — eviction frees stalest unreferenced leaves first, never
     a referenced block, never a pinned (mid-adoption) block;
  3. BIT-IDENTITY — a request admitted against a warm cache emits the
     same greedy tokens as against a cold pool, end-to-end through the
     BatchEngine with preemption churn, with trace_counts still {1,1}.
"""

import jax
import numpy as np
import pytest

from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import BatchEngine, KVPool, \
    RadixPrefixCache


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


def _golden(engine, prompt, gen_len):
    out = engine.serve(np.asarray([prompt], np.int32), gen_len=gen_len)
    return np.asarray(out)[0]


def _pool_and_cache(config, n_blocks=8, block_size=4):
    pool = KVPool(config, n_blocks=n_blocks, block_size=block_size,
                  max_seq_len=32)
    return pool, RadixPrefixCache(pool)


# -- 1. radix tree mechanics --------------------------------------------------

def test_match_insert_roundtrip(setup):
    _, config, _ = setup
    pool, cache = _pool_and_cache(config)
    toks = list(range(10))                      # 2 full blocks + 2-token tail
    assert pool.ensure("a", 10)
    assert cache.insert("a", toks) == 3 and len(cache) == 3
    pool.release("a")
    pool.check_invariants()
    assert pool.n_cached == 3 and pool.n_reclaimable == 3
    # empty lookup, unknown prefix, exact full-chunk hit
    assert cache.match([]).match_len == 0
    assert cache.match([99, 98, 97, 96]).match_len == 0
    m = cache.match(toks[:8])
    assert m.match_len == 8 and len(m.blocks) == 2 and m.cow_src is None
    # the capped lookup ends mid-block: full blocks by reference + CoW tail
    m = cache.match(toks, max_len=9)
    assert m.match_len == 9 and len(m.blocks) == 2
    assert m.cow_src is not None and m.cow_valid == 1
    # match_len probe agrees and has no refcount side effects
    assert cache.match_len(toks, max_len=9) == 9
    pool.check_invariants()


def test_adoption_refcounts_through_ensure(setup):
    _, config, _ = setup
    pool, cache = _pool_and_cache(config)
    toks = list(range(12))
    assert pool.ensure("a", 12)
    cache.insert("a", toks)
    pool.release("a")
    m = cache.match(toks, max_len=11)           # 2 full + 3-token CoW
    assert pool.ensure("b", 13, adopt=m.blocks, cow_src=m.cow_src)
    pool.check_invariants()
    assert all(pool.refs(b) == 1 for b in m.blocks)
    assert pool.refs(m.cow_src) == 0            # CoW copy is PRIVATE
    tab = pool.table("b")
    assert tab[:2] == m.blocks and len(tab) == 4
    assert tab[2] not in pool._cached           # the fresh copy
    # a second adopter shares the same resident blocks
    m2 = cache.match(toks, max_len=11)
    assert m2.blocks == m.blocks
    assert pool.ensure("c", 13, adopt=m2.blocks, cow_src=m2.cow_src)
    assert all(pool.refs(b) == 2 for b in m.blocks)
    pool.check_invariants()
    pool.release("b"), pool.release("c")
    assert all(pool.refs(b) == 0 for b in m.blocks)
    pool.check_invariants()
    # adoption is admission-time only; unknown blocks are rejected
    assert pool.ensure("d", 4)
    with pytest.raises(ValueError):
        pool.ensure("d", 8, adopt=m.blocks)
    with pytest.raises(KeyError):
        pool.ensure("e", 8, adopt=[999])


def test_cow_copies_device_rows(setup):
    """The CoW block must hold the source block's exact K/V bytes: BOTH
    planes of the block's one run."""
    _, config, _ = setup
    pool, cache = _pool_and_cache(config)
    toks = list(range(6))
    assert pool.ensure("a", 6)
    src_blk = pool.table("a")[1]                # the partial tail block
    # stamp recognizable values into the source block on device
    kv = pool.state.kv.at[:, src_blk, 0].set(3.25).at[:, src_blk, 1].set(-1.5)
    pool.state = type(pool.state)(kv=kv)
    cache.insert("a", toks)
    pool.release("a")
    m = cache.match(toks, max_len=5)
    assert m.cow_src == src_blk and m.cow_valid == 1
    assert pool.ensure("b", 6, adopt=m.blocks, cow_src=m.cow_src)
    dst_blk = pool.table("b")[1]
    assert dst_blk != src_blk
    np.testing.assert_array_equal(np.asarray(pool.state.kv[:, dst_blk]),
                                  np.asarray(pool.state.kv[:, src_blk]))
    assert np.all(np.asarray(pool.state.kv[:, dst_blk, 0]) == 3.25)
    assert np.all(np.asarray(pool.state.kv[:, dst_blk, 1]) == -1.5)
    pool.release("b")
    pool.check_invariants()


def test_partial_divergence_creates_sibling_leaves(setup):
    _, config, _ = setup
    pool, cache = _pool_and_cache(config, n_blocks=10)
    a = [0, 1, 2, 3, 4, 5]                      # tail [4, 5]
    b = [0, 1, 2, 3, 4, 9]                      # tail [4, 9] — diverges
    assert pool.ensure("a", 6)
    cache.insert("a", a)
    pool.release("a")
    assert pool.ensure("b", 6)
    assert cache.insert("b", b) == 1            # shares the full block
    pool.release("b")
    assert len(cache) == 3                      # 1 shared + 2 sibling tails
    ma, mb = cache.match(a), cache.match(b)
    assert ma.match_len == 6 and mb.match_len == 6
    assert ma.cow_src != mb.cow_src             # distinct physical blocks
    assert ma.blocks == mb.blocks               # shared full chunk
    pool.check_invariants()


def test_lru_eviction_order_and_pinning(setup):
    _, config, _ = setup
    pool, cache = _pool_and_cache(config, n_blocks=6)
    cold, warm = [1, 1, 1, 1], [2, 2, 2, 2]
    for sid, toks in (("c", cold), ("w", warm)):
        assert pool.ensure(sid, 4)
        cache.insert(sid, toks)
        pool.release(sid)
    cache.match(warm)                           # touch: warm becomes MRU
    cold_blk = cache.match(cold, max_len=3).cow_src
    warm_blk = cache.match(warm, max_len=3).cow_src
    assert cache.evict(1) == 1                  # stalest leaf goes first
    assert not pool.is_cached(cold_blk)
    assert pool.is_cached(warm_blk)
    # pinning: an exclude-listed block survives even as the only candidate
    assert cache.evict(1, exclude={warm_blk}) == 0
    # a referenced block is never evicted
    m = cache.match(warm, max_len=3)
    assert pool.ensure("r", 5, cow_src=m.cow_src)
    # warm_blk is refcount 0 (CoW doesn't incref) but pool pressure must
    # still reclaim it through ensure's automatic LRU pass:
    assert pool.ensure("big", 4 * (pool.n_free + pool.n_reclaimable))
    assert pool.n_cached == 0 and pool.n_free == 0
    pool.release("r"), pool.release("big")
    pool.check_invariants()


def test_disabled_cache_is_inert(setup):
    _, config, _ = setup
    pool, cache = _pool_and_cache(config)
    cache.enabled = False
    assert pool.ensure("a", 8)
    assert cache.insert("a", list(range(8))) == 0
    pool.release("a")
    assert pool.n_cached == 0 and pool.n_free == pool.n_blocks
    assert cache.match(list(range(8))).match_len == 0
    assert cache.match_len(list(range(8))) == 0
    # one cache per pool
    with pytest.raises(RuntimeError):
        RadixPrefixCache(pool)


# -- 2. end-to-end bit-identity ----------------------------------------------

@pytest.mark.parametrize("paged_attn,kv_dtype", [
    ("fused", None), ("gather", None),
    ("fused", "int8"),
    pytest.param("fused", "fp8", marks=pytest.mark.slow),
])
def test_warm_cache_bit_identical_with_churn(setup, paged_attn, kv_dtype):
    """The acceptance bar: >=64 greedy decode steps through an
    oversubscribed engine (preemption churn), 8 requests sharing an
    8-token prefix in 4 prompt groups. Outputs must equal BOTH the
    single-sequence goldens and a prefix-cache-disabled engine's, the
    warm engine must actually hit, and neither engine may retrace.
    Parametrized over the attention path: 'fused' drives every warm
    admission through the fused prefill kernel (the only routed path
    since the gather auto-fallback was retired); 'gather' is the
    escape-hatch oracle and must agree token-for-token. The quantized
    rows (kv_dtype int8/fp8) assert the same warm==cold contract in the
    QUANTIZED domain — cached blocks carry their per-row scales, so CoW
    adoption replays the exact wire bytes — but skip the f32 golden
    comparison, since quantized storage legitimately perturbs tokens."""
    _, config, engine = setup
    rng = np.random.default_rng(11)
    shared = rng.integers(0, config.vocab_size, size=8).tolist()
    uniq = [rng.integers(0, config.vocab_size, size=3).tolist()
            for _ in range(4)]
    # 4 distinct prompts, each submitted twice -> the second admission of
    # each can adopt what the first one computed
    prompts = [shared + u for u in uniq for _ in (0, 1)]
    gen = 8                                     # 8 requests x 8 = 64 steps
    outs = {}
    for label, caching in (("cold", False), ("warm", True)):
        be = BatchEngine(engine, n_slots=3, n_blocks=9, block_size=4,
                         prefill_chunk=8, prefix_cache=caching,
                         paged_attn=paged_attn, kv_dtype=kv_dtype)
        assert (be.prefix_cache is not None) == caching
        rids = [be.submit(p, max_new_tokens=gen) for p in prompts]
        done = be.run(max_steps=1000)
        assert len(done) == len(prompts)
        assert be.metrics.as_dict()["preemptions"] > 0, \
            "pool was sized to force preemption churn"
        assert be.trace_counts == {"decode": 1, "prefill": 1}
        be.pool.check_invariants()
        assert (be.pool.n_free + be.pool.n_reclaimable == be.pool.n_blocks)
        outs[label] = [np.asarray(done[r], np.int32) for r in rids]
        if caching:
            m = be.metrics.as_dict()
            assert m["prefix_hits"] > 0, "warm engine never hit the cache"
            assert m["prefix_cached_tokens"] > 0
            sample = be.perfdb_sample()
            assert 0.0 < sample["prefix_hit_rate"] <= 1.0
            assert 0.0 < sample["prefix_cached_token_frac"] < 1.0
    for cold, warm, p in zip(outs["cold"], outs["warm"], prompts):
        np.testing.assert_array_equal(warm, cold, err_msg="warm != cold")
        if kv_dtype is None:
            np.testing.assert_array_equal(
                warm, _golden(engine, p, gen), err_msg="warm != golden")
