"""Continuous-batching serving subsystem tests.

The load-bearing guarantees (docs/serving.md):
  1. allocator soundness — blocks are never leaked, double-owned, or both
     free and owned, across arbitrary alloc/free/fragmentation churn;
  2. scheduling policy — priority-then-FIFO admission bounded by the block
     budget; eviction picks the lowest-priority latest-admitted slot;
  3. BIT-IDENTICAL greedy output — the slot-batched paged engine emits the
     same tokens as N independent single-sequence ``Engine`` runs, through
     staggered arrivals, chunked prefill, and preemption-by-recompute;
  4. ONE compile per step shape — slot churn (arrivals, departures,
     preemptions) never retraces the decode or mixed step.

``[fused]`` on the cases whose subject is the kernel inside the step (the
churn of ``test_batched_matches_independent_engines``, the in-place append,
the two-block form), ``[gather]`` / ``conftest.PLAIN_PATH`` on those whose
subject is the deal, the pool or the host loop (preemption by recompute,
priority). The TP=8 cases are ``tests/test_serving_tp8.py``: a file of their
own (they share no fixture with these) so that ``--dist loadfile`` hands
them to another worker.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.models.config import DeepseekV3Config
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import BatchEngine, KVPool, \
    PagedKVState, RadixPrefixCache, Request, Scheduler
from triton_distributed_tpu.serving.kv_pool import paged_state_specs


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


@pytest.fixture(scope="module")
def latent_engine(setup):
    """The second model class: a latent pool (one arena, no V)."""
    return Engine(DeepseekV3Config.tiny(), mesh=setup[0], mode="dist")


def _golden(engine, prompt, gen_len):
    """Single-sequence reference run for one request."""
    out = engine.serve(np.asarray([prompt], np.int32), gen_len=gen_len)
    return np.asarray(out)[0]


# -- 1. pool allocator ------------------------------------------------------

def test_pool_alloc_free_invariants(setup):
    _, config, _ = setup
    pool = KVPool(config, n_blocks=10, block_size=4, max_seq_len=32)
    assert pool.max_blocks_per_seq == 8
    assert pool.ensure("a", 5)           # 2 blocks
    assert pool.ensure("b", 4)           # 1 block
    assert pool.owned("a") == 2 and pool.owned("b") == 1
    assert pool.n_free == 7
    pool.check_invariants()
    # growth is incremental: covering 6 tokens needs no new block yet
    assert pool.ensure("a", 8) and pool.owned("a") == 2
    assert pool.ensure("a", 9) and pool.owned("a") == 3
    # all-or-nothing: a request that cannot fully fit allocates NOTHING
    assert pool.ensure("c", 4 * 6)
    free_before = pool.n_free
    assert not pool.ensure("d", 4 * (free_before + 1))
    assert pool.n_free == free_before and pool.owned("d") == 0
    pool.check_invariants()
    # fragmentation: interleaved release returns blocks for reuse
    pool.release("a")
    assert pool.ensure("e", 4 * 3)       # reuses a's blocks
    pool.check_invariants()
    pool.release("b"), pool.release("c"), pool.release("e")
    assert pool.n_free == pool.n_blocks
    pool.check_invariants()
    with pytest.raises(ValueError):
        pool.ensure("z", 33)             # beyond max_seq_len


def test_pool_invariants_under_cache_adoption_stress(setup):
    """Satellite: several hundred random interleavings of ensure / grow /
    finish-and-insert / preempt-release, with prefix-cache adoption (by
    reference AND by CoW) in the mix. ``check_invariants`` — including the
    refcount == table-occurrence agreement — and ``fragmentation()``
    accounting must hold after EVERY mutation."""
    _, config, _ = setup
    pool = KVPool(config, n_blocks=12, block_size=4, max_seq_len=32)
    cache = RadixPrefixCache(pool)
    rng = np.random.default_rng(42)
    live: dict[str, list[int]] = {}       # seq_id -> token stream
    next_id = 0

    def check():
        pool.check_invariants()
        f = pool.fragmentation()
        assert f["free_blocks"] == pool.n_free
        assert f["cached_blocks"] == pool.n_cached
        assert (pool.n_used - pool.n_cached) + pool.n_cached + pool.n_free \
            == pool.n_blocks

    for step in range(400):
        op = rng.choice(["admit", "grow", "finish", "preempt"])
        if op == "admit" and len(live) < 4:
            # shared-prefix population: few distinct streams, many repeats
            base = [int(t) for t in
                    rng.integers(0, 8, size=int(rng.integers(6, 20)))]
            if rng.random() < 0.6 and live:
                base = next(iter(live.values()))[:len(base)] or base
            sid = f"s{next_id}"
            next_id += 1
            m = cache.match(base, max_len=len(base) - 1)
            ok = pool.ensure(sid, len(base) + 1, adopt=m.blocks,
                             cow_src=m.cow_src)
            if ok:
                live[sid] = base
        elif op == "grow" and live:
            sid = list(live)[int(rng.integers(len(live)))]
            toks = live[sid]
            if len(toks) < 28:
                toks.append(int(rng.integers(0, 8)))
                if not pool.ensure(sid, len(toks) + 1):
                    # pool full even after LRU reclaim: preempt instead
                    pool.release(sid)
                    del live[sid]
        elif op == "finish" and live:
            sid = list(live)[int(rng.integers(len(live)))]
            cache.insert(sid, live[sid])
            pool.release(sid)
            del live[sid]
        elif op == "preempt" and live:
            # eviction-by-recompute: release WITHOUT inserting
            sid = list(live)[int(rng.integers(len(live)))]
            pool.release(sid)
            del live[sid]
        check()

    for sid in list(live):
        pool.release(sid)
    check()
    assert pool.n_free + pool.n_reclaimable == pool.n_blocks
    # and the whole cache is evictable once nobody references it
    cache.drop()
    check()
    assert pool.n_free == pool.n_blocks and pool.n_cached == 0


# -- 2. scheduler policy ----------------------------------------------------

def test_scheduler_fifo_and_priority():
    s = Scheduler()
    for i, prio in enumerate([0, 0, 5, 0]):
        s.submit(Request(req_id=i, prompt=[1] * 4, max_new_tokens=2,
                         priority=prio))
    # priority first, FIFO within a class
    assert [s.pop().req_id for _ in range(4)] == [2, 0, 1, 3]


def test_scheduler_admission_budget():
    s = Scheduler()
    for i, plen in enumerate([7, 7, 3]):   # needs 2, 2, 1 blocks (bs=4)
        s.submit(Request(req_id=i, prompt=[1] * plen, max_new_tokens=1))
    got = s.admit(free_slots=3, free_blocks=3, block_size=4)
    # head fits (2 blocks), second head does NOT (2 > 1 left) — and
    # admission must not skip ahead to the smaller third request
    assert [r.req_id for r in got] == [0]
    assert len(s) == 2
    # requeue keeps the original FIFO position
    r = s.pop()
    s.requeue(r)
    assert s.peek().req_id == 1


def test_scheduler_admission_delegates_block_rounding(setup):
    """`blocks_for` (pool or callable) must agree with the legacy
    block_size path — one rounding rule, never two."""
    _, config, _ = setup
    pool = KVPool(config, n_blocks=8, block_size=4, max_seq_len=32)

    def fill(s):
        for i, plen in enumerate([7, 7, 3]):
            s.submit(Request(req_id=i, prompt=[1] * plen, max_new_tokens=1))
        return s

    got_bs = fill(Scheduler()).admit(free_slots=3, free_blocks=3,
                                     block_size=4)
    got_pool = fill(Scheduler()).admit(free_slots=3, free_blocks=3,
                                       blocks_for=pool)
    got_fn = fill(Scheduler()).admit(free_slots=3, free_blocks=3,
                                     blocks_for=pool.blocks_for,
                                     block_size=pool.block_size)
    assert ([r.req_id for r in got_bs] == [r.req_id for r in got_pool]
            == [r.req_id for r in got_fn] == [0])
    with pytest.raises(TypeError):
        Scheduler().admit(free_slots=1, free_blocks=1)


def test_scheduler_admission_discounts_cached_prefix():
    """A mostly-cached request fits where a cold one would not: only the
    uncached suffix is charged (full blocks only — a CoW tail still costs
    a fresh block)."""
    s = Scheduler()
    s.submit(Request(req_id="big", prompt=[1] * 11, max_new_tokens=1))
    # cold: needs ceil(12/4)=3 blocks > 1 available
    assert not s.admit(free_slots=1, free_blocks=1, block_size=4)
    # warm: 8 of 11 prompt tokens cached -> 2 full blocks adopted free
    got = s.admit(free_slots=1, free_blocks=1, block_size=4,
                  match_len=lambda r: 8)
    assert [r.req_id for r in got] == ["big"]
    # the discount is capped at context_len-1 and floored to full blocks:
    # a 9-token "match" of an 8-token context counts 7 -> 1 block
    s2 = Scheduler()
    s2.submit(Request(req_id="edge", prompt=[1] * 8, max_new_tokens=1))
    assert not s2.admit(free_slots=1, free_blocks=1, block_size=4,
                        match_len=lambda r: 9)   # 3 - 7//4 = 2 > 1
    assert s2.admit(free_slots=1, free_blocks=2, block_size=4,
                    match_len=lambda r: 9)
    with pytest.raises(TypeError):
        # a bare callable gives no block size to floor the discount with
        s2.admit(free_slots=1, free_blocks=1,
                 blocks_for=lambda n: -(-n // 4), match_len=lambda r: 4)


def test_padded_tables_unknown_seq_raises(setup):
    """An unknown seq_id must raise, not emit an all-zero table (which is
    indistinguishable from a real table pointing at block 0)."""
    _, config, _ = setup
    pool = KVPool(config, n_blocks=4, block_size=4, max_seq_len=16)
    assert pool.ensure("a", 4)
    t = pool.padded_tables(["a", None])         # None = empty slot, fine
    assert t.shape == (2, pool.max_blocks_per_seq)
    with pytest.raises(KeyError):
        pool.padded_tables(["a", "ghost"])
    pool.release("a")
    with pytest.raises(KeyError):
        pool.padded_tables(["a"])               # released = unknown again


def test_scheduler_victim_selection():
    reqs = [Request(req_id=i, prompt=[1], max_new_tokens=1, priority=p)
            for i, p in enumerate([1, 0, 0])]
    running = [("s0", reqs[0], 0), ("s1", reqs[1], 1), ("s2", reqs[2], 2)]
    # lowest priority, latest admitted among equals
    assert Scheduler.select_victim(running) == "s2"
    assert Scheduler.select_victim(running, exclude=("s2",)) == "s1"
    assert Scheduler.select_victim([], exclude=()) is None


# -- 3+4. batched engine: equivalence + one-compile -------------------------

def test_batched_matches_independent_engines(setup):
    """Staggered arrivals/departures, varied prompt lengths and gen
    lengths: greedy tokens must equal N independent Engine runs, with ONE
    compile for each of the decode / mixed steps across all the churn."""
    _, config, engine = setup
    rng = np.random.default_rng(0)
    be = BatchEngine(engine, n_slots=4, block_size=4, prefill_chunk=8)
    specs = [(3, 4), (5, 6), (7, 3), (4, 5), (6, 4)]
    prompts = [rng.integers(0, config.vocab_size, size=n).tolist()
               for n, _ in specs]
    # staggered: two up front, the rest mid-flight
    rids = [be.submit(prompts[0], specs[0][1]),
            be.submit(prompts[1], specs[1][1])]
    be.step(), be.step()
    rids.append(be.submit(prompts[2], specs[2][1]))
    be.step()
    rids.append(be.submit(prompts[3], specs[3][1]))
    rids.append(be.submit(prompts[4], specs[4][1]))
    out = be.run(max_steps=300)
    assert len(out) == len(specs)
    for rid, p, (_, g) in zip(rids, prompts, specs):
        np.testing.assert_array_equal(
            np.asarray(out[rid], np.int32), _golden(engine, p, g),
            err_msg=f"request {rid} diverged from its single-sequence run")
    # the one-compile-across-churn guarantee
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    be.pool.check_invariants()
    # Everything released: finished requests park their blocks in the
    # prefix cache (resident, zero refs) instead of freeing them.
    assert be.pool.n_free + be.pool.n_reclaimable == be.pool.n_blocks
    assert be.pool.n_reclaimable == be.pool.n_cached  # no live readers
    m = be.metrics.as_dict()
    assert m["requests_completed"] == len(specs)
    assert m["tokens_generated"] == sum(g for _, g in specs)
    assert m["ttft_s_count"] == len(specs)


def test_preemption_by_recompute_matches_golden(setup):
    """Oversubscribed pool: eviction + re-admission must reproduce the
    exact greedy continuation (recompute restores the KV state)."""
    _, config, engine = setup
    rng = np.random.default_rng(1)
    # 3 slots x (7 prompt + 8 gen = 15 tokens -> 4 blocks) but only 6
    # blocks: decode growth forces evictions.
    be = BatchEngine(engine, n_slots=3, n_blocks=6, block_size=4,
                     prefill_chunk=8, **PLAIN_PATH)
    prompts = [rng.integers(0, config.vocab_size, size=7).tolist()
               for _ in range(4)]
    rids = [be.submit(p, max_new_tokens=8) for p in prompts]
    out = be.run(max_steps=500)
    assert len(out) == 4
    m = be.metrics.as_dict()
    assert m["preemptions"] > 0, "pool was sized to force preemption"
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(np.asarray(out[rid], np.int32),
                                      _golden(engine, p, 8))
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    be.pool.check_invariants()


def test_priority_preempts_low_priority(setup):
    """A high-priority arrival into a full pool evicts low-priority work."""
    _, config, engine = setup
    rng = np.random.default_rng(2)
    be = BatchEngine(engine, n_slots=2, n_blocks=4, block_size=4,
                     prefill_chunk=8, **PLAIN_PATH)
    lo = [be.submit(rng.integers(0, config.vocab_size, size=6).tolist(),
                    max_new_tokens=6, priority=0) for _ in range(2)]
    be.step()                                    # both low-prio admitted
    hi = be.submit(rng.integers(0, config.vocab_size, size=6).tolist(),
                   max_new_tokens=6, priority=9)
    out = be.run(max_steps=500)
    assert set(out) == {*lo, hi}
    finished = be.finished
    # the high-priority request finished before at least one evictee
    assert finished[hi].finish_t < max(finished[r].finish_t for r in lo)
    assert finished[hi].n_preemptions == 0


# -- 5. the pool is updated where it lies -----------------------------------

_BS, _NB = 4, 40      # block size and pool blocks of the hand-made steps


def _paged_step(engine, kind, paged_attn, fmt="bf16"):
    """One hand-made paged step of ``forward_paged`` (through the step's
    own shard_map): 4 slots with slot 2 DEAD but holding stale table rows,
    shuffled tables, every arena of the pool's state filled with random
    data. ``fmt``: "bf16" (the K+V arena in the model dtype, a block's two
    planes side by side), "int8" (plus the scale arena) or "latent"
    (``engine`` a latent model: one arena of rows, no planes). Returns ``(sm, args, written)``: ``args[2]`` is the state and
    ``written`` the set of (block, line) the step's tables address for live
    tokens — the same in every layer."""
    c = engine.config
    rng = np.random.default_rng(7)
    B, L = 4, (1 if kind == "decode" else 4)
    max_blocks = min(c.max_length // _BS, _NB // B)
    planes = () if fmt == "latent" else (2,)
    shape = (c.n_layers, _NB, *planes, _BS, *c.kv_row_shapes[0])
    tables = rng.permutation(_NB)[:B * max_blocks].reshape(B, max_blocks)
    offsets = np.asarray([5, 0, 9, 3], np.int32)
    mask = np.asarray([True, True, False, True])
    seq_lens = np.asarray([4, 2, 3, 0] if L > 1 else [1] * B, np.int32)
    written = {(int(tables[b, (offsets[b] + l) // _BS]),
                int((offsets[b] + l) % _BS))
               for b in range(B) if mask[b] for l in range(seq_lens[b])}

    def rows():
        return jnp.asarray(rng.normal(size=shape), c.dtype)

    if fmt == "int8":
        state = PagedKVState(
            jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8),
            jnp.asarray(rng.uniform(0.01, 0.02, size=shape[:-1]),
                        jnp.float32))
    else:
        state = PagedKVState(kv=rows())
    ids = jnp.asarray(rng.integers(0, c.vocab_size, size=(B, L)), jnp.int32)
    args = [engine.params, ids, state, jnp.asarray(offsets),
            jnp.asarray(tables, jnp.int32), jnp.asarray(mask)]
    if kind == "prefill":
        args.append(jnp.asarray(seq_lens))
    sm = engine._make_sm(
        engine.decode_mode, paged=kind, paged_attn=paged_attn,
        state_specs=paged_state_specs(c, quant=fmt == "int8"))
    return sm, args, written


def _touched(arena, written):
    """The rows of a row arena that appends at the (block, line) pairs
    ``written`` may touch, in every layer: BOTH planes of the pair in a K+V
    arena ``(layers, blocks, 2, lines, ...)`` (or its scale arena), the row
    in a latent one ``(layers, blocks, lines, row)``."""
    paired = arena.shape[2] == 2 and arena.ndim >= 5
    touched = np.zeros(arena.shape[:4 if paired else 3], bool)
    for blk, line in written:
        touched[(slice(None), blk, slice(None), line) if paired
                else (slice(None), blk, line)] = True
    return touched


@pytest.mark.parametrize("fmt", ["bf16", "latent"])
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_paged_step_appends_in_place_fused_equals_gather(
        setup, latent_engine, kind, fmt):
    """One decode step and one mixed step on a pool full of data: the
    append touches exactly the (layer, block, line) rows the tables
    address for live tokens — a dead slot's stale table rows and positions
    past ``seq_lens`` write NOTHING (neither plane), every other byte of
    every layer is the input's — on the fused path (the kernel DMAs
    ``[layer, block]``, both planes in one copy, out of the carried arena)
    and on the gather oracle (``pool[layer]``) alike, for the K+V arena
    (ONE append writes the K plane and the V plane of the (block, line))
    and for a latent pool's arena. The two
    agree bit for bit on the first layer's appended rows and, past it, to
    the float32 rounding of their two softmax orders."""
    engine = latent_engine if fmt == "latent" else setup[2]
    logits, pools = {}, {}
    for paged_attn in ("fused", "gather"):
        sm, args, written = _paged_step(engine, kind, paged_attn, fmt)
        out, _, state = jax.jit(sm)(*args)
        assert jax.tree.structure(state) == jax.tree.structure(args[2])
        logits[paged_attn] = np.asarray(out)
        pools[paged_attn] = [np.asarray(a) for a in jax.tree.leaves(state)]
        assert written
        for before, after in zip(jax.tree.leaves(args[2]),
                                 pools[paged_attn]):
            before = np.asarray(before)
            touched = _touched(before, written)
            np.testing.assert_array_equal(after[~touched], before[~touched])
            # an appended row is new data, in every layer
            n = int(touched.sum())
            assert (after[touched] != before[touched]).reshape(n, -1).any(
                axis=-1).all()
    for f, g in zip(pools["fused"], pools["gather"]):
        np.testing.assert_array_equal(f[0], g[0])
        np.testing.assert_allclose(f, g, rtol=0, atol=1e-5)
    live = np.asarray(args[5]) & (np.asarray(args[-1]) > 0)
    np.testing.assert_allclose(logits["fused"][live],
                               logits["gather"][live], rtol=0, atol=1e-5)


@pytest.mark.parametrize("fmt", ["bf16", "int8", "latent"])
@pytest.mark.parametrize("paged_attn", ["fused", "gather"])
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_paged_pool_rides_the_layer_scan_as_carry(setup, latent_engine, kind,
                                                  paged_attn, fmt):
    """Structural guard (PERF.md, PR 26): in ``forward_paged`` the arenas
    of the pool's state — the K+V arena, a quantized pool's scale arena, a
    latent pool's one arena — appear in the layer scan ONLY among the
    carry. As ``xs``/``ys`` each layer of the pool is sliced out to feed
    the Pallas call and stacked back, five passes over both arenas a step
    on the chip; nothing else in tier-1 would notice."""
    engine = latent_engine if fmt == "latent" else setup[2]
    config = engine.config
    sm, args, _ = _paged_step(engine, kind, paged_attn, fmt)
    arena = tuple(args[2].kv.shape)
    pool_shapes = {arena, arena[1:], arena[:-1], arena[1:-1]}

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scans(sub)

    def n_pool(avals):
        return sum(tuple(v.aval.shape) in pool_shapes for v in avals)

    layer_scans = [e for e in scans(jax.make_jaxpr(sm)(*args).jaxpr)
                   if n_pool(e.invars)]
    assert len(layer_scans) == 1
    eqn, = layer_scans
    # the latent model's leading dense layers run before the scan
    assert eqn.params["length"] == config.n_layers - getattr(
        config, "n_dense_layers", 0)
    n_const, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    carry = eqn.invars[n_const:n_const + n_carry]
    n_arenas = len(jax.tree.leaves(args[2]))
    assert n_arenas == {"bf16": 1, "int8": 2, "latent": 1}[fmt]
    assert n_pool(carry) == n_arenas
    assert n_pool(eqn.invars) == n_arenas            # none in consts or xs
    assert n_pool(eqn.outvars[:n_carry]) == n_arenas
    assert n_pool(eqn.outvars[n_carry:]) == 0        # none in ys


@pytest.mark.parametrize("fmt,speculative", [
    ("model-dtype", False), ("model-dtype", True), ("int8", False),
    ("int8", True), ("latent", False)])
def test_the_steps_take_the_state_whole_and_return_one_record(
        setup, latent_engine, fmt, speculative):
    """The seam between ``KVPool``, ``BatchEngine`` and the model, for every
    pool format and with speculation on and off (the latent model builds no
    verify step): exactly two jitted steps, named ``decode_step`` and
    ``mixed_step``; the pool's state is their one donated operand, every
    leaf of it and nothing else; each returns ``(nxt, finite, greedy |
    None, state)`` with the model's ``step_stats`` appended to ``nxt`` and
    ``state`` of the structure of ``pool.state``; slots churn through both
    without a second trace."""
    engine = latent_engine if fmt == "latent" else setup[2]
    n_slots, chunk = 2, 8
    be = BatchEngine(engine, n_slots=n_slots, block_size=4,
                     prefill_chunk=chunk, paged_attn="gather",
                     kv_dtype="int8" if fmt == "int8" else None,
                     speculative=speculative)
    structure = jax.tree.structure(be.pool.state)
    assert structure == jax.tree.structure(be.pool.specs)
    assert len(jax.tree.leaves(be.pool.state)) == {
        "model-dtype": 1, "int8": 2, "latent": 1}[fmt]
    steps = {name: getattr(be, name)
             for name in ("_decode_step", "_mixed_step")}
    seen = {}

    def recording(name):
        step = steps[name]
        assert step.__name__ == name.lstrip("_")      # jit_decode_step, ...

        def call(*args):
            out = step(*args)
            assert isinstance(out, tuple) and len(out) == 4
            nxt, finite, greedy, state = out
            assert nxt.dtype == jnp.int32 and nxt.shape == (
                n_slots + len(engine.model.step_stats),)
            assert finite.dtype == bool and finite.shape == (n_slots,)
            if speculative and name == "_mixed_step":
                assert greedy.dtype == jnp.int32
                assert greedy.shape == (n_slots, chunk)
            else:
                assert greedy is None
            assert jax.tree.structure(state) == structure
            seen[name] = args
            return out
        return call

    be._decode_step = recording("_decode_step")
    be._mixed_step = recording("_mixed_step")
    rng = np.random.default_rng(5)
    vocab = engine.config.vocab_size
    # five requests over two slots, prompts longer and shorter than a chunk
    rids = [be.submit(rng.integers(0, vocab, size=n).tolist(),
                      max_new_tokens=4) for n in (11, 3, 9, 5, 13)]
    out = be.run(max_steps=200)
    assert set(out) == set(rids) and all(len(out[r]) == 4 for r in rids)
    assert set(seen) == {"_decode_step", "_mixed_step"}
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    assert jax.tree.structure(be.pool.state) == structure
    if engine.model.step_stats:
        assert be.metrics.counters["latent_rows_appended"] > 0

    # Donation, as declared in the lowering (the platform does not matter):
    # argument 2, the state, with every leaf of it; nothing else.
    for name, args in seen.items():
        args = args[:2] + (be.pool.state,) + args[3:]
        lowered = steps[name].lower(*args)
        infos, _ = lowered.args_info
        assert all(i.donated for i in jax.tree.leaves(infos[2]))
        assert len(jax.tree.leaves(infos[2])) == structure.num_leaves
        rest = infos[:2] + infos[3:]
        assert not any(i.donated for i in jax.tree.leaves(rest))
        assert f"@jit_{name.lstrip('_')}" in lowered.as_text()


# -- 6. the mixed step's two blocks -------------------------------------------

from triton_distributed_tpu.serving import batch_engine as _be_mod


def _small_block(monkeypatch, n_slots, chunk, rows):
    """Steer the derived height of the prefill block from the test: the
    budget is a module constant, not an option."""
    monkeypatch.setattr(_be_mod, "MIXED_STEP_TOKEN_BUDGET",
                        n_slots + rows * chunk)


def _recording(be, name="_mixed_step"):
    """Wrap a compiled step of ``be``: every call's host-side operands."""
    calls, step = [], getattr(be, name)

    def call(*args):
        calls.append(jax.tree.map(np.asarray, args[1:2] + args[3:7]))
        return step(*args)
    setattr(be, name, call)
    return calls


def _one_at_a_time(engine, prompts, gen, **kw):
    """The oracle: the same engine class serving each request ALONE (one
    row at a time: nothing waits, nothing shares a step). ``gen`` is one
    count for all or one a prompt; ONE engine serves them all."""
    out = []
    be = BatchEngine(engine, **kw)
    gens = [gen] * len(prompts) if isinstance(gen, int) else gen
    for p, g in zip(prompts, gens, strict=True):
        rid = be.submit(p, max_new_tokens=g)
        out.append(be.run(max_steps=400)[rid])
    assert be.metrics.counters.get("prefill_rows_deferred", 0) == 0
    return out


@pytest.mark.parametrize("n_slots,chunk,speculative,budget,rows", [
    (32, 64, False, 256, 3), (32, 64, False, 512, 7), (32, 64, False, 128, 1),
    (32, 64, False, 64, 1), (4, 8, False, 256, 4), (8, 64, False, 256, 3),
    (4, 8, True, 12, 4)])
def test_the_prefill_blocks_height_is_derived(setup, monkeypatch, n_slots,
                                              chunk, speculative, budget,
                                              rows):
    """``prefill_rows`` is what fits beside the decode block in the token
    budget, at least one row and never more than the slots; with
    speculation every slot has a row (any decode row may be a verify row).
    Nothing is compiled until a step is called."""
    monkeypatch.setattr(_be_mod, "MIXED_STEP_TOKEN_BUDGET", budget)
    be = BatchEngine(setup[2], n_slots=n_slots, n_blocks=8, block_size=4,
                     prefill_chunk=chunk, speculative=speculative)
    assert be.prefill_rows == rows


@pytest.mark.parametrize("model,paged_attn", [
    ("qwen", "gather"), ("qwen", "fused"), ("latent", "gather"),
    ("latent", "fused")])
def test_more_rows_prefilling_than_the_block_holds(
        setup, latent_engine, monkeypatch, model, paged_attn):
    """Churn with MORE rows prefilling at once than the prefill block
    holds (4 slots, a block of one row on the gather path and of two on
    the fused one, where three prompts arrive together): rows wait their
    turn, decode rows ride the decode block beside them, and every
    request's greedy stream equals the one it gets served alone; one
    compile a step across all of it."""
    engine = latent_engine if model == "latent" else setup[2]
    fused = paged_attn == "fused"
    n_slots, chunk, rows = 4, 8, (2 if fused else 1)
    _small_block(monkeypatch, n_slots, chunk, rows)
    kw = dict(n_slots=n_slots, block_size=4, prefill_chunk=chunk,
              paged_attn=paged_attn)
    rng = np.random.default_rng(11)
    specs = [(19, 2), (9, 2), (12, 2)] if fused else \
        [(19, 5), (9, 4), (12, 6), (3, 4), (26, 3), (17, 4)]
    prompts = [rng.integers(0, engine.config.vocab_size, size=n).tolist()
               for n, _ in specs]
    be = BatchEngine(engine, **kw)
    assert be.prefill_rows == rows
    calls = _recording(be)
    rids = [be.submit(p, max_new_tokens=g)
            for p, (_, g) in zip(prompts[:4], specs)]
    be.step(), be.step()
    rids += [be.submit(p, max_new_tokens=g)
             for p, (_, g) in zip(prompts[4:], specs[4:])]
    out = be.run(max_steps=400)
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    c = be.metrics.counters
    assert c["prefill_rows_deferred"] > 0
    assert max(int((sl > 1).sum()) for _, _, _, _, sl in calls) == rows
    # decode rows rode a step whose block was full
    assert any((sl == 1).any() and (sl > 1).sum() == rows
               for _, _, _, _, sl in calls)
    # the oracle on the gather path: token-identical to the fused one
    # (tests/test_paged_attention.py) at a thousandth of its cost here
    want = _one_at_a_time(engine, prompts, [g for _, g in specs],
                          **{**kw, **PLAIN_PATH})
    for i, rid in enumerate(rids):
        assert out[rid] == want[i], f"request {i} diverged"
    if model == "qwen":
        np.testing.assert_array_equal(
            np.asarray(out[rids[0]], np.int32),
            _golden(engine, prompts[0], specs[0][1]))
    be.pool.check_invariants()


@pytest.mark.parametrize("model", ["qwen", "latent"])
def test_deferral_is_oldest_admitted_first_and_counted(
        setup, latent_engine, monkeypatch, model):
    """Three prompts admitted together into a block of ONE row: each step
    the oldest admitted row still prefilling takes a chunk, the others
    take nothing and are counted; a decode row keeps its one token a step.
    ``mixed_step_tokens`` and ``prefill_rows_deferred`` (counters, and
    attributes of the ``mixed_step`` span) say what happened."""
    from triton_distributed_tpu.obs import trace as _trace

    engine = latent_engine if model == "latent" else setup[2]
    n_slots, chunk = 4, 8
    _small_block(monkeypatch, n_slots, chunk, 1)
    be = BatchEngine(engine, n_slots=n_slots, block_size=4,
                     prefill_chunk=chunk, paged_attn="gather")
    calls = _recording(be)
    rng = np.random.default_rng(13)
    lens = (20, 12, 10)
    rids = [be.submit(rng.integers(0, engine.config.vocab_size,
                                   size=n).tolist(), max_new_tokens=3)
            for n in lens]
    with _trace.tracing() as tracer:
        tracer.reset()
        be.run(max_steps=100)
        spans = [r for r in tracer.records if r.name == "mixed_step"]
    assert set(be.finished) == set(rids)
    # Admission order is submission order (FIFO, equal priority) and the
    # slots fill in order: slot i holds request i.
    takes = np.stack([sl for _, _, _, _, sl in calls])       # (steps, slots)
    assert takes.shape[1] == n_slots and not takes[:, 3].any()
    # 20 = 8+8+4, 12 = 8+4, 10 = 8+2: six steps with a row in the block
    want = [[8, 0, 0], [8, 0, 0], [4, 0, 0], [1, 8, 0], [1, 4, 0],
            [0, 1, 8], [0, 1, 2]]
    assert takes[:7, :3].tolist() == want
    deferred = [2, 2, 2, 1, 1, 0, 0]
    c = be.metrics.counters
    assert c["prefill_rows_deferred"] == sum(deferred)
    assert c["mixed_step_tokens"] == takes.sum()
    assert c["prefill_tokens"] == sum(lens)
    assert c["prefill_steps"] == len(calls) == len(spans)
    assert [s.attrs["prefill_rows_deferred"] for s in spans[:7]] == deferred
    assert [s.attrs["mixed_step_tokens"] for s in spans] == \
        takes.sum(axis=1).tolist()
    assert all(s.attrs["prefill_rows"] <= 1 for s in spans)


@pytest.mark.parametrize("model,paged_attn", [
    ("qwen", "gather"), ("qwen", "fused"), ("latent", "gather")])
def test_a_last_take_of_one_token_rides_the_decode_block(
        setup, latent_engine, monkeypatch, model, paged_attn):
    """A prompt one token longer than a chunk through a block of ONE row:
    its second take is ONE token, which needs no row of the prefill block
    — the step that serves it carries an empty block — and the stream is
    the one served by whole chunks of another width."""
    engine = latent_engine if model == "latent" else setup[2]
    chunk = 8
    _small_block(monkeypatch, 2, chunk, 1)
    kw = dict(n_slots=2, block_size=4, paged_attn=paged_attn)
    prompt = np.random.default_rng(17).integers(
        0, engine.config.vocab_size, size=chunk + 1).tolist()
    be = BatchEngine(engine, prefill_chunk=chunk, **kw)
    calls = _recording(be)
    rid = be.submit(prompt, max_new_tokens=3)
    out = be.run(max_steps=50)[rid]
    (_, chunk0, dealt0), _, _, _, sl0 = calls[0]
    (tok1, chunk1, dealt1), offsets, _, _, sl1 = calls[1]
    assert sl0.tolist() == [chunk, 0] and chunk0[0].tolist() == prompt[:chunk]
    assert dealt0.tolist() == [[0, 0, chunk]]
    assert sl1.tolist() == [1, 0] and offsets[0] == chunk
    assert tok1[0] == prompt[-1] and not chunk1.any()
    assert dealt1.tolist() == [[-1, 0, 0]]
    assert len(calls) == 2 and be.metrics.counters["prefill_tokens"] == 9
    other, = _one_at_a_time(engine, [prompt], 3, prefill_chunk=chunk + 4,
                            **kw)
    assert out == other


_DENSE: dict = {}


@pytest.mark.parametrize("fmt", ["bf16", "int8", "latent"])
@pytest.mark.parametrize("paged_attn", ["fused", "gather"])
@pytest.mark.parametrize("rows_a_slot", [1, 2])
def test_two_blocks_equal_the_dense_block(setup, latent_engine, paged_attn,
                                          fmt, rows_a_slot):
    """One hand-made mixed step both ways, on a pool full of data: as the
    dense (slots, L) block and as the two blocks (one token a slot, a block
    of rows the host has dealt to the slots that take more): the one row of
    L for slot 0 or, ``rows_a_slot`` 2, TWO rows of L / 2, consecutive
    chunks of the same sequence in one step. The same rows are appended,
    bit for bit in the first layer, the rest of the pool is the input's,
    and the live slots' logits agree; a slot that takes nothing and a dead
    slot with stale tables write nothing."""
    engine = latent_engine if fmt == "latent" else setup[2]
    sm, args, written = _paged_step(engine, "prefill", paged_attn, fmt)
    ids, seq_lens = np.asarray(args[1]), np.asarray([4, 1, 3, 0], np.int32)
    mask = np.asarray([True, True, False, True])
    offsets, tables = np.asarray(args[3]), np.asarray(args[4])
    written = {(int(tables[b, (offsets[b] + l) // _BS]),
                int((offsets[b] + l) % _BS))
               for b in range(4) if mask[b] for l in range(seq_lens[b])}
    args[5], args[6] = jnp.asarray(mask), jnp.asarray(seq_lens)
    # the dense block's result is the same for both ``rows_a_slot``
    if (paged_attn, fmt) not in _DENSE:
        dense_logits, _, dense = jax.jit(sm)(*args)
        _DENSE[paged_attn, fmt] = dense_logits, dense
    dense_logits, dense = _DENSE[paged_attn, fmt]
    # slot 0 is the one slot that takes more than a token: its 4 tokens
    # as rows of ``width``, beside a dead row and (to be refused by the
    # mask) a row dealt to the dead slot 2
    width = ids.shape[1] // rows_a_slot
    chunk = np.zeros((rows_a_slot + 2, width), np.int32)
    chunk[:rows_a_slot] = ids[0].reshape(rows_a_slot, width)
    dealt = [[0, offsets[0] + j * width, width] for j in range(rows_a_slot)]
    dealt += [[-1, 0, 0], [2, offsets[2], width]]
    three = (jnp.asarray(ids[:, 0]), jnp.asarray(chunk),
             jnp.asarray(dealt, jnp.int32))
    logits, _, state = jax.jit(sm)(args[0], three, *args[2:])
    assert jax.tree.structure(state) == jax.tree.structure(args[2])
    for before, d, t in zip(*map(jax.tree.leaves, (args[2], dense, state))):
        before, d, t = map(np.asarray, (before, d, t))
        touched = _touched(before, written)
        np.testing.assert_array_equal(t[~touched], before[~touched])
        np.testing.assert_array_equal(t[0], d[0])
        np.testing.assert_allclose(t.astype(np.float32),
                                   d.astype(np.float32), rtol=0, atol=1e-5)
    live = mask & (seq_lens > 0)
    np.testing.assert_allclose(np.asarray(logits)[live],
                               np.asarray(dense_logits)[live],
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("fmt", ["model-dtype", "int8", "latent"])
def test_mixed_step_keeps_no_pool_sized_temporary(setup, latent_engine, fmt):
    """The compiled two-block step (the CPU lowering: it says what is
    compiled, no time): the state is donated leaf for leaf and aliased to
    the output whole, and the temporaries are activations of ``T``
    positions, far under one arena — two appends and two attention calls
    on the carried state did not bring back a copy of the pool (PERF.md,
    PR 26)."""
    engine = latent_engine if fmt == "latent" else setup[2]
    be = BatchEngine(engine, n_slots=4, n_blocks=8192, block_size=4,
                     prefill_chunk=8, paged_attn="gather",
                     kv_dtype="int8" if fmt == "int8" else None)
    calls = []
    step = be._mixed_step
    be._mixed_step = lambda *a: calls.append(a) or step(*a)
    be.submit([1, 2, 3, 4, 5], max_new_tokens=1)
    be.run(max_steps=5)
    args = calls[0][:2] + (be.pool.state,) + calls[0][3:]
    lowered = step.lower(*args)
    infos, _ = lowered.args_info
    assert all(i.donated for i in jax.tree.leaves(infos[2]))
    mem = lowered.compile().memory_analysis()
    leaves = jax.tree.leaves(be.pool.state)
    pool_bytes = sum(a.nbytes for a in leaves)
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < min(a.nbytes for a in leaves
                                        if a.ndim == leaves[0].ndim) // 4


# -- 7. the host deals the prefill block's rows -------------------------------
# A prompt takes every free row of the block: the rows are dealt by the host
# as (slot, cache length before the row, live tokens) and several may be
# consecutive chunks of ONE sequence (``BatchEngine._run_mixed``,
# ``nn.paged_token_blocks``).

_DEAL_CASES = pytest.mark.parametrize("model,paged_attn", [
    ("qwen", "gather"), ("qwen", "fused"), ("latent", "gather")])


def _dealt_engine(monkeypatch, engine, paged_attn, rows, n_slots=4, chunk=8):
    """An engine whose prefill block has ``rows`` rows, and the host-side
    operands of every mixed step it dispatches."""
    _small_block(monkeypatch, n_slots, chunk, rows)
    be = BatchEngine(engine, n_slots=n_slots, block_size=4,
                     prefill_chunk=chunk, paged_attn=paged_attn)
    assert be.prefill_rows == rows
    return be, _recording(be)


def test_the_blocks_rows_come_from_the_host():
    """``nn.paged_token_blocks`` alone, two-block form: two rows of one
    slot get offsets ``o`` and ``o + L``, the slot's table twice, and
    ``last`` on the second row's last live token; a slot of one row and a
    decode row beside them; a dead row names no slot and a row dealt to a
    masked slot is dead."""
    from triton_distributed_tpu.layers import nn

    B, P, L = 4, 5, 4
    rng = np.random.default_rng(3)
    tables = rng.permutation(B * 6).reshape(B, 6).astype(np.int32)
    offsets = np.asarray([0, 5, 7, 2], np.int32)
    seq_lens = np.asarray([3, 6, 1, 4], np.int32)
    mask = np.asarray([True, True, True, False])
    dealt = np.asarray([[1, 5, 4], [1, 9, 2], [-1, 0, 0], [0, 0, 3],
                        [3, 2, 4]], np.int32)
    tok = np.arange(B, dtype=np.int32) + 100
    chunk = np.arange(P * L, dtype=np.int32).reshape(P, L)
    flat, (dec, pre), last = nn.paged_token_blocks(
        (tok, chunk, dealt), offsets, tables, mask, seq_lens, multiple=8)
    assert flat.shape == (24,) and flat[:B + P * L].tolist() == [
        *tok, *chunk.reshape(-1)]
    assert (dec.start, dec.L, pre.start, pre.L) == (0, 1, B, L)
    assert dec.mask.tolist() == [False, False, True, False]
    assert dec.offsets.tolist() == [0, 0, 7, 0]
    np.testing.assert_array_equal(dec.tables, tables)
    live = [True, True, False, True, False]
    assert pre.mask.tolist() == live
    assert pre.offsets.tolist() == [5, 9, 0, 0, 0]
    assert pre.seq_lens.tolist() == [4, 2, 0, 3, 0]
    assert np.asarray(pre.slots)[live].tolist() == [1, 1, 0]
    np.testing.assert_array_equal(np.asarray(pre.tables)[live],
                                  tables[[1, 1, 0]])
    # slot 1's last live token ends its SECOND row; slot 0's its one row;
    # the decode row's and the masked slot's are their own positions
    assert last.tolist() == [B + 3 * L + 2, B + 1 * L + 1, 2, 3]
    assert pre.valid().reshape(P, L).sum(axis=1).tolist() == [4, 2, 0, 3, 0]
    with pytest.raises(ValueError):
        nn.paged_token_blocks((tok, chunk), offsets, tables, mask, seq_lens)


@_DEAL_CASES
def test_a_prompt_takes_every_free_row_of_the_block(
        setup, latent_engine, monkeypatch, model, paged_attn):
    """A prompt of 20 tokens at chunks of 8: through a block of ONE row it
    takes 8, 8, 4 in three mixed steps; through a block of THREE rows it
    takes all 20 in one, as rows (0, 8), (8, 8), (16, 4) of the one slot.
    Token for token the same stream, and the golden's."""
    engine = latent_engine if model == "latent" else setup[2]
    prompt = np.random.default_rng(19).integers(
        0, engine.config.vocab_size, size=20).tolist()
    outs, takes, steps = {}, {}, {}
    for rows in (1, 3):
        be, calls = _dealt_engine(monkeypatch, engine, paged_attn, rows)
        rid = be.submit(prompt, max_new_tokens=4)
        outs[rows] = be.run(max_steps=50)[rid]
        takes[rows] = [int(sl[0]) for *_, sl in calls]
        steps[rows] = dict(be.metrics.counters)
        assert be.trace_counts == {"decode": 1, "prefill": 1}
        be.pool.check_invariants()
    assert takes == {1: [8, 8, 4], 3: [20]}
    assert outs[3] == outs[1] and len(outs[3]) == 4
    if model == "qwen":
        np.testing.assert_array_equal(np.asarray(outs[3], np.int32),
                                      _golden(engine, prompt, 4))
    (_, chunk, dealt), offsets, _, _, sl = calls[0]
    assert dealt.tolist() == [[0, 0, 8], [0, 8, 8], [0, 16, 4]]
    assert chunk.reshape(-1)[:20].tolist() == prompt
    assert not offsets.any() and sl.tolist() == [20, 0, 0, 0]
    for rows, filled, extra in ((1, 3, 0), (3, 3, 2)):
        c = steps[rows]
        assert c["prefill_steps"] == len(takes[rows])
        assert c["prefill_tokens"] == c["mixed_step_tokens"] == 20
        assert (c["prefill_rows_filled"], c["prefill_rows_extra"],
                c["prefill_rows_deferred"]) == (filled, extra, 0)


@_DEAL_CASES
def test_the_deal_is_one_row_each_then_the_free_rows_to_the_oldest(
        setup, latent_engine, monkeypatch, model, paged_attn):
    """Three prompts admitted together into a block of FOUR rows: one row
    each first, and the fourth goes to the oldest that can fill it; no
    request's take is ever below the one row it took before; into a block
    of TWO rows the same prompts are dealt as they were (the two oldest a
    row each, the third waits and is counted). The counters and the
    ``mixed_step`` span say what happened, and every stream is the one
    the request gives alone."""
    from triton_distributed_tpu.obs import trace as _trace

    engine = latent_engine if model == "latent" else setup[2]
    rng = np.random.default_rng(23)
    lens, chunk = (28, 12, 10), 8
    prompts = [rng.integers(0, engine.config.vocab_size, size=n).tolist()
               for n in lens]
    want = _one_at_a_time(engine, prompts, 3, n_slots=4, block_size=4,
                          prefill_chunk=chunk, paged_attn="gather")
    plans = {
        # rows: (takes a step, rows filled / extra / deferred a step)
        4: ([[16, 8, 8], [12, 4, 2]], [(4, 1, 0), (4, 1, 0)]),
        2: ([[8, 8, 0], [8, 4, 0], [8, 1, 8], [4, 1, 2]],
            [(2, 0, 1), (2, 0, 1), (2, 0, 0), (2, 0, 0)]),
    }
    for rows, (takes, filled) in plans.items():
        be, calls = _dealt_engine(monkeypatch, engine, paged_attn, rows)
        rids = [be.submit(p, max_new_tokens=3) for p in prompts]
        with _trace.tracing() as tracer:
            tracer.reset()
            out = be.run(max_steps=100)
            spans = [r.attrs for r in tracer.records
                     if r.name == "mixed_step"]
        assert [out[r] for r in rids] == want
        got = np.stack([sl for *_, sl in calls])
        assert got[:len(takes), :3].tolist() == takes and not got[:, 3].any()
        # never less than the one (narrowed) row a request took before
        left = np.asarray(lens)
        for step in got[:, :3]:
            served = step > 0
            assert (step[served] >= np.minimum(chunk, left)[served]).all()
            left = np.maximum(left - step, 0)
        assert [(a["prefill_rows_filled"], a["prefill_rows_extra"],
                 a["prefill_rows_deferred"])
                for a in spans[:len(filled)]] == filled
        c = be.metrics.counters
        for k, name in enumerate(("prefill_rows_filled",
                                  "prefill_rows_extra",
                                  "prefill_rows_deferred")):
            assert c[name] == sum(f[k] for f in filled), name
        assert c["prefill_tokens"] == sum(lens)
        assert c["mixed_step_tokens"] == got.sum()
        assert c["prefill_steps"] == len(calls) == len(spans)
        snap = be.stats_snapshot()["prefill_block"]
        assert snap["rows"] == rows
        assert snap["prefill_rows_extra"] == c["prefill_rows_extra"]
        if rows == 4:
            dealt = [ids[2].tolist() for ids, *_ in calls[:2]]
            assert dealt == [
                [[0, 0, 8], [0, 8, 8], [1, 0, 8], [2, 0, 8]],
                [[0, 16, 8], [0, 24, 4], [1, 8, 4], [2, 8, 2]]]
        be.pool.check_invariants()


@_DEAL_CASES
def test_a_take_of_several_rows_is_taken_back_whole(
        setup, latent_engine, monkeypatch, model, paged_attn):
    """In the middle of a prompt that takes three rows a step: a read that
    fails takes the host's count back by the WHOLE take of both steps in
    flight (``_unwind``), and an eviction requeues the request to prefill
    from the start; either way the stream is the undisturbed one."""
    engine = latent_engine if model == "latent" else setup[2]
    prompt = np.random.default_rng(29).integers(
        0, engine.config.vocab_size, size=28).tolist()
    calm, _ = _dealt_engine(monkeypatch, engine, "gather", 3, chunk=4)
    rid = calm.submit(prompt, max_new_tokens=3)
    want = calm.run(max_steps=50)[rid]

    be, calls = _dealt_engine(monkeypatch, engine, paged_attn, 3, chunk=4)
    rid = be.submit(prompt, max_new_tokens=3)
    be.step()                                   # 12 tokens in flight
    assert be._slots[0].offset == 12 and be._inflight.rows[0].written == 12

    def lost(x):
        raise RuntimeError("device lost")

    with monkeypatch.context() as m:
        m.setattr(jax, "device_get", lost)
        with pytest.raises(RuntimeError, match="device lost"):
            be.step()                           # 12 more, then the read fails
    assert be._slots[0].offset == 0 and be._inflight is None
    be.step(), be.step()
    assert be._slots[0].offset == 24
    be._preempt(0)                              # reads the step in flight
    assert be._slots[0] is None
    assert be.scheduler.pending()[0].n_preemptions == 1
    assert be.run(max_steps=50)[rid] == want
    assert [int(sl[0]) for *_, sl in calls if sl[0] > 1] == [12] * 6 + [4]
    c = be.metrics.counters
    # the failed step and the one behind it were never read (nor counted)
    assert c["prefill_tokens"] == 24 + 28 and c["preemptions"] == 1
    be.pool.check_invariants()


def test_a_narrowed_budget_keeps_one_narrowed_row(setup, monkeypatch):
    """With the controller's ``prefill_budget`` below the chunk a request
    keeps ONE narrowed row a step, as before the deal (the knob says how
    much prompt a step may take beside the decode rows); back at the
    chunk's width the prompt takes the free rows again."""
    engine = setup[2]
    prompt = np.random.default_rng(43).integers(
        0, engine.config.vocab_size, size=26).tolist()
    be, calls = _dealt_engine(monkeypatch, engine, "gather", 4)
    be.prefill_budget = 3
    rid = be.submit(prompt, max_new_tokens=3)
    be.step(), be.step()
    be.prefill_budget = 8
    out = be.run(max_steps=50)[rid]
    assert [int(sl[0]) for *_, sl in calls] == [3, 3, 20]
    assert [ids[2][:3].tolist() for ids, *_ in calls] == [
        [[0, 0, 3], [-1, 0, 0], [-1, 0, 0]],
        [[0, 3, 3], [-1, 0, 0], [-1, 0, 0]],
        [[0, 6, 8], [0, 14, 8], [0, 22, 4]]]
    assert be.metrics.counters["prefill_rows_extra"] == 2
    np.testing.assert_array_equal(np.asarray(out, np.int32),
                                  _golden(engine, prompt, 3))
