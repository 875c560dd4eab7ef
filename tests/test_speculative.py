"""Speculative decoding tests (serving/speculative.py + the BatchEngine
verify wiring).

The load-bearing guarantees (docs/serving.md, "Speculative decoding"):
  1. LOSSLESS — greedy output is bit-identical to the non-speculative
     engine (and therefore to N independent single-sequence ``Engine``
     runs), through staggered arrivals, preemption churn, rejection
     rollback, and chaos quarantine;
  2. ONE compile — verify rows ride the existing mixed step as ragged
     ``seq_lens`` data: ``trace_counts`` stays {decode: 1, prefill: 1}
     no matter how draft widths churn;
  3. rollback soundness — ``KVPool.truncate`` returns exactly the
     now-empty tail blocks, never corrupts cache-adopted blocks, and
     ``check_invariants`` holds after every rejection;
  4. drafter determinism — ``adopt(prompt + output)`` lands on the same
     tables as the original adopt + observe timeline, so preempted /
     requeued / fleet-migrated requests propose identically;
  5. acceptance accounting — with a scripted drafter the accept/reject
     stream is exact: counters, histograms, and controller k moves are
     fully predictable.

Accounting, rollback, drafters and requeue are host logic: the engines here
take the plain path (``conftest.PLAIN_PATH``). Two cases stay on "fused",
their subject being the kernel under a verify row:
``test_spec_ngram_bit_identical_with_preemption`` (ragged verify rows through
the fused mixed step, with rollback's stale rows behind them) and the
quantized rows of ``test_spec_rollback_then_prefix_cache_warm_equals_cold``
(rolled-back rows dequantized in the kernel's staging).
"""

import jax
import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.resilience import FaultPlan, FaultSpec, faults
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import (
    BatchEngine,
    Controller,
    Fleet,
    KVPool,
    LearnedHeadDrafter,
    NGramDrafter,
    RadixPrefixCache,
    ScriptedDrafter,
    SpecController,
    Speculative,
)


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


def _golden(engine, prompt, gen_len):
    out = engine.serve(np.asarray([prompt], np.int32), gen_len=gen_len)
    return np.asarray(out)[0]


def _golden_drafter(engine, prompts, gen_lens, *, offset=0, rids=None):
    """ScriptedDrafter that proposes the request's own golden
    continuation (``offset=0`` => every draft accepted) or a token-
    shifted corruption (``offset=1`` => every draft rejected at
    position 0). Exact accept/reject control for accounting tests."""
    if rids is None:
        rids = range(len(prompts))
    gold = {rid: _golden(engine, p, g).tolist()
            for rid, p, g in zip(rids, prompts, gen_lens)}
    plen = {rid: len(p) for rid, p in zip(rids, prompts)}
    vocab = engine.config.vocab_size

    def fn(rid, hist, max_k):
        done = len(hist) - plen[rid]
        nxt = gold[rid][done:done + max_k]
        return [(t + offset) % vocab for t in nxt]

    return ScriptedDrafter(fn), gold


# -- 3. KVPool.truncate ------------------------------------------------------

def test_truncate_frees_tail_blocks(setup):
    _, config, _ = setup
    pool = KVPool(config, n_blocks=10, block_size=4, max_seq_len=32)
    assert pool.ensure("a", 11)            # 3 blocks
    assert pool.owned("a") == 3 and pool.n_free == 7
    # still covered by 3 blocks: nothing to free
    assert pool.truncate("a", 9) == 0
    assert pool.owned("a") == 3
    pool.check_invariants()
    # 5 tokens fit in 2 blocks: exactly one tail block returns
    assert pool.truncate("a", 5) == 1
    assert pool.owned("a") == 2 and pool.n_free == 8
    pool.check_invariants()
    # down to a single block
    assert pool.truncate("a", 1) == 2 - 1
    assert pool.owned("a") == 1 and pool.n_free == 9
    pool.check_invariants()
    # rollback never grows, never empties, never invents sequences
    with pytest.raises(ValueError):
        pool.truncate("a", 12)
    with pytest.raises(ValueError):
        pool.truncate("a", 0)
    with pytest.raises(KeyError):
        pool.truncate("ghost", 4)
    pool.release("a")
    with pytest.raises(KeyError):
        pool.truncate("a", 4)              # released == unknown
    pool.check_invariants()


def test_truncate_decrefs_cache_adopted_blocks(setup):
    """Rolling back over blocks adopted from the prefix cache must
    DECREF them (they stay resident for future hits), while private tail
    blocks go back to the free list."""
    _, config, _ = setup
    pool = KVPool(config, n_blocks=8, block_size=4, max_seq_len=32)
    cache = RadixPrefixCache(pool)
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    assert pool.ensure("warm", len(toks))
    cache.insert("warm", toks)
    pool.release("warm")                   # 2 blocks, cached + 0 refs
    assert pool.n_cached == 2
    m = cache.match(toks, max_len=len(toks))
    assert len(m.blocks) == 2
    assert pool.ensure("b", 9, adopt=m.blocks, cow_src=m.cow_src)
    assert pool.owned("b") == 3            # 2 adopted + 1 private
    pool.check_invariants()
    free0 = pool.n_free
    # drop the private tail: a real free
    assert pool.truncate("b", 8) == 1
    assert pool.n_free == free0 + 1
    pool.check_invariants()
    # drop a cache-adopted block: decref only — NOT freed
    assert pool.truncate("b", 4) == 0
    assert pool.n_free == free0 + 1
    assert pool.n_cached == 2              # both blocks still resident
    pool.check_invariants()
    pool.release("b")
    pool.check_invariants()


# -- 4. drafter determinism --------------------------------------------------

def test_ngram_adopt_equals_replay():
    """adopt(prompt + output) == adopt(prompt) then observe(each output
    token): the structural property that makes preemption recompute and
    fleet requeue propose identically."""
    full = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 1, 4, 1, 5]
    for cut in (0, 4, 9, len(full)):
        a, b = NGramDrafter(), NGramDrafter()
        a.adopt("r", full)
        b.adopt("r", full[:cut])
        for t in full[cut:]:
            b.observe("r", t)
        assert a.fingerprint("r") == b.fingerprint("r")
        assert a._hist["r"] == b._hist["r"]
        assert a._occ["r"] == b._occ["r"]
        for k in (1, 2, 4, 8):
            assert a.propose("r", k) == b.propose("r", k)
    # re-adoption rebuilds from scratch, never merges survivors
    a.adopt("r", full[:5])
    b = NGramDrafter()
    b.adopt("r", full[:5])
    assert a.fingerprint("r") == b.fingerprint("r")


def test_ngram_proposes_prior_continuation():
    d = NGramDrafter()
    d.adopt("r", [7, 8, 9, 1, 2, 7, 8, 9])
    # trailing 3-gram (7,8,9) previously ended at index 2 -> continue 1,2,7
    assert d.propose("r", 3) == [1, 2, 7]
    assert d.propose("r", 8) == [1, 2, 7, 8, 9]
    assert d.propose("r", 0) == []
    d.release("r")
    assert d.propose("r", 4) == []
    assert d.fingerprint("r") == ()


def test_learned_head_drafter_is_declared_interface():
    d = LearnedHeadDrafter()
    with pytest.raises(NotImplementedError):
        d.adopt("r", [1, 2, 3])
    ok = LearnedHeadDrafter(head_fn=lambda rid, hist, k: hist[-k:])
    ok.adopt("r", [1, 2, 3, 4])
    assert ok.propose("r", 2) == [3, 4]


# -- adaptive-k controller ---------------------------------------------------

def test_spec_controller_hysteresis():
    c = SpecController(k_init=2, k_max=8, window=8, min_samples=4,
                       grow_cooldown=4)
    assert c.k_for("r") == 2
    # sustained full acceptance: grows by 1, at most once per cooldown
    for _ in range(4):
        c.record("r", 2, 2)
    assert c.k_for("r") == 3 and c.grows == 1
    for _ in range(3):
        c.record("r", 3, 3)
    assert c.k_for("r") == 3               # cooldown holds
    c.record("r", 3, 3)
    assert c.k_for("r") == 4 and c.grows == 2
    # collapse: rejections must first drown out the windowed full-accept
    # history (5 x (4,0) against the surviving (3,3) entries tips the
    # rate under shrink_at), then k halves immediately
    for _ in range(5):
        c.record("r", 4, 0)
    assert c.k_for("r") == 2 and c.shrinks == 1 and c.reversals == 1
    for _ in range(3):
        c.record("r", 2, 0)
    assert c.k_for("r") == 2               # post-shrink evidence demanded
    c.record("r", 2, 0)
    assert c.k_for("r") == 1 and c.shrinks == 2
    # the SLO-side cap clamps without touching acceptance state
    c2 = SpecController(k_init=6)
    c2.k_cap = 2
    assert c2.k_for("x") == 2
    c2.k_cap = 8
    assert c2.k_for("x") == 6
    # static arms never move
    st = SpecController(k_init=4, adaptive=False)
    for _ in range(16):
        st.record("r", 4, 0)
    assert st.k_for("r") == 4 and st.shrinks == 0


# -- 1+2. lossless + one-compile --------------------------------------------

def test_spec_k0_bit_identical(setup):
    """Width-zero speculation (the spec machinery on, proposing nothing)
    must be indistinguishable from the plain engine."""
    _, config, engine = setup
    rng = np.random.default_rng(7)
    plan = Speculative(drafter=NGramDrafter(),
                       controller=SpecController(k_init=0, adaptive=False))
    be = BatchEngine(engine, n_slots=4, block_size=4, prefill_chunk=8,
                     speculative=plan, **PLAIN_PATH)
    specs = [(5, 6), (3, 5), (7, 4), (4, 6)]
    prompts = [rng.integers(0, config.vocab_size, size=n).tolist()
               for n, _ in specs]
    rids = [be.submit(p, g) for p, (_, g) in zip(prompts, specs)]
    out = be.run(max_steps=300)
    for rid, p, (_, g) in zip(rids, prompts, specs):
        np.testing.assert_array_equal(np.asarray(out[rid], np.int32),
                                      _golden(engine, p, g))
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    m = be.metrics.as_dict()
    assert "spec_proposed_tokens" not in m
    assert be.perfdb_sample()["spec_accept_rate"] == 0.0


def test_spec_ngram_bit_identical_with_preemption(setup):
    """The real thing: n-gram drafts + fused verify + rollback, on an
    oversubscribed pool that forces preemption-by-recompute, over a long
    (64+ decode steps) repetitive request that the drafter can actually
    hit — output must equal the single-sequence golden run, with ONE
    compile per step shape."""
    mesh, config, engine = setup
    rng = np.random.default_rng(2)
    # same params, longer dense reference cache: the module engine's
    # serve() caps prompt+gen at 32, the 66-token run needs more
    eng_long = Engine(config, mesh=mesh, mode="xla", block_n=8,
                      max_length=128, params=engine.params)
    # the long request alone needs 19 blocks; three concurrent slots
    # want up to 27 — decode growth forces evictions.
    be = BatchEngine(engine, n_slots=3, n_blocks=22, block_size=4,
                     prefill_chunk=8, max_seq_len=96, speculative=True)
    # one long repetitive prompt (n-gram fuel) + random churn neighbors
    prompts = [[5, 6, 7, 5, 6, 7, 5, 6]]
    gens = [66]
    for _ in range(3):
        prompts.append(rng.integers(0, config.vocab_size,
                                    size=int(rng.integers(4, 8))).tolist())
        gens.append(int(rng.integers(5, 9)))
    rids = [be.submit(p, g) for p, g in zip(prompts, gens)]
    out = be.run(max_steps=800)
    assert len(out) == len(prompts)
    for rid, p, g in zip(rids, prompts, gens):
        np.testing.assert_array_equal(
            np.asarray(out[rid], np.int32), _golden(eng_long, p, g),
            err_msg=f"request {rid} diverged under speculation")
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    be.pool.check_invariants()
    m = be.metrics.as_dict()
    assert m.get("spec_proposed_tokens", 0) > 0, \
        "the repetitive request should have drawn proposals"
    snap = be.stats_snapshot()
    assert snap["spec"]["drafter"] == "ngram"
    assert snap["spec"]["proposed"] == m["spec_proposed_tokens"]


def test_scripted_full_accept_exact_accounting(setup):
    """Drafting the model's own golden continuation: every draft
    accepts, every verify step emits k+1 tokens, the acceptance
    histogram is exactly 1.0, and k grows on the cooldown schedule."""
    _, config, engine = setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, config.vocab_size, size=5).tolist()
               for _ in range(2)]
    gens = [24, 24]
    drafter, gold = _golden_drafter(engine, prompts, gens)
    plan = Speculative(drafter=drafter,
                       controller=SpecController(k_init=2, adaptive=False))
    be = BatchEngine(engine, n_slots=2, block_size=4, prefill_chunk=8,
                     speculative=plan, **PLAIN_PATH)
    rids = [be.submit(p, g, req_id=i) for i, (p, g)
            in enumerate(zip(prompts, gens))]
    out = be.run(max_steps=200)
    for i, rid in enumerate(rids):
        assert out[rid] == gold[i]
    m = be.metrics.as_dict()
    assert m["spec_proposed_tokens"] == m["spec_accepted_tokens"] > 0
    assert "spec_rollback_tokens" not in m      # nothing ever rejected
    # every verify outcome was a full accept
    w = be.metrics.window("spec_accept_ratio", 3600.0)
    assert w["p50"] == 1.0 and w["p99"] == 1.0
    assert be.perfdb_sample()["spec_accept_rate"] == 1.0
    ctl = plan.controller
    assert ctl.verify_steps == m["spec_verify_rows"]
    assert m["tokens_generated"] == sum(gens)
    for kind, n in be.trace_counts.items():
        assert n <= 1, f"retraced {kind}"


def test_scripted_full_reject_exact_accounting(setup):
    """Drafting always-wrong tokens: every draft rejects at position 0,
    the bonus token alone advances the stream (still bit-identical),
    and every rejection rolls the pool back."""
    _, config, engine = setup
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, config.vocab_size, size=5).tolist()]
    gens = [12]
    drafter, gold = _golden_drafter(engine, prompts, gens, offset=1)
    plan = Speculative(drafter=drafter,
                       controller=SpecController(k_init=1, adaptive=False))
    be = BatchEngine(engine, n_slots=1, block_size=4, prefill_chunk=8,
                     speculative=plan, **PLAIN_PATH)
    rid = be.submit(prompts[0], gens[0], req_id=0)
    out = be.run(max_steps=100)
    assert out[rid] == gold[0]
    m = be.metrics.as_dict()
    # 12 tokens: 1 prefill + 11 decode steps; the last decode step has
    # remaining_new == 1 so drafting is capped to 0 => 10 verify rows,
    # each proposing 1 and accepting 0.
    assert m["spec_verify_rows"] == 10
    assert m["spec_proposed_tokens"] == 10
    assert m["spec_accepted_tokens"] == 0
    assert m["spec_rollback_tokens"] == 10
    w = be.metrics.window("spec_accept_ratio", 3600.0)
    assert w["p50"] == 0.0 and w["p99"] == 0.0
    be.pool.check_invariants()


def test_spec_adaptive_shrinks_to_zero_on_rejection(setup):
    """Adaptive controller vs a hostile drafter: k collapses to 0 (spec
    off for the request) instead of burning verify width forever."""
    _, config, engine = setup
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, config.vocab_size, size=5).tolist()]
    gens = [20]
    drafter, gold = _golden_drafter(engine, prompts, gens, offset=1)
    plan = Speculative(drafter=drafter,
                       controller=SpecController(k_init=2, min_samples=3))
    be = BatchEngine(engine, n_slots=1, block_size=4, prefill_chunk=8,
                     speculative=plan, **PLAIN_PATH)
    rid = be.submit(prompts[0], gens[0], req_id=0)
    out = be.run(max_steps=100)
    assert out[rid] == gold[0]
    assert plan.controller.shrinks >= 1
    m = be.metrics.as_dict()
    # after the collapse the engine stops proposing: far fewer proposals
    # than the 19 decode steps would allow
    assert m["spec_proposed_tokens"] < 19
    assert m["spec_accepted_tokens"] == 0


@pytest.mark.parametrize("kv_dtype", [
    None, "int8", pytest.param("fp8", marks=pytest.mark.slow)])
def test_spec_rollback_then_prefix_cache_warm_equals_cold(setup, kv_dtype):
    """A finished request whose KV went through rejection rollbacks
    inserts its blocks into the prefix cache; a warm re-run adopting
    those blocks must match the cold output exactly — truncate never
    poisons what the cache will later share. The quantized rows replay
    the same contract on int8/fp8 arenas: truncate decrefs scale blocks
    in lockstep with wire blocks, so a rolled-back-then-cached block
    still dequantizes to the cold run's exact values (the f32 golden
    comparison is skipped there — quantized storage perturbs tokens)."""
    _, config, engine = setup
    rng = np.random.default_rng(6)
    p = rng.integers(0, config.vocab_size, size=9).tolist()
    prompts, gens = [p, p], [10, 10]
    drafter, gold = _golden_drafter(engine, prompts, gens, offset=1,
                                    rids=["cold", "warm"])
    plan = Speculative(drafter=drafter,
                       controller=SpecController(k_init=2, adaptive=False))
    be = BatchEngine(engine, n_slots=2, block_size=4, prefill_chunk=8,
                     speculative=plan, kv_dtype=kv_dtype,
                     **(PLAIN_PATH if kv_dtype is None else {}))
    be.submit(prompts[0], gens[0], req_id="cold")
    cold = be.run(max_steps=100)
    assert be.metrics.as_dict()["spec_rollback_tokens"] > 0
    be.submit(prompts[0], gens[0], req_id="warm")
    warm = be.run(max_steps=100)
    assert warm["warm"] == cold["cold"]
    if kv_dtype is None:
        assert cold["cold"] == gold["cold"]
    assert be.metrics.as_dict()["prefix_hits"] >= 1
    be.pool.check_invariants()
    for kind, n in be.trace_counts.items():
        assert n <= 1, f"retraced {kind}"


def test_spec_chaos_quarantine_leaves_survivors_bit_identical(setup):
    """NaN-poison one verify row: that request quarantines, the
    survivors (whose drafts keep verifying in the same fused steps)
    stay bit-identical, and nothing retraces."""
    _, config, engine = setup
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, config.vocab_size, size=5).tolist()
               for _ in range(3)]
    gens = [8, 8, 8]
    drafter, gold = _golden_drafter(engine, prompts, gens)
    plan = Speculative(drafter=drafter,
                       controller=SpecController(k_init=2, adaptive=False))
    be = BatchEngine(engine, n_slots=3, block_size=4, prefill_chunk=8,
                     speculative=plan, **PLAIN_PATH)
    for i, (p, g) in enumerate(zip(prompts, gens)):
        be.submit(p, g, req_id=i)
    # with full-accept k=2 drafting every decode step is a verify row
    # riding the MIXED step: poison slot 0 there, once
    fplan = FaultPlan([FaultSpec(site="engine.prefill", kind="nan", p=1.0,
                                 row=0, start_after=1, max_fires=1)])
    with faults.plan(fplan):
        out = be.run(max_steps=200)
    assert fplan.n_fired == 1
    assert set(be.failed) == {0}
    assert "non-finite" in be.failed[0].error
    for i in (1, 2):
        assert out[i] == gold[i]
    for kind, n in be.trace_counts.items():
        assert n <= 1, f"retraced {kind}"
    be.pool.check_invariants()
    assert be.pool.n_free + be.pool.n_reclaimable == be.pool.n_blocks


def test_spec_requires_greedy(setup):
    _, config, engine = setup
    t0 = engine.temperature
    engine.temperature = 0.7
    try:
        with pytest.raises(ValueError, match="temperature"):
            BatchEngine(engine, n_slots=2, speculative=True)
    finally:
        engine.temperature = t0


# -- serving-controller integration -----------------------------------------

def test_controller_spec_k_cap_knob(setup):
    """SLO pressure shrinks the speculative width cap; a clean OK streak
    relaxes it back — and the actuation lands on the engine's
    SpecController."""
    _, config, engine = setup
    be = BatchEngine(engine, n_slots=2, block_size=4, prefill_chunk=8,
                     speculative=True, **PLAIN_PATH)
    ctl = Controller(engine=be)
    assert "spec_k_cap" in ctl.knobs
    k_max = be.spec.controller.k_max
    assert be.spec.controller.k_cap == k_max

    def obs(level):
        return {"level": level, "decode_rows": 2, "prefill_rows": 0,
                "backlog_tokens": 0, "queue": 0, "free_frac": 0.9,
                "step": 0, "dead": ()}

    ctl.tick(obs(1))
    assert be.spec.controller.k_cap < k_max
    shrunk = be.spec.controller.k_cap
    # sustained pressure keeps shrinking toward 0
    for _ in range(6):
        ctl.tick(obs(2))
    assert be.spec.controller.k_cap <= shrunk
    # recovery: after the relax streak the cap returns to k_max
    for _ in range(20):
        ctl.tick(obs(0))
    assert be.spec.controller.k_cap == k_max
    # non-speculative engines keep the stock knob set
    be2 = BatchEngine(engine, n_slots=2, block_size=4, prefill_chunk=8,
                      **PLAIN_PATH)
    assert "spec_k_cap" not in Controller(engine=be2).knobs


# -- fleet: kill + requeue determinism ---------------------------------------

def test_fleet_kill_requeue_spec_bit_identical(setup):
    """Replica 0 dies mid-decode with speculation on everywhere; the
    requeued requests re-adopt their drafters on the survivors and every
    output still matches the single-sequence golden run."""
    from triton_distributed_tpu.resilience import default_fleet_chaos_plan
    _, config, engine = setup
    fleet = Fleet.build(engine, n_replicas=3, n_slots=2, n_blocks=16,
                        block_size=4, prefill_chunk=8, fail_threshold=2,
                        speculative=True, **PLAIN_PATH)
    rng = np.random.default_rng(9)
    specs = []
    for i in range(8):
        if i % 2:
            specs.append(([5, 6, 7, 5, 6, 7, 5, 6], 8))   # n-gram fuel
        else:
            specs.append((rng.integers(0, config.vocab_size,
                                       size=int(rng.integers(4, 9))
                                       ).tolist(),
                          int(rng.integers(4, 7))))
    rids = [fleet.submit(p, max_new_tokens=g) for p, g in specs]
    plan = default_fleet_chaos_plan(seed=0, kill_replica=0, kill_after=4)
    with faults.plan(plan):
        while fleet.step() or fleet.pending:
            fleet.check_invariants()
            assert fleet.n_steps < 2000
    assert not fleet.failed, f"unexpected failures: {fleet.failed}"
    out = {rid: list(req.output) for rid, req in fleet.finished.items()}
    for rid, (p, g) in zip(rids, specs):
        np.testing.assert_array_equal(
            np.asarray(out[rid], np.int32), _golden(engine, p, g),
            err_msg=f"request {rid} diverged after requeue")
    for rep in fleet.replicas:
        for kind, n in rep.engine.trace_counts.items():
            assert n <= 1, f"replica {rep.idx} retraced {kind}"
    # the fleet rollups see speculation
    snap = fleet.stats_snapshot()
    assert "spec" in snap and snap["spec"]["proposed"] >= 0
    assert "spec_accept_rate" in fleet.perfdb_sample()


def test_fleet_requeue_drafter_fingerprint_matches_fresh_adopt():
    """The migration witness in isolation: re-adopting (prompt + output
    so far) on ANOTHER drafter instance reproduces the original
    instance's tables exactly."""
    prompt = [5, 6, 7, 5, 6, 7]
    emitted = [5, 6, 7, 5, 6]
    original = NGramDrafter()
    original.adopt("r", prompt)
    for t in emitted:
        original.observe("r", t)
    # the request carries prompt+output across the requeue; the new
    # replica's drafter sees only that
    migrated = NGramDrafter()
    migrated.adopt("r", prompt + emitted)
    assert migrated.fingerprint("r") == original.fingerprint("r")
    for k in (1, 2, 4, 8):
        assert migrated.propose("r", k) == original.propose("r", k)


def test_a_verify_row_stays_one_row_and_a_prompt_beside_it_takes_more(setup):
    """Every slot of a speculative engine has a row of the prefill block. A
    verify row is ONE row of ``1 + proposed`` tokens at the slot's offset,
    as it was before the host dealt the rows; a prompt admitted beside it
    takes the rows that are free, several a step; both streams are the
    golden's."""
    _, config, engine = setup
    rng = np.random.default_rng(37)
    prompts = [rng.integers(0, config.vocab_size, size=n).tolist()
               for n in (6, 27)]
    gens = (12, 3)
    drafter, gold = _golden_drafter(engine, prompts, gens)
    plan = Speculative(drafter=drafter,
                       controller=SpecController(k_init=3, adaptive=False))
    be = BatchEngine(engine, n_slots=4, block_size=4, prefill_chunk=8,
                     speculative=plan, **PLAIN_PATH)
    assert be.prefill_rows == 4
    calls, step = [], be._mixed_step

    def recording(*args):
        calls.append(jax.tree.map(np.asarray, (args[1][2], args[3], args[6])))
        return step(*args)

    be._mixed_step = recording
    rids = [be.submit(prompts[0], gens[0], req_id=0)]
    be.step(), be.step()
    rids.append(be.submit(prompts[1], gens[1], req_id=1))
    out = be.run(max_steps=100)
    for rid, g in zip(rids, gens):
        assert out[rid] == gold[rid][:g]
    assert be.trace_counts["prefill"] == 1
    verify = beside = 0
    for dealt, offsets, takes in calls:
        rows_of = {}
        for slot, before, n in dealt.tolist():
            if slot >= 0:
                rows_of.setdefault(slot, []).append((before, n))
        for slot, rows in rows_of.items():
            assert rows[0][0] == offsets[slot]
            assert sum(n for _, n in rows) == takes[slot]
        for slot, rows in rows_of.items():      # slot i holds request i
            if offsets[slot] >= len(prompts[slot]):
                assert len(rows) == 1 and 1 < takes[slot] <= 1 + 3
                verify += 1
                beside += any(len(r) > 1 for r in rows_of.values())
    c = be.metrics.counters
    assert verify == c["spec_verify_rows"] > 0 and beside > 0
    assert c["prefill_rows_extra"] == 2     # 27 tokens: 24 beside, then 3
    be.pool.check_invariants()
