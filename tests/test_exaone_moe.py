"""The EXAONE-MoE block (``models/exaone_moe.py``: attention in every layer,
WINDOW layers with rope whose rows live in a ring a slot beside FULL layers
without position embedding whose rows live in the block arenas; a dense
SwiGLU, then sigmoid-routed experts with a shared expert) against the
benchmark's plain reference (``perfbench/families/exaone_moe.py``), at tiny
float32 sizes on the CPU: five layers in no period (window, window, full,
window, full), a window of 6, 8 experts top-2. ``paged_attn="gather"``
wherever the fused kernel is not the thing tested.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference, weights
from perfbench.families import exaone_moe as family
from triton_distributed_tpu.kernels.paged_attention import paged_attention
from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.moe_mlp import HeldExpertsMoE
from triton_distributed_tpu.models.config import ExaoneMoeConfig
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.exaone_moe import ExaoneMoe
from triton_distributed_tpu.obs import trace as _trace
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving.batch_engine import BatchEngine
from triton_distributed_tpu.serving.kv_pool import KVPool, window_ring_blocks

from conftest import pair_planes

WINDOW = 6
SIZES = family.Sizes(
    vocab_size=256, d_model=64, n_layers=5, windows=(6, 6, 0, 6, 0),
    sparse=(False, True, True, True, True), heads=4, kv_heads=2, head_dim=16,
    dense_width=96, expert_width=32, router_width=8, held=8, lo=0, topk=2,
    shared=1, scaling=2.5, norm_topk=True, theta=1e4, eps=1e-5,
    max_length=128, dtype="float32")
SEED = 47
N_WINDOW, N_FULL, N_MOE = 3, 2, 4


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)


@pytest.fixture(scope="module")
def served(mesh):
    mcfg, params = family.program({"source": "t"}, SIZES, SEED, mesh, {})
    return Engine(mcfg, mesh=mesh, params=params, mode="dist")


def ref_read(tokens, first):
    w = weights.Weights(family, SIZES, SEED)
    return reference.forward_positions(w, [(tokens, first)])[0]


_DONORS: dict = {}


def batch_engine(served, **kw):
    """A ``BatchEngine`` at the tests' geometry (a prefill block of 4 rows
    of 8: a ring of 6 - 1 + 32 positions, 10 blocks of 4); engines of one
    geometry share their compiled steps."""
    kw = {**dict(n_slots=4, n_blocks=96, block_size=4, prefill_chunk=8,
                 paged_attn="gather"), **kw}
    be = BatchEngine(served, **kw)
    donor = _DONORS.setdefault(
        (id(served), kw["n_slots"], kw["paged_attn"]), be)
    if donor is not be:
        be.share_steps_from(donor)
    return be


def prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SIZES.vocab_size, n).tolist() for n in lengths]


def alone(served, prompt, n_new):
    """What a request gives in an engine it has to itself."""
    be = batch_engine(served, n_slots=2)
    rid = be.submit(prompt, n_new)
    be.run()
    return be.finished[rid].output


def assert_served_is_the_references_best(prompt, out):
    ref = ref_read(prompt + out, len(prompt))
    assert ref["best_token"].tolist() == out
    assert np.all(ref["best"] - ref["picked"] <= 1e-5)


def test_engine_picks_the_model_and_the_walk_is_read_from_the_tuples(served):
    assert isinstance(served.model, ExaoneMoe)
    assert served.model.layer_counts == {"dense": 1, "moe": N_MOE,
                                         "window": N_WINDOW, "full": N_FULL}
    assert served.model.segments == (
        ((("window", "dense"),), 1),
        ((("window", "moe"), ("full", "moe")), 2))
    cfg = served.config
    assert (cfg.n_cache_layers, cfg.n_window_layers, cfg.window) == (2, 3, 6)
    # the published 48 layers: the dense layer, then LLLG by the tuples
    pub = ExaoneMoe(ExaoneMoeConfig(experts_held=16))
    assert pub.layer_counts == {"dense": 1, "moe": 47, "window": 36,
                                "full": 12}
    assert sum((unit * n for unit, n in pub.segments), ()) == \
        pub.config.layer_kinds
    assert sum(len(unit) for unit, _ in pub.segments) <= 8
    assert pub.config.layer_kinds[:5] == (
        ("window", "dense"), ("window", "moe"), ("window", "moe"),
        ("full", "moe"), ("window", "moe"))
    assert (pub.config.n_cache_layers, pub.config.n_window_layers,
            pub.config.window) == (12, 36, 128)
    with pytest.raises(ValueError, match="ONE window"):
        ExaoneMoeConfig.tiny(sliding_windows=(6, 4, 0, 6))
    with pytest.raises(ValueError, match="do not lie inside"):
        ExaoneMoeConfig(experts_held=16, experts_lo=120)


# -- prefill then decode through the cache, against the reference ----------------

P_ROWS = 3                   # the hand-driven steps' prefill block: 3 rows of 8


def paged_steps(engine, n_slots, n_blocks=96):
    pool = KVPool(engine.config, n_blocks=n_blocks, block_size=4,
                  max_seq_len=128, mesh=engine.mesh, n_slots=n_slots,
                  max_take=P_ROWS * 8)
    kw = dict(paged_attn="gather", state_specs=pool.specs)
    return (pool, jax.jit(engine._make_sm("dist", paged="prefill", **kw)),
            jax.jit(engine._make_sm("dist", paged="decode", **kw)))


TOKENS_A, TOKENS_B = prompts(3, 81, 13)


def logits_of_a_staggered_batch(engine):
    """Three slots through the step functions ``BatchEngine`` compiles, the
    mixed step in its two-block form with a prefill block of three rows of
    8. Sequence a takes ALL THREE rows of a step (24 tokens: several rows of
    one slot, crossing the window of 6 inside ONE step), three times, then
    5 tokens, then decodes: 80 positions through a ring of 6 - 1 + 24
    positions (8 blocks of 4: 32 lines), so the ring wraps more than once.
    b is admitted one step later (one row of 8, then 4 beside a's two
    rows, then its last token on the decode block); slot 1 stays empty.
    Returns the logits of a at positions 76..80 and of b at 11, 12."""
    a, b = TOKENS_A, TOKENS_B
    pool, pre, dec = paged_steps(engine, 3)
    assert pool.state.wkv.shape == (
        N_WINDOW, 3, 2, -(-(engine.config.window - 1 + 24) // 4), 4, 2, 16)
    assert pool.ensure("a", 82) and pool.ensure("b", 14)
    tables = jnp.asarray(pool.padded_tables(["a", None, "b"]))
    state, got_a, got_b = pool.state, [], []

    def mixed(state, off, lens, tok, rows):
        """rows: (slot, tokens) in block order, at most 8 tokens a row."""
        chunk = np.zeros((P_ROWS, 8), np.int32)
        dealt = np.tile(np.int32([-1, 0, 0]), (P_ROWS, 1))
        at = dict(enumerate(off))
        for k, (slot, toks) in enumerate(rows):
            chunk[k, :len(toks)] = toks
            dealt[k] = slot, at[slot], len(toks)
            at[slot] += len(toks)
        live = jnp.asarray([n > 0 for n in lens])
        return pre(engine.params,
                   (jnp.asarray(tok, jnp.int32), jnp.asarray(chunk),
                    jnp.asarray(dealt)), state,
                   jnp.asarray(off, jnp.int32), tables, live,
                   jnp.asarray(lens, jnp.int32))

    def counts(aux, takes):
        """The five ``step_stats``; ``takes``: (cache length before, new
        tokens) of each live slot."""
        st = aux["stats"].tolist()
        live = sum(n for _, n in takes)
        assert st[0] == st[1] == live * 2 * N_MOE and st[3] == 0
        assert 0 < st[2] <= 8 * N_MOE
        assert st[4:] == [live * 5]

    def rows_of(slot, toks):
        return [(slot, toks[i:i + 8]) for i in range(0, len(toks), 8)]

    _, aux, state = mixed(state, [0, 0, 0], [24, 0, 0], [0, 0, 0],
                          rows_of(0, a[0:24]))
    counts(aux, [(0, 24)])
    _, aux, state = mixed(state, [24, 0, 0], [16, 0, 8], [0, 0, 0],
                          rows_of(0, a[24:40]) + rows_of(2, b[0:8]))
    counts(aux, [(24, 16), (0, 8)])
    _, aux, state = mixed(state, [40, 0, 8], [16, 0, 4], [0, 0, 0],
                          rows_of(0, a[40:56]) + rows_of(2, b[8:12]))
    counts(aux, [(40, 16), (8, 4)])
    logits, aux, state = mixed(state, [56, 0, 12], [20, 0, 1], [0, 0, b[12]],
                               rows_of(0, a[56:76]))
    counts(aux, [(56, 20), (12, 1)])
    got_b.append(logits[2])                                # b position 12
    for k in range(5):
        logits, aux, state = dec(
            engine.params,
            jnp.asarray([[a[76 + k]], [0], [0]], jnp.int32), state,
            jnp.asarray([76 + k, 0, 0], jnp.int32), tables,
            jnp.asarray([True, False, False]))
        counts(aux, [(76 + k, 1)])
        got_a.append(logits[0])
    assert jax.tree.structure(state) == jax.tree.structure(pool.state)
    return np.asarray(got_a), np.asarray(got_b)


def assert_logits_agree(got, tokens, first):
    """Float32 on both sides, so what separates them is the order of the
    sums (sorted expert tiles against one expert after another, a gathered
    ring against a blocked mask, five layers deep): 2e-5 on a logit of
    spread ~1. Computing any sub-layer in bfloat16 (relative 4e-3) fails it
    by two orders; one key more or fewer in a window fails it by three."""
    ref = ref_read(tokens + [0], first)
    for i, logits in enumerate(got):
        assert ref["best_token"][i] == int(logits.argmax())
        assert ref["best"][i] == pytest.approx(float(logits.max()), abs=2e-5)
        assert ref["std"][i] == pytest.approx(float(logits.std()), rel=1e-3)
        nxt = (tokens + [0])[first + i]
        assert ref["picked"][i] == pytest.approx(float(logits[nxt]),
                                                 abs=2e-5)


def test_prefill_then_decode_through_a_ring_that_wraps_agrees_on_logits(served):
    """Rows admitted at different steps, contexts that pass the window, wrap
    the ring more than once and cross the window inside ONE mixed step,
    against the reference's ONE full forward pass of each sequence."""
    got_a, got_b = logits_of_a_staggered_batch(served)
    assert_logits_agree(got_a, TOKENS_A, 77)       # positions 76 .. 80
    assert_logits_agree(got_b, TOKENS_B, 13)       # position 12


def _with_windows(engine, w):
    cfg = dataclasses.replace(
        engine.config,
        sliding_windows=tuple(w if x else 0
                              for x in engine.config.sliding_windows))
    return Engine(cfg, mesh=engine.mesh, params=engine.params, mode="dist")


def _with_rope_on_full(engine):
    wrong = Engine(engine.config, mesh=engine.mesh, params=engine.params,
                   mode="dist")
    attn = wrong.model.attn
    wrong.model.__dict__["attn"] = {
        "window": attn["window"],
        "full": dataclasses.replace(attn["full"], rope=True)}
    return wrong


def _with_one_expert_altered(engine):
    moe = engine.params["moe"]
    params = dict(engine.params, moe=dict(
        moe, w_down=moe["w_down"].at[2, 5].multiply(0.5)))
    return Engine(engine.config, mesh=engine.mesh, params=params,
                  mode="dist")


FAULTS = {"window-1": lambda e: _with_windows(e, WINDOW - 1),
          "window+1": lambda e: _with_windows(e, WINDOW + 1),
          "rope-on-full": _with_rope_on_full,
          "one-expert": _with_one_expert_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_no_longer_agrees(served, fault):
    """A window one key short or one key long, rope on the full layers, one
    expert of one layer altered: the same batch disagrees with the
    reference."""
    got_a, _ = logits_of_a_staggered_batch(FAULTS[fault](served))
    with pytest.raises(AssertionError):
        assert_logits_agree(got_a, TOKENS_A, 77)


# -- through BatchEngine ----------------------------------------------------------

@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_batch_engine_serves_what_the_reference_puts_first(served,
                                                           paged_attn):
    """Requests of several lengths through ``BatchEngine`` (the longest
    wraps its ring of 40 lines; the deal gives a prompt several rows of a
    step), one submitted after the others have started: every served token
    is the reference's best; the step's span carries the counts, the
    snapshot the layers by kind, the window storage and the window build."""
    _trace.get_tracer().reset()
    _trace.enable()
    try:
        be = batch_engine(served, paged_attn=paged_attn)
        ps = prompts(5, 5, 43, 17, 9)
        reqs = [be.submit(p, 6) for p in ps[:3]]
        for _ in range(3):
            be.step()
        reqs.append(be.submit(ps[3], 6))
        be.run()
        spans = [r for r in _trace.get_tracer().records
                 if r.name in ("decode_step", "mixed_step")]
    finally:
        _trace.disable()
        _trace.get_tracer().reset()
    be.pool.check_invariants()
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    assert be.prefix_cache is None and be.prefill_rows == 4
    c = be.metrics.counters
    tokens = sum(len(p) for p in ps) + 4 * 5
    assert c["kv_rows_appended"] == tokens * 5
    assert c["moe_pairs_routed"] == c["moe_pairs_held"] == tokens * 2 * N_MOE
    assert c["moe_dropped_pairs"] == 0 < c["moe_experts_touched"]
    assert c["prefill_rows_extra"] > 0          # the deal engages
    for name in served.model.step_stats:
        assert sum(r.attrs[name] for r in spans) == c[name]
    snap = be.stats_snapshot()
    assert snap["layers"] == {"dense": 1, "moe": N_MOE, "window": N_WINDOW,
                              "full": N_FULL}
    assert snap["pool"]["window_bytes"] == be.pool.state.wkv.nbytes > 0
    if paged_attn == "fused":
        named = [k for k in snap["paged_arithmetic"]
                 if k.endswith(f":window{WINDOW}")]
        assert len(named) == 2          # the decode shape and the chunk shape
        assert {snap["paged_arithmetic"][k] for k in named} == \
            {"folded", "per_head"}
    for rid, prompt in zip(reqs, ps):
        assert_served_is_the_references_best(prompt, be.finished[rid].output)


def test_a_preempted_and_readmitted_request_gives_what_an_undisturbed_one_gives(
        served):
    """Preemption frees the slot; the resumed request recomputes its rows
    from position 0 (the ring may be another's by then) and goes on as if
    undisturbed."""
    be = batch_engine(served, n_slots=2)
    p, q = prompts(9, 22, 7)
    rp, rq = be.submit(p, 9), be.submit(q, 9)
    for _ in range(5):                   # both prefilled, some tokens out
        be.step()
    victim = next(i for i, s in enumerate(be._slots)
                  if s is not None and s.req.req_id == rp)
    assert 0 < len(be._slots[victim].req.output) < 9
    be._preempt(victim)
    be.run()
    be.pool.check_invariants()
    assert be.metrics.counters["preemptions"] == 1
    assert be.finished[rp].output == alone(served, p, 9)
    assert be.finished[rq].output == alone(served, q, 9)
    assert_served_is_the_references_best(p, be.finished[rp].output)


def test_a_slot_reused_and_a_common_prefix_give_what_each_gives_alone(served):
    """A second request in a slot reads nothing of the first's rows (the
    ring is never cleared: the mask is by position), and the prefix cache
    asked for (the default) adopts no block: a block does not carry the
    window layers' last rows at its boundary."""
    be = batch_engine(served, n_slots=1, prefix_cache=True)
    donor, = prompts(11, 45)                  # wraps the one slot's ring
    tail, = prompts(12, 5)
    ra = be.submit(donor, 3)
    rb = be.submit(donor[:12] + tail, 6)
    be.run()
    c = be.metrics.counters
    assert be.prefix_cache is None and be.pool.n_cached == 0
    assert not be.pool.prefix_cacheable
    assert c.get("prefix_cached_tokens", 0) == 0 == c.get("prefix_hits", 0)
    assert be.finished[ra].output == alone(served, donor, 3)
    out = be.finished[rb].output
    assert out == alone(served, donor[:12] + tail, 6)
    assert_served_is_the_references_best(donor[:12] + tail, out)


def test_the_window_storage_is_sized_by_the_window_and_not_by_the_context(
        served):
    """The geometry is read from the configuration: block arenas as deep as
    the FULL layers, a ring a (window layer, slot) that holds the window and
    a step's largest take, whatever ``max_seq_len`` and ``n_blocks`` are."""
    kw = dict(block_size=4, n_slots=3, max_take=32)
    pool = KVPool(served.config, n_blocks=6, max_seq_len=64, **kw)
    st = pool.state
    assert st.kv.shape == (N_FULL, 6, 2, 4, 2, 16)
    assert window_ring_blocks(WINDOW, 4, 32) == 10     # ceil((6 - 1 + 32) / 4)
    # one ring a (window layer, slot), the planes OUTSIDE its lines
    assert st.wkv.shape == (N_WINDOW, 3, 2, 10, 4, 2, 16)
    assert pool.window_bytes == 2 * N_WINDOW * 3 * 10 * 4 * 2 * 16 * 4
    assert pool.geometry()["window"] == {
        "layers": N_WINDOW, "window": WINDOW, "max_take": 32,
        "ring_blocks": 10, "bytes": pool.window_bytes}
    assert pool.kv_fingerprint() == "float32:none:paired:window6x3"
    pool.check_invariants()
    big = KVPool(served.config, n_blocks=600, max_seq_len=4096, **kw)
    assert big.window_bytes == pool.window_bytes
    assert big.state.kv.nbytes == 100 * st.kv.nbytes
    # a decode-only engine needs the window alone; the published geometry
    assert window_ring_blocks(128, 16, 1) == 8
    assert window_ring_blocks(128, 16, 7 * 64) == 36
    # a model without window layers has no such storage
    from triton_distributed_tpu.models.config import ModelConfig

    rows = KVPool(ModelConfig.from_name("tiny"), n_blocks=6, block_size=4)
    assert rows.state.wkv is None and rows.window_bytes == 0
    assert rows.prefix_cacheable and "window" not in rows.geometry()


def test_a_ring_too_small_for_the_steps_take_is_refused(served):
    """A pool of window layers has no default take, and a step that gives
    one slot more tokens than the ring was built for is refused when it is
    traced: its later rows would append over lines the first row reads, and
    the mask by position would take them for valid keys."""
    with pytest.raises(ValueError, match="max_take"):
        KVPool(served.config, n_blocks=8, block_size=4, n_slots=2)
    pool = KVPool(served.config, n_blocks=8, block_size=4, n_slots=2,
                  max_take=8)                 # 4 ring blocks: 16 lines
    assert pool.state.wkv.shape[3] == 4
    step = jax.jit(served._make_sm(
        "dist", paged="prefill", paged_attn="gather",
        state_specs=pool.specs))

    def args(rows, L):
        return (served.params,
                (jnp.zeros((2,), jnp.int32), jnp.zeros((rows, L), jnp.int32),
                 jnp.full((rows, 3), -1, jnp.int32)),
                pool.state, jnp.zeros((2,), jnp.int32),
                jnp.zeros((2, 32), jnp.int32), jnp.ones((2,), bool),
                jnp.ones((2,), jnp.int32))

    step.lower(*args(2, 4))                   # 5 + 8 lines of 16
    step.lower(*args(1, 11))                  # 5 + 11: the ring to the line
    with pytest.raises(ValueError, match="16 lines.*12 tokens.*max_take"):
        step.lower(*args(3, 4))               # three rows may be ONE slot's
    with pytest.raises(ValueError, match="needs 17"):
        step.lower(*args(1, 12))


def test_what_is_not_built_is_refused_by_name(served):
    pool = KVPool(served.config, n_blocks=8, block_size=4, n_slots=2,
                  max_take=8)
    args = (served.params, jnp.zeros((2, 8), jnp.int32), pool.state,
            jnp.zeros((2,), jnp.int32), jnp.zeros((2, 32), jnp.int32),
            jnp.ones((2,), bool), jnp.ones((2,), jnp.int32))
    step = jax.jit(served._make_sm(
        "dist", paged="prefill", paged_attn="gather", spec_verify=True,
        state_specs=pool.specs))
    with pytest.raises(NotImplementedError, match="overwritten ring"):
        step.lower(*args)
    with pytest.raises(ValueError, match="no quantized"):
        KVPool(served.config, n_blocks=6, block_size=4, n_slots=2,
               max_take=8, kv_dtype="int8")
    with pytest.raises(ValueError, match="needs n_slots"):
        KVPool(served.config, n_blocks=6, block_size=4)
    mesh2 = make_mesh({"tp": 2}, devices=jax.devices()[:2], set_default=False)
    engine = Engine(ExaoneMoeConfig.tiny(), mesh=mesh2, mode="dist")
    pool2 = KVPool(engine.config, n_blocks=8, block_size=4, mesh=mesh2,
                   n_slots=2, max_take=1)
    step = jax.jit(engine._make_sm("dist", paged="decode",
                                   paged_attn="gather",
                                   state_specs=pool2.specs))
    with pytest.raises(NotImplementedError,
                       match="window layers under tensor parallelism"):
        step.lower(engine.params, jnp.zeros((2, 1), jnp.int32), pool2.state,
                   jnp.zeros((2,), jnp.int32), jnp.zeros((2, 16), jnp.int32),
                   jnp.ones((2,), bool))


# -- the window build of the block walk -------------------------------------------

def ring_of(seq, lens, n_slots, slots, ring_blocks, bs):
    """One plane ``(1, n_slots, ring_blocks, bs, Hkv, dh)`` of ring storage
    (``pair_planes(k plane, v plane, 3)`` is the ring) holding what
    appending the first ``lens[b]`` positions of ``seq`` (B, S, Hkv, dh),
    one by one, leaves there: the last ``ring_blocks * bs`` of them, each in
    its line; NaN where nothing was written (a reader must never let it
    through)."""
    lines = ring_blocks * bs
    ring = np.full((1, n_slots, lines, *seq.shape[2:]), np.nan, np.float32)
    for b, n in enumerate(lens):
        for p in range(n):
            ring[0, slots[b], p % lines] = seq[b, p]
    return jnp.asarray(ring.reshape(1, n_slots, ring_blocks, bs,
                                    *seq.shape[2:]))


def plain_window_attention(q, k, v, kv_lens, q_lens, window, scale):
    """q (B, L, Hq, dh) at positions ``kv_len - q_len + j``; k, v the WHOLE
    sequences (B, S, Hkv, dh); plain numpy, one row at a time."""
    B, L, Hq, dh = q.shape
    g = Hq // k.shape[2]
    out = np.zeros((B, L, Hq, dh), np.float32)
    for b in range(B):
        for j in range(int(q_lens[b])):
            pos = int(kv_lens[b] - q_lens[b]) + j
            lo = max(0, pos - window + 1)
            for h in range(Hq):
                s = k[b, lo:pos + 1, h // g] @ q[b, j, h] * scale
                p = np.exp(s - s.max())
                out[b, j, h] = (p / p.sum()) @ v[b, lo:pos + 1, h // g]
    return out


# (window, block size, ring blocks, context lengths a row): contexts below,
# at and above the window; window edges on a block boundary (8 | 16), off it
# (6 in blocks of 4), on a tile boundary (the tile is window // bs blocks);
# a ring that has wrapped (contexts past ring_blocks * bs). The last two
# pass ``tile_blocks`` (a fifth entry), so a window is several tiles and a
# WHOLE tile is one copy: the cells' own 284 / 32 and 36 / 8 scaled down to
# rings of 19 and 9 blocks under tiles of 2, no multiple of the tile, so
# that in one call a tile is whole (contexts 64, 150), whole and wrapping
# the ring (tile 9 of context 77, tile 28 of 251 and 233; tile 4 of 40),
# ragged behind ``lo`` (251; 71) and ragged at ``limit`` (150, 251; 17, 90).
WALKS = {
    "edges-on-blocks": (8, 4, 6, (3, 8, 9, 16, 24, 41)),
    "edges-off-blocks": (6, 4, 5, (2, 6, 7, 13, 21, 38)),
    "one-block-window": (4, 4, 4, (1, 4, 5, 12, 17, 30)),
    "whole-tiles-ring-19": (64, 4, 19, (20, 64, 77, 150, 251, 233), 2),
    "whole-tiles-ring-9": (16, 4, 9, (5, 16, 17, 40, 71, 90), 2),
}


@pytest.mark.parametrize(
    "walk,L,Hq",
    [(w, L, 4) for w in sorted(WALKS) for L in (1, 5)]
    + [("edges-off-blocks", 1, 14), ("edges-off-blocks", 5, 14)],
    ids=lambda v: {1: "decode", 5: "chunk", 4: "g2", 14: "g7"}.get(v, v))
def test_the_window_build_equals_plain_numpy(walk, L, Hq):
    """``paged_attention(window=...)`` under the interpreter, both step
    shapes (the decode shape's folded arithmetic, the chunk shape's per
    head, ragged ``q_lens``), rows in shuffled slots, against plain numpy
    over the whole sequences; and the gather oracle the same. Two query
    heads to a key head, and seven (a group that is no power of two)."""
    window, bs, ring_blocks, ctx, *tile = WALKS[walk]
    rng = np.random.default_rng(len(walk) + L)
    B, Hkv, dh, S = len(ctx), 2, 16, max(ctx)
    k = rng.standard_normal((B, S, Hkv, dh)).astype(np.float32)
    v = rng.standard_normal((B, S, Hkv, dh)).astype(np.float32)
    q = rng.standard_normal((B, L, Hq, dh)).astype(np.float32)
    kv_lens = np.asarray(ctx, np.int32)
    q_lens = np.minimum(kv_lens, rng.integers(1, L + 1, B)).astype(np.int32)
    if window + L - 1 > ring_blocks * bs:
        pytest.skip("the ring does not hold the window and the take")
    slots = rng.permutation(B + 2)[:B].astype(np.int32)
    kr = ring_of(k, kv_lens, B + 2, slots, ring_blocks, bs)
    vr = ring_of(v, kv_lens, B + 2, slots, ring_blocks, bs)
    want = plain_window_attention(q, k, v, kv_lens, q_lens, window,
                                  dh ** -0.5)
    ring = pair_planes(kr, vr, 3)       # the planes outside a slot's lines
    got = paged_attention(
        jnp.asarray(q), ring, jnp.asarray(slots)[:, None],
        jnp.asarray(kv_lens), q_lens=jnp.asarray(q_lens), layer=0,
        window=window, interpret=True, tile_blocks=tile[0] if tile else None)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    oracle = nn.window_attn_with_cache(
        jnp.asarray(q), ring, jnp.asarray(slots),
        jnp.asarray(kv_lens - q_lens), window=window, layer=0,
        scale=dh ** -0.5, seq_lens=jnp.asarray(q_lens), paged_attn="gather")
    np.testing.assert_allclose(np.asarray(oracle), want, atol=2e-5)


def test_the_window_build_refuses_what_it_has_not():
    """(Its name in a device trace, ``window_paged_attention``, is the
    compile rehearsal's to check: ``tests/test_chip_compile.py``.)"""
    q = jnp.zeros((2, 1, 4, 16))
    ring = jnp.zeros((1, 2, 2, 4, 4, 2, 16))
    with pytest.raises(ValueError, match="ring storage"):
        paged_attention(q, ring[0], jnp.zeros((2, 1), jnp.int32),
                        jnp.ones((2,), jnp.int32), layer=0, window=4)
    with pytest.raises(NotImplementedError, match="no latent, quantized"):
        paged_attention(q, ring, jnp.zeros((2, 1), jnp.int32),
                        jnp.ones((2,), jnp.int32), layer=0, window=4,
                        probes=True)


def copies_of_a_step(kv_len, q_len, qt, q_tile, window, bs, tile, ring):
    """What one grid step of the window walk fetches, plainly: (whole tiles
    that lie side by side in the ring, whole tiles that wrap it, live
    blocks of the ragged tiles). A block is live where it holds a key that
    some query of the tile sees; a tile is whole where every block of it is
    and its last line is a key (the kernel's test)."""
    if qt * q_tile >= q_len:
        return 0, 0, 0
    first_q = kv_len - q_len + qt * q_tile
    limit = kv_len - q_len + min((qt + 1) * q_tile, q_len)
    lo = max(first_q - window + 1, 0)
    tiles = {}
    for j in range(-(-limit // bs)):
        if (j + 1) * bs > lo:
            tiles.setdefault(j // tile, []).append(j)
    side_by_side = wrapping = ragged = 0
    for t, blocks in tiles.items():
        if len(blocks) < tile or (t + 1) * tile * bs > limit:
            ragged += len(blocks)
        elif (t * tile) % ring + tile <= ring:
            side_by_side += 1
        else:
            wrapping += 1
    return side_by_side, wrapping, ragged


ROUND_THE_RING = dict(max_blocks=7, window=40, kv_len=(90, 131))


@pytest.mark.parametrize("shape", [
    {}, {"L": 8, "q_tile": 4}, ROUND_THE_RING,
    dict(ROUND_THE_RING, L=8, q_tile=4)],
    ids=["decode", "chunk", "decode-round-the-ring", "chunk-round-the-ring"])
def test_a_block_behind_the_window_is_neither_copied_nor_waited_for(shape):
    """The analyzer's event log of the window build (``paged.window``: a
    ring of 6 blocks of 8, a window of 24, contexts of 48, tiles of 2
    blocks): a grid step moves the blocks that hold a visible key and no
    other (the decode shape: keys 24..47, three blocks of six; a chunk's
    query tile of 4: four), a WHOLE tile of them whose blocks lie side by
    side in the ring as ONE copy, both planes (the decode shape: blocks 4-5;
    block 3 alone, its tile ragged at the start), every other live block as
    its own; every started copy is waited for, at its size, and the sweeps
    of every registered kernel stay clean with it. Round a ring of 7 blocks
    (no multiple of the tile; a window of 40; contexts of 90 and 131) a
    walk has whole tiles, whole tiles that wrap (a copy a block) and ragged
    ones at both ends."""
    from triton_distributed_tpu.analysis import checks, events, resources
    from triton_distributed_tpu.analysis import registry as reg

    spec = reg.get("paged.window").build(1, **shape)
    kw, (B, n_q_tiles) = spec.kwargs, spec.grid
    tile, bs, L = kw["tile_blocks"], kw["bs"], shape.get("L", 1)
    ring = shape.get("max_blocks", 6)
    kv_lens = np.broadcast_to(shape.get("kv_len", ring * bs), (B,))
    walks = np.array([
        copies_of_a_step(int(kv_lens[b]), L, qt, kw["q_tile"], kw["window"],
                         bs, tile, ring)
        for b in range(B) for qt in range(n_q_tiles)])
    side_by_side, wrapping, ragged = (int(n) for n in walks.sum(0))
    assert side_by_side > 0 and ragged > 0
    assert (wrapping > 0) == ("kv_len" in shape)
    if "kv_len" not in shape:           # the numbers the docstring gives
        assert (side_by_side, ragged) == ((2, 2) if L == 1 else (6, 4))
    log = events.trace_kernel(spec, 1).logs[0]
    pair_bytes = 2 * bs * kw["n_kv"] * 128 * 4    # a block's K and V plane
    for kind in ("inc", "wait"):
        sizes = sorted(e.amount for e in log if e.kind == kind)
        # K and V TOGETHER: one copy a tile that is whole and side by side,
        # one a live block of any other (half the copies two arenas took);
        # a block behind the window moves no byte
        assert sizes == sorted(
            [pair_bytes] * (wrapping * tile + ragged)
            + [tile * pair_bytes] * side_by_side), kind
    assert checks.check_kernel("paged.window", 1) == []
    assert resources.check_kernel("paged.window", 1, shape) == []


# -- the expert layer's shares ----------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips' shares of four experts each (held 4 of 8, lo 0 and 4; the
    program's layer, told which experts it holds), the shared expert counted
    once, equal the reference's uncut expert layer over all 8."""
    uncut = SIZES
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(jax.random.PRNGKey(12), (24, uncut.d_model))
    lw = reference.f32(family.plain_layer(uncut, key, True))
    shared = family.swiglu(x, lw["s_gu"], lw["s_d"], "float32")
    want = shared + family.routed_part(uncut, x, lw, "float32")
    total = ref_total = shared
    held_pairs = 0
    for lo in (0, 4):
        m = dataclasses.replace(uncut, held=4, lo=lo)
        slw = family.plain_layer(m, key, True)
        np.testing.assert_array_equal(slw["e_gu"], lw["e_gu"][lo:lo + 4])
        layer = HeldExpertsMoE(
            d_model=m.d_model, d_ff=m.expert_width, n_experts=m.router_width,
            topk=m.topk, n_held=4, lo=lo, routed_scaling=m.scaling,
            dtype=jnp.float32)
        y, stats = layer.routed(
            {"router": slw["router"], "bias": jnp.zeros((8,)),
             "w_gate_up": slw["e_gu"], "w_down": slw["e_d"]}, x)
        total = total + y
        ref_total = ref_total + family.routed_part(
            m, x, reference.f32(slw), "float32")
        held_pairs += int(stats[1])
        assert int(stats[3]) == 0
    assert held_pairs == 24 * 2                   # every pair has one owner
    np.testing.assert_allclose(total, want, atol=5e-5)
    np.testing.assert_allclose(ref_total, want, atol=5e-5)


def test_the_seeded_router_deals_every_chip_the_same_sum_and_spread():
    """At the published deal (128 experts, 16 a chip) chip c's router rows
    are an orthogonal remix of chip 0's that keeps the constant vector: for
    EVERY token the 16 scores of each chip have the same sum and the same
    sum of squares, each row keeps its N(0, 1 / d) size, no two chips have
    the same rows, and over a stream with a common part a third of a token's
    own (which rows drawn one by one deal unevenly by a fifth) every chip is
    dealt an eighth of the routed pairs to a fiftieth; the balance is one of
    first and second order and a common part that drowns the tokens undoes
    it. A tiny test's handful is drawn row by row, as before."""
    m = dataclasses.replace(SIZES, d_model=512, router_width=128, held=16,
                            topk=8)
    key = jax.random.PRNGKey(5)
    router = family.seeded_router(m, key)
    assert (router.shape, router.dtype) == ((512, 128), jnp.float32)
    w = np.asarray(router, np.float64)
    np.testing.assert_array_equal(
        family.seeded_router(dataclasses.replace(m, lo=48), key), w)
    rng = np.random.default_rng(6)
    common = 0.3 * rng.standard_normal(512)
    x = common + rng.standard_normal((4096, 512))
    s = (x @ w).reshape(4096, 8, 16)
    np.testing.assert_allclose(s.sum(-1), s.sum(-1)[:, :1].repeat(8, 1),
                               atol=1e-3)
    sq = np.square(s).sum(-1)
    np.testing.assert_allclose(sq, sq[:, :1].repeat(8, 1), rtol=1e-4)
    norms = np.linalg.norm(w, axis=0)
    assert 0.7 < norms.min() and norms.max() < 1.3
    rows = w.T.reshape(8, 16, 512)
    assert min(np.abs(rows[a] - rows[b]).max()
               for a in range(8) for b in range(a)) > 0.05

    def shares(matrix):
        scores = x @ matrix
        chosen = scores >= np.sort(scores, axis=-1)[:, -8:-7]
        return chosen.reshape(4096, 8, 16).sum((0, 2)) / (4096 * 8)

    one_by_one = rng.standard_normal((512, 128)) / np.sqrt(512)
    assert np.abs(shares(w) - 0.125).max() < 0.006
    assert np.abs(shares(one_by_one) - 0.125).max() > 0.015
    tiny = family.seeded_router(SIZES, key)
    np.testing.assert_array_equal(tiny, weights.randw(
        key, (64, 8), 64, jnp.float32))


def test_counts_of_the_published_configuration():
    """The family's counts at K-EXAONE-236B-A23B's sizes, cut to one chip of
    eight at layers 0-4, against the issue's hand count: 3.71 B parameters
    held (7.43 GB), 236.6 B whole, 4,096 B of rows a token a layer, a decode
    step of the cell's 32 rows at contexts of 9,000 reading at least 7.8 GB
    (at 64 rows every held expert but one in a hundred is touched: 9.6 GB)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "perfbench/configs/k-exaone-236b-a23b-ep8.json")) as f:
        cfg = json.load(f)
    m = family.sizes(cfg)
    assert m.windows == (128, 128, 128, 0, 128)
    assert m.sparse == (False, True, True, True, True)
    assert (m.n_window, m.n_full, m.n_sparse, m.window) == (4, 1, 4, 128)
    assert (m.held, m.router_width, m.topk, m.vocab_size) == (16, 128, 8,
                                                              19_200)
    assert m.row_bytes == 4096
    assert family.attn_params(m) == pytest.approx(113.25e6, rel=1e-3)
    assert family.expert_params(m) == 3 * 6144 * 2048
    assert family.params_held(m) == pytest.approx(3.712e9, rel=1e-3)
    assert 2 * family.params_held(m) == pytest.approx(7.43e9, rel=2e-3)
    whole = dataclasses.replace(
        m, n_layers=48, windows=tuple(cfg["sliding_windows"]),
        sparse=tuple(t == "sparse" for t in cfg["mlp_layer_types"]),
        held=128, vocab_size=153_600)
    assert family.params_held(whole) == pytest.approx(236.6e9, rel=2e-3)
    pairs, touched = family.moe_expected(m, 32)
    assert pairs == pytest.approx(4 * 32 * 8 / 8)
    assert touched / 4 == pytest.approx(13.97, abs=0.01)     # of 16 held
    step = family.decode_step_min_bytes(m, [9000] * 32)
    assert step == pytest.approx(
        family.weight_bytes_read(m, touched) + 32 * 9000 * 4096
        + 4 * 32 * 128 * 4096)
    assert step == pytest.approx(7.83e9, rel=0.01)
    step64 = family.decode_step_min_bytes(m, [9000] * 64)
    assert family.moe_expected(m, 64)[1] / 4 == pytest.approx(15.74, abs=0.01)
    assert step64 == pytest.approx(9.61e9, rel=0.01)
    assert family.window_attn_min_bytes(m, 32) == 32 * 128 * 4096 * 4
    assert family.window_attn_flops(m, 1) == 4 * 4 * 64 * 128 * 128
    # one summed context, as the roofline's reader hands it: one row's
    # experts and one window: it reads low, never high
    summed = family.decode_step_min_bytes(m, [32 * 9000])
    assert 0.4 * step < summed < 0.6 * step
    # the program's own configuration object, and what its pool would hold
    mcfg = family.program_config(cfg, m)
    assert (mcfg.n_cache_layers, mcfg.n_window_layers, mcfg.window) == \
        (1, 4, 128)
    assert (mcfg.n_held, mcfg.experts_lo, mcfg.n_experts) == (16, 0, 128)
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(
        ExaoneMoe(mcfg).param_shapes(),
        is_leaf=lambda x: isinstance(x, tuple)))
    norms = 6144 * (1 + 2 * 5) + 2 * 128 * 5
    assert n - norms == family.params_held(m)
    from triton_distributed_tpu.serving.kv_pool import paged_state_shapes

    fleet = cfg["serve"]["fleet"]
    state = paged_state_shapes(
        mcfg, n_blocks=fleet["n_blocks"], block_size=fleet["block_size"],
        n_slots=fleet["n_slots"], max_take=7 * fleet["prefill_chunk"])
    nbytes = {f: int(np.prod(a.shape)) * a.dtype.itemsize
              for f in ("kv", "wkv") if (a := getattr(state, f))}
    assert (fleet["n_slots"], fleet["n_blocks"]) == (32, 28_672)
    assert nbytes["kv"] == pytest.approx(1.879e9, rel=1e-3)
    assert nbytes["wkv"] == 4 * 32 * 36 * 16 * 4096
    # five layers of full rows would be 9.4 GB beside 7.43 GB of weights
    assert sum(nbytes.values()) < 2.5e9 < 9.3e9 < 5 * nbytes["kv"]
    # every published key of the catalog's row stands at its published value
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", "max_position_embeddings"]
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["num_shared_experts"],
            cfg["num_hidden_layers_published"], cfg["num_experts_published"],
            cfg["vocab_size_published"]) == \
        (6144, 18432, 2048, 8, 2.5, 64, 8, 128, 128, 1, 48, 128, 153600)
    assert len(cfg["layer_types"]) == len(cfg["sliding_windows"]) == 48
