"""The LFM2-MoE block, served from the EXAONE walk with a THIRD kind of
operator (``models/exaone_moe.py``: the gated short convolution of
``layers/short_conv.py``, whose whole state is a window of two inputs a slot
in the pool's ``conv`` arena; rope and QK norm on the full layers, two key
heads to a row of the pool, the table as the head, a selection bias), against
the benchmark's plain reference (``perfbench/families/lfm2_moe.py``) at tiny
float32 sizes on the CPU: eight layers ``conv, conv, full, conv, conv, conv,
full, conv``, the first two dense, 8 experts top-2. ``paged_attn="gather"``
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
from perfbench.families import lfm2_moe as family
from triton_distributed_tpu.kernels.short_conv_update import (
    short_conv_update,
    short_conv_update_reference,
)
from triton_distributed_tpu.kernels.ssm_update import ssm_state_update
from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.mamba2 import Mamba2, chunk_scan
from triton_distributed_tpu.layers.moe_mlp import HeldExpertsMoE
from triton_distributed_tpu.layers.short_conv import ShortConv, chained_rows
from triton_distributed_tpu.models.config import (
    ExaoneMoeConfig,
    GraniteHybridConfig,
    Lfm2MoeConfig,
    NemotronHConfig,
)
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.exaone_moe import ExaoneMoe
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving.batch_engine import BatchEngine
from triton_distributed_tpu.serving.kv_pool import KVPool, PagedKVState

CONV = (True, True, False, True, True, True, False, True)
N_LAYERS, N_CONV, N_FULL, N_DENSE, TOPK = 8, 6, 2, 2, 2
N_MOE = N_LAYERS - N_DENSE
SIZES = family.Sizes(
    vocab_size=256, d_model=64, n_layers=N_LAYERS, conv=CONV,
    dense_layers=N_DENSE, taps=3, heads=4, kv_heads=2, head_dim=16,
    dense_width=96, expert_width=32, router_width=8, held=8, lo=0, topk=TOPK,
    scaling=1.0, norm_topk=True, theta=1e4, eps=1e-5, max_length=128,
    dtype="float32")
FILE = {"source": "t", "conv_bias": False, "use_expert_bias": True,
        "tie_word_embeddings": True}
SEED = 61


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)


@pytest.fixture(scope="module")
def served(mesh):
    mcfg, params = family.program(FILE, SIZES, SEED, mesh, {})
    return Engine(mcfg, mesh=mesh, params=params, mode="dist")


def ref_read(tokens, first):
    w = weights.Weights(family, SIZES, SEED)
    return reference.forward_positions(w, [(tokens, first)])[0]


_DONORS: dict = {}


def batch_engine(served, **kw):
    """A ``BatchEngine`` at the tests' geometry; engines of one geometry
    share their compiled steps."""
    kw = {**dict(n_slots=2, n_blocks=96, block_size=4, prefill_chunk=8,
                 paged_attn="gather"), **kw}
    be = BatchEngine(served, **kw)
    donor = _DONORS.setdefault((id(served), kw["paged_attn"]), be)
    if donor is not be:
        be.share_steps_from(donor)
    return be


def prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SIZES.vocab_size, n).tolist() for n in lengths]


def test_the_walk_and_what_it_reads_from_the_configuration(served):
    model = served.model
    assert isinstance(model, ExaoneMoe)
    assert isinstance(served.config, Lfm2MoeConfig)
    assert model.layer_counts == {"dense": N_DENSE, "moe": N_MOE,
                                  "full": N_FULL, "conv": N_CONV}
    assert model.step_stats[-3:] == ("conv_rows_advanced",
                                     "conv_states_reset", "kv_rows_appended")
    assert model.moe_forms == {
        "scoring": "sigmoid", "activation": "swiglu", "shared": False,
        "router_input": "post_attn_norm"}
    # the table is the head, the bias a parameter; attention's weights are a
    # stack over the two attention layers, the norms over all eight
    assert sorted(served.params) == ["attn", "conv", "dense", "embed",
                                     "final_norm", "moe"]
    assert sorted(served.params["moe"]) == ["bias", "router", "w_down",
                                            "w_gate_up"]
    assert sorted(served.params["conv"]) == ["conv_w", "w_in", "w_out"]
    assert served.params["attn"]["attn"]["w_qkv"].shape[0] == N_FULL
    assert served.params["attn"]["input_norm"].shape[0] == N_LAYERS
    full = model.attn["full"]
    assert full.rope and full.qk_norm and full.kv_pack == 2 \
        and full.window is None
    # the published 40 layers: seven layer bodies are traced, four of them
    # the period (here turned by two: conv, conv, full, conv) scanned 9 times
    pub = ExaoneMoe(Lfm2MoeConfig())
    assert pub.layer_counts == {"dense": 2, "moe": 38, "full": 10,
                                "conv": 30}
    assert pub.segments == (
        ((("conv", "dense"),), 2),
        ((("full", "moe"), ("conv", "moe")), 1),
        ((("conv", "moe"), ("conv", "moe"), ("full", "moe"),
          ("conv", "moe")), 9))
    c = pub.config
    assert (c.head_dim, c.kv_pack, c.kv_row_shapes[0], c.n_held) == \
        (64, 2, (4, 128), 64)
    assert c.slot_state_shapes == {"conv": ((4096,), jnp.bfloat16)}
    with pytest.raises(NotImplementedError, match="conv_bias"):
        Lfm2MoeConfig.tiny(conv_bias=True)
    with pytest.raises(ValueError, match="layer_types names"):
        Lfm2MoeConfig.tiny(layer_types=("conv", "mamba"))
    # the block the class was written for keeps its stats and its forms
    exa = ExaoneMoe(ExaoneMoeConfig.tiny())
    assert exa.step_stats[-2:] == ("moe_dropped_pairs", "kv_rows_appended")
    assert not exa.attn["full"].rope and exa.attn["full"].kv_pack == 1


# -- the operator alone, against the reference -----------------------------------

D, L, P, B = 32, 8, 5, 4       # width, chunk, rows of the block, slots
OP = dataclasses.replace(SIZES, d_model=D)


@pytest.fixture(scope="module")
def operator():
    """One ``ShortConv`` with drawn weights (state layer 1 of 2), the
    reference's name for the same weights, and its two step shapes."""
    layer = ShortConv(d_model=D, taps=3)
    lw = family.plain_operator(OP, jax.random.PRNGKey(3), True)

    @jax.jit
    def chunk(x, state, slots, offsets, lens):
        blk = nn.TokenBlock(0, L, offsets, None, lens > 0, lens, slots)
        return layer.fwd(lw, x, state, blocks=(blk,), layer=jnp.int32(1))

    def token(x, state, offsets, live, interpret):
        blk = nn.TokenBlock(0, 1, offsets, None, live, None)
        return layer.fwd(lw, x, state, blocks=(blk,), layer=jnp.int32(1),
                         interpret=interpret)

    return lw, chunk, jax.jit(token, static_argnums=4)


def dirty_state(seed):
    rng = np.random.default_rng(seed)
    return PagedKVState(
        kv=jnp.zeros((1, 1, 2, 1, 1, 1)),
        conv=jnp.asarray(rng.standard_normal((2, B, 2 * D)), jnp.float32))


def rows_of(runs):
    """(slot, cache length before, tokens) a run -> one entry a row."""
    rows = []
    for run in runs:
        if run is None:
            rows.append(None)
            continue
        slot, before, n = run
        rows += [(slot, before + at, min(L, n - at)) for at in range(0, n, L)]
    return rows + [None] * (P - len(rows))


def call_chunk(chunk, seqs, state, placed):
    """The gathered block with the rows ``placed`` (row -> (slot, cache
    length before, live)); every other row dead."""
    x = np.zeros((P, L, D), np.float32)
    ops = np.tile(np.int32([B - 1, 0, 0]), (P, 1))
    for k, (slot, at, n) in placed.items():
        ops[k] = slot, at, n
        x[k, :n] = seqs[slot][at:at + n]
    y, state = chunk(jnp.asarray(x.reshape(P * L, D)), state,
                     *jnp.asarray(ops.T))
    return np.asarray(y).reshape(P, L, D), state


def want_of(lw, seq):
    """The reference over one whole sequence, and the window it leaves:
    the last two values of ``B * X``."""
    with jax.default_matmul_precision("highest"):
        y = family.short_conv(OP, seq, lw, "float32")
        bcx = jnp.dot(seq[-2:], lw["w_in"])
    return np.asarray(y), np.asarray(bcx[:, :D] * bcx[:, 2 * D:]).reshape(-1)


CHUNKS = {
    "one chunk": [(1, 0, 8)],
    "a short chunk": [(2, 0, 5)],
    "chunks chained down a slot's run": [(2, 0, 21)],
    "a run that starts mid-prompt": [(0, 16, 19)],
    "a dead row between live ones": [(1, 0, 8), None, (3, 0, 6)],
    "two runs and a dead row": [(3, 0, 16), None, (0, 8, 11)],
}


@pytest.mark.parametrize("case", sorted(CHUNKS))
def test_the_operators_chunks_agree_with_the_reference(operator, case):
    """A block's rows in ONE call give what the reference's sum of three
    shifted products over each whole sequence gives, and what the same rows
    give one call a row; each run leaves its last two inputs in its slot's
    window and every other entry of the arena as it was, to the bit."""
    lw, chunk, _ = operator
    runs = [r for r in CHUNKS[case] if r is not None]
    rng = np.random.default_rng(len(case))
    seqs = {slot: jnp.asarray(rng.standard_normal((before + n, D)),
                              jnp.float32) for slot, before, n in runs}
    dirty = start = dirty_state(len(case))
    for slot, before, _ in runs:       # what came before a mid-prompt run
        for at in range(0, before, L):
            _, start = call_chunk(chunk, seqs, start,
                                  {0: (slot, at, min(L, before - at))})
    live = {k: r for k, r in enumerate(rows_of(CHUNKS[case]))
            if r is not None}
    got, state = call_chunk(chunk, seqs, start, live)
    state1 = start
    for k, (slot, at, n) in live.items():
        y, state1 = call_chunk(chunk, seqs, state1, {k: (slot, at, n)})
        np.testing.assert_allclose(got[k, :n], y[k, :n], atol=1e-6)
        np.testing.assert_allclose(
            got[k, :n], want_of(lw, seqs[slot])[0][at:at + n], atol=2e-5)
    touched = np.zeros((2, B), bool)
    for slot, _, _ in runs:
        touched[1, slot] = True
        np.testing.assert_allclose(state.conv[1, slot],
                                   want_of(lw, seqs[slot])[1], atol=1e-5)
        np.testing.assert_array_equal(state.conv[1, slot],
                                      state1.conv[1, slot])
    np.testing.assert_array_equal(np.asarray(state.conv)[~touched],
                                  np.asarray(dirty.conv)[~touched])


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["plain", "kernel"])
def test_decode_after_prefill_and_a_fresh_slot_after_a_used_one(operator,
                                                               interpret):
    """Slot 1 prefills 13 tokens in two chained rows and then decodes five,
    one token a step (the kernel under the interpreter, or its plain equal),
    beside a dead slot and slot 3, which STARTS with a decode step on a
    dirty window: every output is the reference's over the whole sequence.
    Then a new sequence takes slot 1 from cache length 0: it reads nothing of
    the one before."""
    lw, chunk, token = operator
    rng = np.random.default_rng(5)
    seqs = {s: jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
            for s, n in ((1, 18), (3, 5))}
    want = {s: want_of(lw, x) for s, x in seqs.items()}
    dirty = dirty_state(5)
    _, state = call_chunk(chunk, seqs, dirty, {0: (1, 0, 8), 1: (1, 8, 5)})
    for t in range(5):
        x = np.zeros((B, D), np.float32)
        x[1], x[3] = seqs[1][13 + t], seqs[3][t]
        y, state = token(jnp.asarray(x), state,
                         jnp.asarray([0, 13 + t, 7, t], jnp.int32),
                         jnp.asarray([False, True, False, True]), interpret)
        np.testing.assert_allclose(y[1], want[1][0][13 + t], atol=2e-5)
        np.testing.assert_allclose(y[3], want[3][0][t], atol=2e-5)
    for s in (1, 3):
        np.testing.assert_allclose(state.conv[1, s], want[s][1], atol=1e-5)
    # the dead slots' windows, and the other layer's, as they were
    keep = np.ones((2, B), bool)
    keep[1, [1, 3]] = False
    np.testing.assert_array_equal(np.asarray(state.conv)[keep],
                                  np.asarray(dirty.conv)[keep])
    fresh = {1: jnp.asarray(rng.standard_normal((6, D)), jnp.float32)}
    got, state = call_chunk(chunk, fresh, state, {2: (1, 0, 6)})
    np.testing.assert_allclose(got[2, :6], want_of(lw, fresh[1])[0],
                               atol=2e-5)
    np.testing.assert_allclose(state.conv[1, 1], want_of(lw, fresh[1])[1],
                               atol=1e-5)


@pytest.mark.parametrize("dtype,slots,taps", [
    ("float32", 4, 3), ("bfloat16", 32, 3), ("bfloat16", 16, 4)])
def test_the_update_kernel_equals_plain_jnp(dtype, slots, taps):
    """``short_conv_update`` under the interpreter against its plain equal:
    layer 1 of a 3-layer arena advanced and the others untouched; a dead
    row's window put back to the bit, a fresh row's read as zero. In
    bfloat16 the two are the same numbers; in float32 the compiled plain
    form fuses a product into a sum the interpreter rounds (1e-6)."""
    d = 128
    keys = jax.random.split(jax.random.PRNGKey(slots + taps), 5)
    arena = jax.random.normal(keys[0], (3, slots, (taps - 1) * d), dtype)
    bcx = jax.random.normal(keys[1], (slots, 3 * d), dtype)
    w = jax.random.normal(keys[2], (taps, d), dtype)
    live = jax.random.bernoulli(keys[3], 0.7, (slots,)).at[0].set(False)
    fresh = live & jax.random.bernoulli(keys[4], 0.4, (slots,))
    fresh = fresh.at[1].set(True) & live.at[1].set(True)
    live = live.at[1].set(True)
    got_a, got_y = short_conv_update(arena, 1, bcx, w, live, fresh,
                                     interpret=True)
    want_a, want_y = short_conv_update_reference(arena, 1, bcx, w, live,
                                                 fresh)
    f32 = np.float32
    tol = dict(atol=0) if dtype == "bfloat16" else dict(atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_a, f32),
                                  np.asarray(want_a, f32))
    np.testing.assert_allclose(np.asarray(got_y, f32)[np.asarray(live)],
                               np.asarray(want_y, f32)[np.asarray(live)],
                               **tol)
    assert got_y.dtype == bcx.dtype and got_a.dtype == arena.dtype
    np.testing.assert_array_equal(np.asarray(got_a, f32)[[0, 2]],
                                  np.asarray(arena, f32)[[0, 2]])
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(got_a, f32)[1][dead],
                                  np.asarray(arena, f32)[1][dead])
    # row 1 is fresh: its output is the last tap over B * X alone
    b, c, x = (np.asarray(bcx[1, i * d:(i + 1) * d], f32) for i in range(3))
    z = np.asarray(jnp.asarray(b * x, dtype), f32)
    np.testing.assert_allclose(
        np.asarray(got_y, f32)[1], c * np.asarray(w, f32)[-1] * z,
        rtol=1e-2 if dtype == "bfloat16" else 1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_a, f32)[1, 1, -d:], z)
    assert not np.asarray(got_a, f32)[1, 1, :-d].any()


# -- prefill then decode through the pool, against the reference -----------------

P_ROWS = 3                   # the hand-driven steps' prefill block: 3 rows of 8
TOKENS, = prompts(3, 50)


def logits_through_the_pool(engine):
    """One sequence through the step functions ``BatchEngine`` compiles, in
    slot 1 of two: the mixed step in its two-block form gives it ALL THREE
    rows of the prefill block (24 tokens: the window chained from row to row
    in six conv layers), then two rows (16), then 4 tokens of one row, then
    it decodes five tokens through the update's decode shape. Returns the
    logits at positions 43 (the last mixed step's) and 44..48."""
    pool = KVPool(engine.config, n_blocks=32, block_size=4, max_seq_len=128,
                  mesh=engine.mesh, n_slots=2)
    kw = dict(paged_attn="gather", state_specs=pool.specs)
    pre = jax.jit(engine._make_sm("dist", paged="prefill", **kw))
    dec = jax.jit(engine._make_sm("dist", paged="decode", **kw))
    assert pool.ensure("a", 51)
    tables = jnp.asarray(pool.padded_tables([None, "a"]))
    state, got, off = pool.state, [], 0
    for take in (24, 16, 4):
        chunk = np.zeros((P_ROWS, 8), np.int32)
        dealt = np.tile(np.int32([-1, 0, 0]), (P_ROWS, 1))
        for k in range(-(-take // 8)):
            toks = TOKENS[off + 8 * k:off + min(take, 8 * k + 8)]
            chunk[k, :len(toks)] = toks
            dealt[k] = 1, off + 8 * k, len(toks)
        logits, aux, state = pre(
            engine.params, (jnp.zeros((2,), jnp.int32), jnp.asarray(chunk),
                            jnp.asarray(dealt)), state,
            jnp.asarray([0, off], jnp.int32), tables,
            jnp.asarray([False, True]), jnp.asarray([0, take], jnp.int32))
        st = aux["stats"].tolist()
        assert st[0] == st[1] == take * TOPK * N_MOE and st[3] == 0
        assert st[4:] == [take * N_CONV, int(off == 0), take * N_FULL]
        off += take
    got.append(logits[1])                                  # position 43
    for k in range(5):
        logits, aux, state = dec(
            engine.params, jnp.asarray([[0], [TOKENS[44 + k]]], jnp.int32),
            state, jnp.asarray([0, 44 + k], jnp.int32), tables,
            jnp.asarray([False, True]))
        assert aux["stats"].tolist()[4:] == [N_CONV, 0, N_FULL]
        got.append(logits[1])
    return np.asarray(got)


def assert_logits_agree(got, tokens, first):
    """Float32 on both sides, so what separates them is the order of the
    sums (sorted expert tiles against one expert after another, a window
    carried through the arena against shifted products, eight layers deep):
    5e-5 on a logit of spread ~1. Computing any sub-layer in bfloat16
    (relative 4e-3) fails it by two orders; a tap on the wrong input, a
    rope angle or a window that was not zeroed fails it by three."""
    ref = ref_read(tokens + [0], first)
    for i, logits in enumerate(got):
        assert ref["best_token"][i] == int(logits.argmax())
        assert ref["best"][i] == pytest.approx(float(logits.max()), abs=5e-5)
        assert ref["std"][i] == pytest.approx(float(logits.std()), rel=1e-3)
        nxt = (tokens + [0])[first + i]
        assert ref["picked"][i] == pytest.approx(float(logits[nxt]),
                                                 abs=5e-5)


def test_prefill_then_decode_through_the_window_agrees_on_logits(served):
    assert_logits_agree(logits_through_the_pool(served), TOKENS[:49], 44)


def _with_params(engine, change):
    return Engine(engine.config, mesh=engine.mesh,
                  params=change(jax.tree.map(lambda a: a, engine.params)),
                  mode="dist")


def _taps_reversed(p):
    p["conv"] = dict(p["conv"], conv_w=p["conv"]["conv_w"][:, ::-1])
    return p


def _bias_left_out(p):
    p["moe"] = dict(p["moe"], bias=100.0 * p["moe"]["bias"])
    return p


def _nope(engine):
    wrong = Engine(engine.config, mesh=engine.mesh, params=engine.params,
                   mode="dist")
    attn = wrong.model.attn
    wrong.model.__dict__["attn"] = {
        k: dataclasses.replace(a, rope=False) for k, a in attn.items()}
    return wrong


FAULTS = {
    "taps-reversed": lambda e: _with_params(e, _taps_reversed),
    "another-bias": lambda e: _with_params(e, _bias_left_out),
    "no-rope-on-the-full-layers": _nope,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_no_longer_agrees(served, fault):
    """The taps in the wrong order, a selection bias that is not the
    reference's, full layers without rope: the same steps disagree."""
    got = logits_through_the_pool(FAULTS[fault](served))
    with pytest.raises(AssertionError):
        assert_logits_agree(got, TOKENS[:49], 44)


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_batch_engine_serves_what_the_reference_puts_first(served,
                                                           paged_attn):
    """Three requests of different lengths through ``BatchEngine`` on TWO
    slots, the third admitted mid-way into the slot the first one leaves
    (mixed and decode steps; the deal gives a prompt several rows of a
    step): every served token is the reference's best over the full forward
    pass, its logit within 1e-5 of the best (float32 both sides). The slot
    that is released and reused starts from a zero window, whatever the
    first request left there: ``conv_states_reset`` counts the three starts.
    The pool has a ``conv`` arena and NO ``ssm``, and no prefix cache."""
    be = batch_engine(served, paged_attn=paged_attn)
    assert be.pool.state.ssm is None and be.pool.state.wkv is None
    assert be.pool.state.conv.shape == (N_CONV, 2, 2 * SIZES.d_model)
    ps = prompts(5, 5, 27, 11)
    reqs = [be.submit(ps[0], 3), be.submit(ps[1], 9)]
    for _ in range(3):
        be.step()
    reqs.append(be.submit(ps[2], 6))
    be.run()
    be.pool.check_invariants()
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    assert be.prefix_cache is None and not be.pool.prefix_cacheable
    c = be.metrics.counters
    tokens = sum(len(p) for p in ps) + (3 + 9 + 6) - 3
    assert c["conv_rows_advanced"] == tokens * N_CONV
    assert c["kv_rows_appended"] == tokens * N_FULL
    assert c["conv_states_reset"] == 3
    assert c["moe_pairs_routed"] == c["moe_pairs_held"] == \
        tokens * TOPK * N_MOE
    assert c["prefill_rows_extra"] > 0          # the deal engages
    snap = be.stats_snapshot()
    assert snap["layers"] == {"dense": N_DENSE, "moe": N_MOE,
                              "full": N_FULL, "conv": N_CONV}
    assert snap["pool"]["slot_state_bytes"] == be.pool.state.conv.nbytes
    assert "slot[conv]" in be.pool.kv_fingerprint()
    for rid, prompt in zip(reqs, ps):
        out = be.finished[rid].output
        ref = ref_read(prompt + out, len(prompt))
        assert ref["best_token"].tolist() == out
        assert np.all(ref["best"] - ref["picked"] <= 1e-5)


def test_what_is_not_built_is_refused_by_name(served):
    pool = KVPool(served.config, n_blocks=8, block_size=4, n_slots=2)
    assert pool.geometry()["slot_state"] == {"conv": [N_CONV, 2, 128]}
    args = (served.params, jnp.zeros((2, 8), jnp.int32), pool.state,
            jnp.zeros((2,), jnp.int32), jnp.zeros((2, 32), jnp.int32),
            jnp.ones((2,), bool), jnp.ones((2,), jnp.int32))

    def step(**kw):
        return jax.jit(served._make_sm(
            "dist", paged="prefill", paged_attn="gather",
            **{"state_specs": pool.specs, **kw}))

    with pytest.raises(NotImplementedError, match="convolution's window"):
        step(spec_verify=True).lower(*args)
    with pytest.raises(ValueError, match="no per-slot conv arena"):
        rows = KVPool(ExaoneMoeConfig.tiny(sliding_windows=(0,) * 4,
                                           layer_types=("full_attention",) * 4,
                                           n_kv_heads=1, head_dim=32),
                      n_blocks=8, block_size=4)
        step(state_specs=rows.specs).lower(args[0], args[1], rows.state,
                                           *args[3:])
    with pytest.raises(ValueError, match="needs n_slots"):
        KVPool(served.config, n_blocks=6, block_size=4)
    with pytest.raises(NotImplementedError, match="no quantized"):
        KVPool(served.config, n_blocks=6, block_size=4, n_slots=2,
               kv_dtype="int8")
    mesh2 = make_mesh({"tp": 2}, devices=jax.devices()[:2], set_default=False)
    # (heads of 128, so that the pool's rows are not packed into one)
    engine = Engine(Lfm2MoeConfig.tiny(d_model=256, n_heads=2), mesh=mesh2,
                    mode="dist")
    pool2 = KVPool(engine.config, n_blocks=8, block_size=4, mesh=mesh2,
                   n_slots=2)
    dec = jax.jit(engine._make_sm("dist", paged="decode", paged_attn="gather",
                                  state_specs=pool2.specs))
    with pytest.raises(NotImplementedError, match="conv layers under it"):
        dec.lower(engine.params, jnp.zeros((2, 1), jnp.int32), pool2.state,
                  jnp.zeros((2,), jnp.int32), jnp.zeros((2, 16), jnp.int32),
                  jnp.ones((2,), bool))


# -- routing, and the share tied to the model ------------------------------------

E_D, E_N, E_FF = 32, 64, 16


def expert_layer(held=E_N, lo=0):
    return HeldExpertsMoE(d_model=E_D, d_ff=E_FF, n_experts=E_N, topk=4,
                          n_held=held, lo=lo, dtype=jnp.float32)


def test_the_bias_changes_the_choice_and_not_the_weights():
    """A hand-made token whose scores are sigmoid(2, 1, 0.9, 0.8, 0.7, -3,
    ...): without a bias experts 0-3 are chosen; a bias of 0.5 on expert 4
    puts it in for expert 3, and its weight is its UNBIASED score over the
    sum of the four chosen scores. The program's layer and the reference
    alike."""
    logits = np.full((E_N,), -3.0, np.float32)
    logits[:5] = 2.0, 1.0, 0.9, 0.8, 0.7
    router = jnp.zeros((E_D, E_N)).at[0].set(logits)
    x = jnp.zeros((1, E_D)).at[0, 0].set(1.0)
    s = 1 / (1 + np.exp(-logits))
    m = dataclasses.replace(SIZES, d_model=E_D, router_width=E_N, topk=4)
    for bias, chosen in ((np.zeros(E_N, np.float32), [0, 1, 2, 3]),
                         (np.eye(E_N, dtype=np.float32)[4] * 0.5,
                          [0, 1, 2, 4])):
        want = s[chosen] / s[chosen].sum()
        for w, ids in (expert_layer().route(router, jnp.asarray(bias), x),
                       family.routing(m, x, router, jnp.asarray(bias))):
            order = np.argsort(np.asarray(ids[0]))
            assert np.asarray(ids[0])[order].tolist() == chosen
            np.testing.assert_allclose(np.asarray(w[0])[order], want,
                                       rtol=1e-5)


def test_eight_shares_of_eight_add_up_to_the_uncut_references_layer():
    """One expert layer of 64 at the published top-4 with a bias: the eight
    chips' shares of 8 experts each (the program's layer, told which experts
    it holds; weights normalised over ALL four chosen, whoever holds them)
    add up to what the reference gives for the whole layer, every pair with
    one owner; so do the reference's own shares."""
    m = dataclasses.replace(SIZES, d_model=E_D, expert_width=E_FF,
                            router_width=E_N, held=E_N, topk=4)
    key = jax.random.PRNGKey(11)
    lw = reference.f32(family.plain_ffn(m, key, True))
    lw["bias"] = 20.0 * lw["bias"]            # so that it changes choices
    # the router every share of 8 has: its rows dealt evenly to the chips
    lw["router"] = family.plain_ffn(dataclasses.replace(m, held=8), key,
                                    True)["router"]
    x = jax.random.normal(jax.random.PRNGKey(12), (24, E_D))
    want = family.routed_part(m, x, lw, "float32")
    plain = family.routed_part(m, x, dict(lw, bias=0.0 * lw["bias"]),
                               "float32")
    assert float(jnp.abs(plain - want).max()) > 1e-2
    total = ref_total = 0.0
    held_pairs = 0
    for lo in range(0, E_N, 8):
        sm = dataclasses.replace(m, held=8, lo=lo)
        slw = reference.f32(family.plain_ffn(sm, key, True))
        np.testing.assert_array_equal(slw["e_gu"], lw["e_gu"][lo:lo + 8])
        np.testing.assert_array_equal(slw["router"], lw["router"])
        ref_total = ref_total + family.routed_part(
            sm, x, dict(slw, bias=lw["bias"]), "float32")
        y, st = expert_layer(held=8, lo=lo).routed(
            {"router": lw["router"], "bias": lw["bias"],
             "w_gate_up": slw["e_gu"], "w_down": slw["e_d"]}, x)
        total, held_pairs = total + y, held_pairs + int(st[1])
        assert int(st[0]) == 24 * 4 and int(st[3]) == 0
    assert held_pairs == 24 * 4
    np.testing.assert_allclose(ref_total, want, atol=5e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)


# -- the window code that Mamba-2 shares: its models' steps unchanged ------------

class Mamba2AsBefore(Mamba2):
    """``layers.mamba2.Mamba2`` as PR 40 wrote it, its window arithmetic
    inside ``_conv`` / ``_block``: the oracle of the move into
    ``layers.short_conv`` (``slot_rows``, ``conv_window``)."""

    def _conv(self, params, window, xbc, n_live):
        K, L = self.d_conv, xbc.shape[1]
        seq = jnp.concatenate([window.astype(jnp.float32),
                               xbc.astype(jnp.float32)], axis=1)
        w = params["conv_w"].astype(jnp.float32)
        out = params["conv_b"].astype(jnp.float32) + sum(
            w[k] * seq[:, k:k + L] for k in range(K))
        take = n_live[:, None] + jnp.arange(K - 1)[None]
        window = jnp.take_along_axis(seq, take[..., None], axis=1)
        return jax.nn.silu(out), window

    def _block(self, params, zxbcdt, state, blk, layer, interpret):
        R, L = blk.offsets.shape[0], blk.L
        H, P, N, G = self.n_heads, self.d_head, self.d_state, self.n_groups
        di, C, K = self.d_inner, self.conv_dim, self.d_conv
        part = zxbcdt[blk.start:blk.stop].reshape(R, L, -1)
        xbc, dt = part[..., di:di + C], part[..., di + C:]
        live = blk.valid().reshape(R, L)
        n_live = jnp.sum(live, axis=1)
        fresh = (blk.offsets == 0) & (n_live > 0)
        whole = blk.slots is None
        slots = jnp.arange(R) if whole else blk.slots
        writes = n_live > 0
        chained = None
        if not whole:
            chained = chained_rows(slots, blk.offsets, n_live, L)
            writes &= ~jnp.roll(chained, -1)
        put = jnp.where(writes, slots, state.conv.shape[1])
        held = (jax.lax.dynamic_index_in_dim(state.conv, layer, 0, False)
                if whole else state.conv[layer, slots])
        window = jnp.where(fresh[:, None, None], 0,
                           held.reshape(R, K - 1, C))
        if chained is not None:
            window = jnp.where(
                chained[:, None, None],
                jnp.roll(xbc[:, L - (K - 1):], 1, axis=0).astype(held.dtype),
                window)
        conv, window = self._conv(params, window, xbc, n_live)
        window = window.reshape(R, -1).astype(held.dtype)
        if whole:
            conv_arena = jax.lax.dynamic_update_index_in_dim(
                state.conv, jnp.where((n_live > 0)[:, None], window, held),
                layer, 0)
        else:
            conv_arena = state.conv.at[layer, put].set(window, mode="drop")
        x = conv[..., :di].reshape(R, L, H, P)
        b = conv[..., di:di + G * N].reshape(R, L, G, N)
        c = conv[..., di + G * N:].reshape(R, L, G, N)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + params["dt_bias"].astype(jnp.float32))
        dt = jnp.where(live[..., None], dt, 0.0)
        a_log_step = dt * -jnp.exp(params["a_log"].astype(jnp.float32))
        if L == 1 and whole:
            decay = jnp.where(fresh[:, None], 0.0, jnp.exp(a_log_step[:, 0]))
            ssm, y = ssm_state_update(
                state.ssm, layer, decay, dt[:, 0, :, None] * x[:, 0],
                b[:, 0], c[:, 0], interpret=interpret)
            y = y[:, None]
        else:
            s0 = jnp.where(fresh[:, None, None, None], 0.0,
                           state.ssm[layer, slots])
            y, s = chunk_scan(x, dt, a_log_step, b, c, s0, chained)
            ssm = state.ssm.at[layer, put].set(s, mode="drop")
        y = y + params["d_skip"].astype(jnp.float32)[:, None] * x
        state = dataclasses.replace(state, ssm=ssm, conv=conv_arena)
        return y.reshape(R * L, di), state


@pytest.mark.parametrize("config", [GraniteHybridConfig, NemotronHConfig],
                         ids=["granite", "nemotron"])
def test_the_shared_window_code_leaves_the_mamba_models_steps_to_the_bit(
        mesh, config):
    """Granite's and Nemotron's tiny steps with ``Mamba2`` calling the
    shared window function, against the same steps with the layer as it was
    before the move: THE SAME NUMBERS TO THE BIT, logits and every arena of
    the pool's state, over a mixed step that gives slot 1 three chained rows
    beside a dead row, one that starts slot 0 fresh beside slot 1's decode
    row, and a decode step of both (a convolution bias that is not zero)."""
    now = Engine(config.tiny(), mesh=mesh, mode="dist")
    flat, tree = jax.tree_util.tree_flatten_with_path(now.params)
    now.params = jax.tree.unflatten(tree, [
        0.1 * jax.random.normal(jax.random.PRNGKey(7), a.shape, a.dtype)
        if path[-1].key == "conv_b" else a for path, a in flat])
    was = Engine(now.config, mesh=mesh, params=now.params, mode="dist")
    was.model.__dict__["mamba"] = Mamba2AsBefore(
        **dataclasses.asdict(now.model.mamba))
    rng = np.random.default_rng(9)
    toks = rng.integers(0, now.config.vocab_size, 64).astype(np.int32)

    def run(engine):
        pool = KVPool(engine.config, n_blocks=32, block_size=4,
                      max_seq_len=64, mesh=mesh, n_slots=2)
        kw = dict(paged_attn="gather", state_specs=pool.specs)
        pre = jax.jit(engine._make_sm("dist", paged="prefill", **kw))
        dec = jax.jit(engine._make_sm("dist", paged="decode", **kw))
        assert pool.ensure("a", 30) and pool.ensure("b", 30)
        tables = jnp.asarray(pool.padded_tables(["b", "a"]))
        out, state = [], pool.state

        def mixed(tok, rows, offsets, takes):
            chunk = np.zeros((4, 8), np.int32)
            dealt = np.tile(np.int32([-1, 0, 0]), (4, 1))
            for k, (slot, at, n, src) in enumerate(rows):
                if slot is not None:
                    chunk[k, :n] = toks[src:src + n]
                    dealt[k] = slot, at, n
            return pre(engine.params,
                       (jnp.asarray(tok, jnp.int32), jnp.asarray(chunk),
                        jnp.asarray(dealt)), state,
                       jnp.asarray(offsets, jnp.int32), tables,
                       jnp.asarray([t > 0 for t in takes]),
                       jnp.asarray(takes, jnp.int32))

        logits, _, state = mixed(
            [0, 0], [(1, 0, 8, 0), (1, 8, 8, 8), (1, 16, 4, 16),
                     (None,) * 4], [0, 0], [0, 20])
        out.append(logits[1])
        logits, _, state = mixed(
            [0, toks[20]], [(None,) * 4, (0, 0, 5, 40)], [0, 20], [5, 1])
        out.append(logits)
        logits, _, state = dec(
            engine.params, jnp.asarray([[toks[45]], [toks[21]]], jnp.int32),
            state, jnp.asarray([5, 21], jnp.int32), tables,
            jnp.asarray([True, True]))
        out.append(logits)
        return out, state

    got, got_state = run(now)
    want, want_state = run(was)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(g, w)
    for g, w in zip(jax.tree.leaves(got_state), jax.tree.leaves(want_state)):
        np.testing.assert_array_equal(g, w)
    assert float(jnp.abs(got_state.conv).max()) > 0


# -- the published configuration --------------------------------------------------

def test_counts_of_the_published_configuration():
    """The family's counts at LFM2-24B-A2B's sizes, one chip of eight with
    all 40 layers, against the issue's hand count: 3.761 B parameters held
    (7.52 GB), 20,480 B of rows a token, 7.9 MB of windows for 32 slots, a
    decode step of 32 rows at contexts of 1,800 reading at least 8.0 GB."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "perfbench/configs/lfm2-24b-a2b-ep8.json")) as f:
        cfg = json.load(f)
    m = family.sizes(cfg)
    assert (m.n_layers, m.n_conv, m.n_full, m.n_sparse) == (40, 30, 10, 38)
    assert [i for i, c in enumerate(m.conv) if not c] == list(range(2, 40, 4))
    assert (m.held, m.router_width, m.topk, m.vocab_size, m.max_length) == \
        (8, 64, 4, 65_536, 4096)
    assert family.expert_params(m) == 3 * 2048 * 1536
    assert family.conv_params(m) == 4 * 2048 ** 2 + 3 * 2048
    assert family.attn_params(m) == 2 * 2048 * 40 * 64
    assert family.params_held(m) == pytest.approx(3.761e9, rel=1e-3)
    assert m.n_full * m.row_bytes == 20_480
    pairs, touched = family.moe_expected(m, 32)
    assert pairs == 38 * 32 * 4 / 8                      # 2 a held expert
    assert touched / 38 / 8 == pytest.approx(0.873, abs=1e-3)
    contexts = [1800] * 32
    step = family.decode_step_min_bytes(m, contexts)
    assert step == pytest.approx(
        family.weight_bytes_read(m, touched) + 32 * 30 * 3 * 2048 * 2
        + 32 * 1800 * 20_480)
    assert step == pytest.approx(8.0e9, rel=0.01)
    assert family.moe_ffn_min_bytes(m, touched) / step == pytest.approx(
        0.63, abs=0.01)
    # ONE summed context, as the step roofline's reader hands it: one row's
    # experts and windows, fewer bytes and never more
    assert family.decode_step_min_bytes(m, [sum(contexts)]) < step
    assert family.decode_step_min_bytes(m, [1000]) > \
        family.decode_step_min_bytes(m, [0]) > 0
    # a row of a layer through the update: two held inputs read and one
    # written, 3 x 2,048 values of 2 bytes (activations need not touch HBM)
    assert family.short_conv_min_bytes(m, 1) == 3 * 2048 * 2
    assert family.short_conv_flops(m, 30 * 32) == 7 * 2048 * 30 * 32
    # the program's own configuration object, and what its pool holds
    mcfg = family.program_config(cfg, m)
    assert isinstance(mcfg, Lfm2MoeConfig)
    assert dataclasses.replace(
        mcfg, model_name=Lfm2MoeConfig.model_name, experts_held=None,
        max_length=4096) == Lfm2MoeConfig()
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(
        ExaoneMoe(mcfg).param_shapes(),
        is_leaf=lambda x: isinstance(x, tuple)))
    assert n - 2048 * (1 + 2 * 40) - 10 * 2 * 64 - 38 * 64 == \
        family.params_held(m)
    from triton_distributed_tpu.serving.kv_pool import paged_state_shapes

    fleet = cfg["serve"]["fleet"]
    state = paged_state_shapes(
        mcfg, n_blocks=fleet["n_blocks"], block_size=fleet["block_size"],
        n_slots=fleet["n_slots"])
    assert state.ssm is None and state.wkv is None
    assert state.conv.shape == (30, 32, 4096)
    assert state.conv.dtype == jnp.bfloat16
    # a block's pair is 32 KiB: one copy where two arenas took two of 16
    assert state.kv.shape == (10, 3328, 2, 16, 4, 128)
    nbytes = {f: int(np.prod(a.shape)) * a.dtype.itemsize
              for f in ("kv", "conv") if (a := getattr(state, f))}
    assert nbytes["conv"] == pytest.approx(7.9e6, rel=0.01)
    assert nbytes["kv"] == pytest.approx(1.09e9, rel=2e-3)
    assert 2 * family.params_held(m) + sum(nbytes.values()) == \
        pytest.approx(8.6e9, rel=0.01)
