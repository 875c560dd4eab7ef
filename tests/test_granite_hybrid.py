"""The Granite-4.0-H block (``models/granite_hybrid.py``: Mamba-2 layers that
keep a fixed-size state a slot beside attention layers that keep rows in
the paged pool) against the benchmark's plain reference
(``perfbench/families/granite_hybrid.py``: one sequential scan over the
positions), at tiny float32 sizes on the CPU. ``paged_attn="gather"``
wherever the fused kernel is not the thing tested.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference, weights
from perfbench.families import granite_hybrid as family
from triton_distributed_tpu.kernels.ssm_update import (
    ssm_state_update,
    ssm_state_update_reference,
)
from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.mamba2 import Mamba2, draw_own
from triton_distributed_tpu.models.config import (
    GraniteHybridConfig,
    ModelConfig,
)
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.obs import trace as _trace
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving.batch_engine import BatchEngine
from triton_distributed_tpu.serving.kv_pool import KVPool, PagedKVState

# Two periods of (Mamba-2, attention, Mamba-2); two key heads of 16 packed
# into one row of 32.
SIZES = family.Sizes(
    vocab_size=256, d_model=64, layer_types=("mamba", "attention", "mamba") * 2,
    heads=4, kv_heads=2, mlp_width=96, ssm_heads=4, ssm_head_width=8,
    ssm_state=16, ssm_conv=4, ssm_groups=2, embedding_multiplier=12.0,
    residual_multiplier=0.22, attention_multiplier=1 / 16,
    logits_scaling=8.0, eps=1e-5, max_length=64, dtype="float32")
SEED = 41
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


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
    """A ``BatchEngine`` at the tests' geometry. Engines of one geometry
    share their compiled steps (``share_steps_from``, what an elastic spawn
    does), so a test that builds several compiles once."""
    kw = {**dict(n_slots=4, n_blocks=48, block_size=4, prefill_chunk=8,
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


def test_engine_picks_the_model_from_the_configuration_object(served):
    from triton_distributed_tpu.models.granite_hybrid import GraniteHybrid
    from triton_distributed_tpu.models.qwen import Qwen3

    assert isinstance(served.model, GraniteHybrid)
    assert served.model.pattern == ("mamba", "attention", "mamba")
    assert isinstance(Engine(ModelConfig.from_name("tiny"), mesh=served.mesh,
                             mode="xla").model, Qwen3)
    # the published pattern is one period of ten
    assert GraniteHybrid(GraniteHybridConfig()).pattern == \
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def paged_steps(served, n_slots, n_blocks=24):
    pool = KVPool(served.config, n_blocks=n_blocks, block_size=4,
                  mesh=served.mesh, n_slots=n_slots)
    kw = dict(paged_attn="gather", state_specs=pool.specs)
    return (pool, jax.jit(served._make_sm("dist", paged="prefill", **kw)),
            jax.jit(served._make_sm("dist", paged="decode", **kw)))


def logits_of_a_staggered_batch(engine, decode: bool = True):
    """Three slots through the step functions ``BatchEngine`` compiles, the
    mixed step in its two-block form: sequence a prefills in chunks of 8
    (8, 8, 3) and then decodes; b is admitted one step later (5 tokens,
    then decodes beside a's prefill: a decode-block row beside a
    prefill-block row); slot 1 stays empty. Returns the logits of a at
    positions 18, 19, 20 and of b at 4, 5, 6, 7 (without ``decode``, those
    the three mixed steps give: a at 18, b at 4, 5)."""
    a, b = TOKENS_A, TOKENS_B
    pool, pre, dec = paged_steps(engine, 3)
    assert pool.ensure("a", 21) and pool.ensure("b", 8)
    tables = jnp.asarray(pool.padded_tables(["a", None, "b"]))
    state, got_a, got_b = pool.state, [], []

    def mixed(state, off, lens, tok, chunk_rows):
        chunk = np.zeros((2, 8), np.int32)
        for k, row in enumerate(chunk_rows):
            chunk[k, :len(row)] = row
        live = jnp.asarray([n > 0 for n in lens])
        # one row a slot that takes more than a token, in slot order
        dealt = [[i, off[i], n] for i, n in enumerate(lens) if n > 1]
        dealt += [[-1, 0, 0]] * (2 - len(dealt))
        return pre(engine.params,
                   (jnp.asarray(tok, jnp.int32), jnp.asarray(chunk),
                    jnp.asarray(dealt, jnp.int32)), state,
                   jnp.asarray(off, jnp.int32), tables, live,
                   jnp.asarray(lens, jnp.int32))

    # step 1: a[0:8] alone
    logits, aux, state = mixed(state, [0, 0, 0], [8, 0, 0], [0, 0, 0],
                               [a[0:8]])
    assert aux["stats"].tolist() == [8 * 4, 1, 8 * 2]
    # step 2: a[8:16] beside b[0:5] (two rows of the prefill block)
    logits, aux, state = mixed(state, [8, 0, 0], [8, 0, 5], [0, 0, 0],
                               [a[8:16], b[0:5]])
    assert aux["stats"].tolist() == [13 * 4, 1, 13 * 2]
    got_b.append(logits[2])                                # b position 4
    # step 3: a[16:19] in the prefill block, b[5] in the decode block
    logits, aux, state = mixed(state, [16, 0, 5], [3, 0, 1], [0, 0, b[5]],
                               [a[16:19]])
    assert aux["stats"].tolist() == [4 * 4, 0, 4 * 2]
    got_a.append(logits[0])                                # a position 18
    got_b.append(logits[2])                                # b position 5
    # steps 4, 5: both decode
    for k in range(2 if decode else 0):
        logits, aux, state = dec(
            engine.params,
            jnp.asarray([[a[19 + k]], [0], [b[6 + k]]], jnp.int32), state,
            jnp.asarray([19 + k, 0, 6 + k], jnp.int32), tables,
            jnp.asarray([True, False, True]))
        got_a.append(logits[0])
        got_b.append(logits[2])
    assert aux["stats"].tolist()[0] == (2 if decode else 4) * 4
    assert jax.tree.structure(state) == jax.tree.structure(pool.state)
    return np.asarray(got_a), np.asarray(got_b)


TOKENS_A, TOKENS_B = prompts(3, 21, 8)


def assert_logits_agree(got, tokens, first):
    ref = ref_read(tokens + [0], first)
    for i, logits in enumerate(got):
        assert ref["best_token"][i] == int(logits.argmax())
        assert ref["best"][i] == pytest.approx(float(logits.max()), abs=2e-5)
        assert ref["std"][i] == pytest.approx(float(logits.std()), rel=1e-3)
        nxt = (tokens + [0])[first + i]
        assert ref["picked"][i] == pytest.approx(float(logits[nxt]),
                                                 abs=2e-5)


def test_prefill_then_decode_of_rows_admitted_at_different_steps_agrees_on_logits(
        served):
    """Chunked prefill with a ragged last chunk, a decode row beside a
    prefilling one, then decode steps, against the reference's ONE full
    forward pass of each sequence: the best logit, its token, the next
    token's logit and the row's spread at each position read."""
    got_a, got_b = logits_of_a_staggered_batch(served)
    assert_logits_agree(got_a, TOKENS_A, 19)       # positions 18, 19, 20
    assert_logits_agree(got_b, TOKENS_B, 5)        # positions 4, 5, 6, 7


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_changes_the_result(served, name):
    """The same batch with ONE of the four multipliers set to 1 no longer
    agrees with the reference."""
    wrong = Engine(dataclasses.replace(served.config, **{name: 1.0}),
                   mesh=served.mesh, params=served.params, mode="dist")
    got_a, _ = logits_of_a_staggered_batch(wrong, decode=False)
    with pytest.raises(AssertionError):
        assert_logits_agree(got_a, TOKENS_A, 19)


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_batch_engine_serves_what_the_reference_puts_first(served,
                                                           paged_attn):
    """Requests of several lengths through ``BatchEngine``, one of them
    submitted after the others have started (admission, chunked prefill
    beside decode rows, the state update's kernel under the interpreter,
    the packed rows' block tables): every served token is the reference's
    best at its position."""
    _trace.get_tracer().reset()
    _trace.enable()
    try:
        be = batch_engine(served, paged_attn=paged_attn)
        ps = prompts(5, 5, 11, 17, 9)
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
    assert be.prefix_cache is None
    c = be.metrics.counters
    tokens = sum(len(p) for p in ps) + 4 * 5
    assert c["ssm_rows_advanced"] == tokens * 4
    assert c["kv_rows_appended"] == tokens * 2
    assert c["ssm_states_reset"] == 4
    assert "prefix_cached_tokens" not in c
    assert sum(r.attrs["ssm_rows_advanced"] for r in spans) == tokens * 4
    snap = be.stats_snapshot()["pool"]
    assert snap["slot_state_bytes"] == be.pool.state.ssm.nbytes \
        + be.pool.state.conv.nbytes > 0
    for rid, prompt in zip(reqs, ps):
        assert_served_is_the_references_best(prompt, be.finished[rid].output)


def test_the_chunk_scan_equals_the_sequential_one_with_a_ragged_last_chunk(
        served):
    """One Mamba-2 layer over 21 positions in chunks of 8 (8, 8, 5 live of
    8), its state and window carried from chunk to chunk in slot 1 of a
    3-slot arena, against the reference's sequential scan of the whole
    sequence; then the same through single-token steps (the kernel)."""
    layer = served.model.mamba
    lp = jax.tree.map(lambda a: a[1, 0],
                      served.params["periods"]["mamba"]["mixer"])
    lw = reference.f32(family.plain_layer(
        SIZES, weights.keys(SEED, SIZES.n_layers)[1][3], True))
    np.testing.assert_array_equal(lp["w_in"], lw["w_in"])
    x = jax.random.normal(jax.random.PRNGKey(2), (21, SIZES.d_model))
    with jax.default_matmul_precision("highest"):
        want = family.ssm_mixer(SIZES, x, lw, "float32")
    pool = KVPool(served.config, n_blocks=4, block_size=4, n_slots=3)
    dirty = jax.tree.map(lambda a: None if a is None else a + 3.0,
                         pool.state)      # what an earlier tenant left

    def fwd(part, state, offsets, lens, *, L):
        blk = nn.TokenBlock(0, L, offsets, None, lens > 0,
                            None if L == 1 else lens)
        return layer.fwd(lp, part, state, blocks=(blk,), layer=jnp.int32(2),
                         interpret=True)

    fwd = jax.jit(fwd, static_argnames="L")

    def run(take):
        state, outs, done = dirty, [], 0
        while done < 21:
            n = min(take, 21 - done)
            L = take if take > 1 else 1
            part = jnp.zeros((3, L, SIZES.d_model)).at[1, :n].set(
                x[done:done + n])
            out, state = fwd(part.reshape(3 * L, -1), state,
                             jnp.asarray([0, done, 0], jnp.int32),
                             jnp.asarray([0, n, 0], jnp.int32), L=L)
            outs.append(out.reshape(3, L, -1)[1, :n])
            done += n
        return jnp.concatenate(outs), state

    got, state = run(8)
    np.testing.assert_allclose(got, want, atol=2e-5)
    one, state1 = run(1)
    np.testing.assert_allclose(one, want, atol=2e-5)
    np.testing.assert_allclose(state1.ssm[2, 1], state.ssm[2, 1], atol=1e-5)
    np.testing.assert_allclose(state1.conv[2, 1], state.conv[2, 1],
                               atol=1e-6)
    # the other slots and the other layers are as they were, to the bit
    for s in (state, state1):
        for got_a, was in ((s.ssm, dirty.ssm), (s.conv, dirty.conv)):
            keep = np.ones(got_a.shape[:2], bool)
            keep[2, 1] = False
            np.testing.assert_array_equal(np.asarray(got_a)[keep],
                                          np.asarray(was)[keep])


# Rows of one slot chained through the chunk scan. A case is the prefill
# block as the host could deal it: runs ``(slot, cache length before the
# run, tokens)`` and dead rows (None), rows of 8 in a block of 7 over an
# arena of 4 slots (a dead row names the last, which no run uses).
CHAIN_L, CHAIN_P, CHAIN_SLOTS = 8, 7, 4
CHAINS = {
    "one slot: full, full, ragged last": [(1, 0, 21)],
    "two slots' runs side by side": [(0, 0, 20), (2, 0, 13)],
    "a dead row between and after runs": [(0, 0, 16), None, (1, 0, 11),
                                          None],
    "one run from zero, one mid-prompt from the arena": [(0, 0, 12),
                                                         (1, 8, 17)],
    "the last full row ends at the prompt's end": [(2, 0, 24), (0, 0, 8)],
}


@functools.cache
def chain_layer(groups):
    """One ``Mamba2`` layer of 8 heads in ``groups`` groups with drawn
    weights, the reference's view of the same weights, and the layer's
    forward over one gathered block (state layer 1 of 2)."""
    m = dataclasses.replace(SIZES, d_model=32, ssm_heads=8, ssm_head_width=4,
                            ssm_groups=groups)
    layer = Mamba2(d_model=32, n_heads=8, d_head=4, d_state=m.ssm_state,
                   d_conv=m.ssm_conv, n_groups=groups, rms_eps=m.eps,
                   dtype=jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(groups), 8)
    lp = {name: (draw_own(name, k, shape) if fan_in is None else
                 jax.random.normal(k, shape) / np.sqrt(fan_in))
          for k, (name, (shape, fan_in))
          in zip(keys, layer.param_shapes().items())}
    lp["conv_b"] = 0.1 * jax.random.normal(keys[0], lp["conv_b"].shape)

    @jax.jit
    def fwd(x, state, slots, offsets, lens):
        blk = nn.TokenBlock(0, CHAIN_L, offsets, None, lens > 0, lens, slots)
        return layer.fwd(lp, x, state, blocks=(blk,), layer=jnp.int32(1),
                         interpret=True)

    return m, {**lp, "gate_norm": lp["norm"]}, fwd


@pytest.mark.parametrize("groups", [1, 8])
@pytest.mark.parametrize("case", sorted(CHAINS))
def test_rows_of_one_slot_are_chained_through_the_chunk_scan(case, groups):
    """A slot's run of rows in ONE call of the layer gives the ``y``, the
    final state and the final window that the same rows give one call a
    row (no row chained: the path a block of distinct slots takes) and that
    the reference's sequential scan of the whole sequence gives. The arena
    ends on each run's LAST row; dead rows and rows that are followed write
    nothing, so every other entry is as it was, to the bit."""
    m, lw, fwd = chain_layer(groups)
    L, P, B = CHAIN_L, CHAIN_P, CHAIN_SLOTS
    K, C = m.ssm_conv, m.conv_width
    rng = np.random.default_rng(len(case))
    dirty = PagedKVState(
        kv=jnp.zeros((1, 1, 2, 1, 1, 1)),
        ssm=jnp.asarray(rng.standard_normal(
            (2, B, m.ssm_heads, m.ssm_head_width, m.ssm_state)), jnp.float32),
        conv=jnp.asarray(rng.standard_normal((2, B, (K - 1) * C)),
                         jnp.float32))
    runs = [r for r in CHAINS[case] if r is not None]
    seqs = {slot: jnp.asarray(rng.standard_normal((before + n, m.d_model)),
                              jnp.float32) for slot, before, n in runs}
    rows = []                      # (slot, cache length before, live) a row
    for run in CHAINS[case]:
        if run is None:
            rows.append(None)
            continue
        slot, before, n = run
        rows += [(slot, before + at, min(L, n - at)) for at in range(0, n, L)]
    rows += [None] * (P - len(rows))
    assert len(rows) == P

    def call(state, placed):
        """The block with the rows ``placed`` (row of the block -> (slot,
        cache length before, live)); every other row dead."""
        x = np.zeros((P, L, m.d_model), np.float32)
        ops = np.tile(np.int32([B - 1, 0, 0]), (P, 1))
        for k, (slot, at, n) in placed.items():
            ops[k] = slot, at, n
            x[k, :n] = seqs[slot][at:at + n]
        y, state = fwd(jnp.asarray(x.reshape(P * L, -1)), state,
                       *jnp.asarray(ops.T))
        return np.asarray(y).reshape(P, L, -1), state

    # a run that starts mid-prompt: its slot's arena holds what came before
    start = dirty
    for slot, before, _ in runs:
        for at in range(0, before, L):
            _, start = call(start, {0: (slot, at, min(L, before - at))})
    live = {k: r for k, r in enumerate(rows) if r is not None}
    got, state = call(start, live)
    apart, state1 = np.zeros_like(got), start
    for k, row in live.items():
        y, state1 = call(state1, {k: row})
        apart[k] = y[k]
    with jax.default_matmul_precision("highest"):
        want = {slot: np.asarray(family.ssm_mixer(m, x, lw, "float32"))
                for slot, x in seqs.items()}
    for k, (slot, at, n) in live.items():
        np.testing.assert_allclose(got[k, :n], apart[k, :n], atol=1e-5)
        np.testing.assert_allclose(got[k, :n], want[slot][at:at + n],
                                   atol=2e-5)
    touched = np.zeros((2, B), bool)
    for slot, before, n in runs:
        touched[1, slot] = True
        np.testing.assert_allclose(state.ssm[1, slot], state1.ssm[1, slot],
                                   atol=1e-5)
        # the window: the run's last K-1 raw inputs of the convolution
        xbc = jnp.dot(seqs[slot][-(K - 1):], lw["w_in"],
                      precision="highest")[:, m.d_inner:m.d_inner + C]
        np.testing.assert_allclose(state.conv[1, slot], xbc.reshape(-1),
                                   atol=1e-5)
        np.testing.assert_allclose(state.conv[1, slot], state1.conv[1, slot],
                                   atol=1e-6)
    for got_a, was in ((state.ssm, dirty.ssm), (state.conv, dirty.conv)):
        np.testing.assert_array_equal(np.asarray(got_a)[~touched],
                                      np.asarray(was)[~touched])


def test_a_prefill_row_shorter_than_the_window_is_refused_by_name():
    """A chained row takes its window from the row before alone: a block
    whose rows are shorter than ``d_conv - 1`` is refused when the step is
    traced."""
    m, _, _ = chain_layer(1)
    layer = Mamba2(d_model=32, n_heads=8, d_head=4, d_state=m.ssm_state,
                   d_conv=m.ssm_conv, dtype=jnp.float32)
    blk = nn.TokenBlock(0, 2, jnp.zeros(3, jnp.int32), None, None,
                        jnp.full(3, 2), jnp.arange(3))
    with pytest.raises(ValueError, match="prefill_chunk = 2 is below"):
        layer._block(None, jnp.zeros((6, layer.param_shapes()["w_in"][0][1])),
                     None, blk, 0, True)


@pytest.mark.parametrize("groups,tile", [(1, 2), (2, 4), (1, 8), (2, 8),
                                         (8, 4), (8, 8), (4, None)])
def test_the_state_update_kernel_equals_plain_jnp(groups, tile):
    """``ssm_state_update`` under the interpreter: layer 1 of a 3-layer
    arena advanced, the others untouched; a slot with decay 1 and no input
    keeps its state to the bit. A block is part of one group (1, 2), one
    whole group (2, 4), or SPANS groups: both of two (2, 8), four and all of
    eight (8, 4 and 8, 8: a group of one head), all four under the module's
    own tile (4, None)."""
    rng = np.random.default_rng(tile or 0)
    n_layers, n_slots, H, P, N = 3, 4, 8, 16, 128
    arena = jnp.asarray(rng.standard_normal((n_layers, n_slots, H, P, N)),
                        jnp.float32)
    a = jnp.asarray(rng.uniform(0.5, 1, (n_slots, H)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((n_slots, H, P)), jnp.float32)
    a, u = a.at[2].set(1.0), u.at[2].set(0.0)              # a dead slot
    b = jnp.asarray(rng.standard_normal((n_slots, groups, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((n_slots, groups, N)), jnp.float32)
    got, y = ssm_state_update(arena, jnp.int32(1), a, u, b, c,
                              head_tile=tile, interpret=True)
    want, y_want = ssm_state_update_reference(arena, 1, a, u, b, c)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(y, y_want, atol=2e-5)
    np.testing.assert_array_equal(got[1, 2], arena[1, 2])
    np.testing.assert_array_equal(got[0], arena[0])
    np.testing.assert_array_equal(got[2], arena[2])


def test_off_the_tpu_the_state_update_is_the_plain_form_unless_asked(served):
    """``platform.plain_off_tpu``: AUTO off the TPU takes the plain form.
    Under ``interpret=None`` here (no TPU) the entry returns the reference,
    to the bit, and the tiny model's decode step holds no ``pallas_call`` of
    that name; under ``interpret=True`` it holds the
    kernel (``Engine(..., interpret=True)`` is how a test asks for it inside
    a step). ``interpret=False`` hands Mosaic the kernel from this process:
    ``tests/test_chip_compile.py`` holds that."""
    from triton_distributed_tpu.kernels.ssm_update import NAME
    from triton_distributed_tpu.runtime.platform import plain_off_tpu

    assert plain_off_tpu(None)
    assert not plain_off_tpu(True) and not plain_off_tpu(False)
    rng = np.random.default_rng(5)
    arena = jnp.asarray(rng.standard_normal((2, 3, 4, 8, 128)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.5, 1, (3, 4)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((3, 4, 8)), jnp.float32)
    b, c = (jnp.asarray(rng.standard_normal((3, 2, 128)), jnp.float32)
            for _ in range(2))
    for got, want in zip(
            ssm_state_update(arena, jnp.int32(1), a, u, b, c),
            ssm_state_update_reference(arena, jnp.int32(1), a, u, b, c)):
        np.testing.assert_array_equal(got, want)

    asked = Engine(served.config, mesh=served.mesh, mode="dist",
                   params=served.params, interpret=True)
    n_slots = 2
    held = {}
    for name, engine in (("auto", served), ("asked", asked)):
        pool, _, dec = paged_steps(engine, n_slots)
        tables = jnp.zeros((n_slots, pool.max_blocks_per_seq), jnp.int32)
        held[name] = f"name={NAME}" in str(jax.make_jaxpr(dec)(
            engine.params, jnp.zeros((n_slots, 1), jnp.int32), pool.state,
            jnp.zeros(n_slots, jnp.int32), tables, jnp.ones(n_slots, bool)))
    assert held == {"auto": False, "asked": True}


DEAD = [-1, 0, 0]
DEALS = {
    # the whole chunk a row: a takes three rows of the first step
    8: dict(steps=[([[0, 0, 8], [0, 8, 8], [0, 16, 4], [1, 0, 8]],
                    [20, 8, 0, 0]),
                   ([[1, 8, 4], DEAD, DEAD, DEAD], [1, 4, 0, 0])],
            filled=5, extra=2, prefill_steps=2),
    # a narrowed budget: a row cut short could not be followed, so one row
    # a slot, as before the deal
    4: dict(steps=[([[0, 0, 4], [1, 0, 4], DEAD, DEAD], [4, 4, 0, 0]),
                   ([[0, 4, 4], [1, 4, 4], DEAD, DEAD], [4, 4, 0, 0]),
                   ([[0, 8, 4], [1, 8, 4], DEAD, DEAD], [4, 4, 0, 0]),
                   ([[0, 12, 4], DEAD, DEAD, DEAD], [4, 1, 0, 0]),
                   ([[0, 16, 4], DEAD, DEAD, DEAD], [4, 1, 0, 0])],
            filled=8, extra=0, prefill_steps=5),
}


@pytest.mark.parametrize("budget", sorted(DEALS, reverse=True))
def test_a_model_with_per_slot_state_takes_every_free_row(served, budget):
    """The host deals the prefill block's rows and a prompt takes every
    free one, with per-slot state too: the state layers chain a slot's rows
    (``layers.mamba2``). Two prompts (20 and 12 tokens) into a block of four
    rows of 8: the older takes three rows of the first step, and each gives
    what it gives alone; still two programs. Under a ``prefill_budget``
    below the chunk a slot keeps to one (narrowed) row a step."""
    batch_engine(served)            # the donor of the steps, never wrapped
    be = batch_engine(served)
    assert be.prefill_rows == 4 and be.pool.slot_state
    be.prefill_budget = budget
    calls, step = [], be._mixed_step

    def recording(*args):
        calls.append((np.asarray(args[1][2]).tolist(),
                      np.asarray(args[6]).tolist()))
        return step(*args)

    be._mixed_step = recording
    a, b = prompts(41, 20, 12)
    rids = [be.submit(a, 3), be.submit(b, 3)]
    be.run()
    want = DEALS[budget]
    assert calls[:len(want["steps"])] == want["steps"]
    c = be.metrics.counters
    assert (c["prefill_rows_filled"], c["prefill_rows_extra"]) == \
        (want["filled"], want["extra"])
    assert c["prefill_steps"] == want["prefill_steps"]
    assert c["prefill_tokens"] == 32 and c["ssm_states_reset"] == 2
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    for rid, prompt in zip(rids, (a, b)):
        assert be.finished[rid].output == alone(served, prompt, 3)


def test_a_slot_reused_by_a_second_request_gives_what_that_request_gives_alone(
        served):
    be = batch_engine(served, n_slots=1)
    first, second = prompts(7, 13, 9)
    ra = be.submit(first, 5)
    rb = be.submit(second, 7)
    be.run()
    assert be.metrics.counters["ssm_states_reset"] == 2
    assert be.finished[ra].output == alone(served, first, 5)
    out = be.finished[rb].output
    assert out == alone(served, second, 7)
    assert_served_is_the_references_best(second, out)


def test_a_preempted_and_readmitted_request_gives_what_an_undisturbed_one_gives(
        served):
    be = batch_engine(served, n_slots=2)
    p, q = prompts(9, 12, 7)
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
    assert be.metrics.counters["ssm_states_reset"] == 3     # p twice
    assert be.finished[rp].output == alone(served, p, 9)
    assert be.finished[rq].output == alone(served, q, 9)
    assert_served_is_the_references_best(p, be.finished[rp].output)


def test_a_common_prefix_is_not_matched_and_each_gives_what_it_gives_alone(
        served):
    """The prefix cache asked for (the default) and two requests that share
    three whole blocks with a finished one: no block is adopted (a block
    holds rows, not the state at its boundary), each prefills from 0."""
    be = batch_engine(served, prefix_cache=True)
    donor, = prompts(11, 14)
    be.submit(donor, 3)
    be.run()
    tails = prompts(12, 4, 6)
    reqs = [be.submit(donor[:12] + t, 5) for t in tails]
    be.run()
    c = be.metrics.counters
    assert be.prefix_cache is None and be.pool.n_cached == 0
    assert c.get("prefix_cached_tokens", 0) == 0 == c.get("prefix_hits", 0)
    assert c["ssm_states_reset"] == 3
    for rid, t in zip(reqs, tails):
        out = be.finished[rid].output
        assert out == alone(served, donor[:12] + t, 5)
        assert_served_is_the_references_best(donor[:12] + t, out)


def test_drain_and_quarantine_leave_nothing_the_next_request_reads(served):
    be = batch_engine(served, n_slots=1)
    p, q = prompts(13, 10, 6)
    be.submit(p, 8)
    for _ in range(4):
        be.step()
    drained = be.drain("test")
    assert [r.prompt for r in drained] == [p] and drained[0].output
    rq = be.submit(q, 4)                 # takes the slot p's state is in
    for _ in range(2):
        be.step()
    be._quarantine(0, "test")
    assert be.failed[rq].status == "failed"
    rp = be.adopt(drained[0])            # p again, recomputed from 0
    be.run()
    be.pool.check_invariants()
    assert be.finished[rp].output == alone(served, p, 8)


def test_the_pool_holds_two_kinds_of_state(served):
    cfg = served.config
    with pytest.raises(ValueError, match="needs n_slots"):
        KVPool(cfg, n_blocks=6, block_size=4)
    with pytest.raises(NotImplementedError, match="quantized"):
        KVPool(cfg, n_blocks=6, block_size=4, n_slots=2, kv_dtype="int8")
    pool = KVPool(cfg, n_blocks=6, block_size=4, n_slots=3)
    st = pool.state
    # rows: as deep as the model has attention layers, two heads to a row,
    # a block's K plane and V plane side by side
    assert st.kv.shape == (2, 6, 2, 4, 1, 32)
    assert st.ssm.shape == (4, 3, 4, 8, 16) and st.ssm.dtype == jnp.float32
    assert st.conv.shape == (4, 3, 3 * (32 + 2 * 2 * 16))
    assert pool.slot_state_bytes == st.ssm.nbytes + st.conv.nbytes
    assert pool.kv_fingerprint() == "float32:none:paired:slot[conv+ssm]"
    assert pool.geometry()["slot_state"] == {
        "conv": [4, 3, 288], "ssm": [4, 3, 4, 8, 16]}
    assert jax.tree.structure(pool.specs) == jax.tree.structure(st)
    # a block's copy moves rows and leaves the per-slot arenas alone
    pool.state = dataclasses.replace(
        st, kv=st.kv.at[:, 2].set(7.0), ssm=st.ssm.at[:, 2].set(5.0))
    pool._copy_block_device(2, 5)
    assert np.all(np.asarray(pool.state.kv[:, 5]) == 7.0)
    assert np.all(np.asarray(pool.state.ssm[:, 1]) == 0.0)
    assert np.all(np.asarray(pool.state.ssm[:, 2]) == 5.0)
    pool.check_invariants()
    pool.state = dataclasses.replace(pool.state, conv=None)
    with pytest.raises(AssertionError, match="conv"):
        pool.check_invariants()
    # a pool of rows only has none of it
    rows = KVPool(ModelConfig.from_name("tiny"), n_blocks=6, block_size=4)
    assert rows.state.ssm is None and rows.slot_state_bytes == 0
    assert "slot_state" not in rows.geometry()


def test_steps_are_shared_only_between_pools_of_one_format(served):
    a = batch_engine(served, n_slots=2)
    b = batch_engine(served, n_slots=2)
    b.share_steps_from(a)
    assert b._decode_step is a._decode_step
    c = batch_engine(served, n_slots=2)
    c.pool.state = dataclasses.replace(
        c.pool.state, ssm=c.pool.state.ssm.astype(jnp.bfloat16))
    with pytest.raises(ValueError, match="pool format"):
        c.share_steps_from(a)


def test_what_is_not_built_is_refused_by_name(served):
    pool = KVPool(served.config, n_blocks=8, block_size=4, n_slots=2)
    args = (served.params, jnp.zeros((2, 8), jnp.int32), pool.state,
            jnp.zeros((2,), jnp.int32), jnp.zeros((2, 16), jnp.int32),
            jnp.ones((2,), bool), jnp.ones((2,), jnp.int32))
    step = jax.jit(served._make_sm(
        "dist", paged="prefill", paged_attn="gather", spec_verify=True,
        state_specs=pool.specs))
    with pytest.raises(NotImplementedError, match="roll the state back"):
        step.lower(*args)
    mesh2 = make_mesh({"tp": 2}, devices=jax.devices()[:2], set_default=False)
    # (heads of 64: two to a row, two rows a token, one a device)
    engine = Engine(GraniteHybridConfig.tiny(d_model=256, n_kv_heads=4),
                    mesh=mesh2, mode="dist")
    pool2 = KVPool(engine.config, n_blocks=8, block_size=4, mesh=mesh2,
                   n_slots=2)
    step = jax.jit(engine._make_sm("dist", paged="decode",
                                   paged_attn="gather",
                                   state_specs=pool2.specs))
    with pytest.raises(NotImplementedError,
                       match="per-slot state under tensor parallelism"):
        step.lower(engine.params, jnp.zeros((2, 1), jnp.int32), pool2.state,
                   jnp.zeros((2,), jnp.int32), jnp.zeros((2, 16), jnp.int32),
                   jnp.ones((2,), bool))


def test_counts_of_the_published_configuration():
    """The family's counts at granite-4.0-h-micro's sizes against the
    issue's hand count: 3.19 B parameters (6.38 GB), 8,192 B of rows a
    token, and 76.4 MB of state a slot: the issue's 77.4 MB counted the
    convolution's window in float32; it is held in the served dtype."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "perfbench/configs/granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    m = family.sizes(cfg)
    assert (m.n_layers, family.n_ssm_layers(m), m.d_inner, m.conv_width) == \
        (40, 36, 4096, 4352)
    assert [i for i in range(40) if not family.is_ssm(m, i)] == \
        [5, 15, 25, 35]
    assert family.layer_params(m, True) == pytest.approx(76.2e6, rel=2e-3)
    assert family.layer_params(m, False) == pytest.approx(60.8e6, rel=2e-3)
    assert family.weight_params(m) == pytest.approx(3.19e9, rel=2e-3)
    assert family.kv_bytes_per_token(m) == 8192
    per_slot = family.state_bytes_per_slot(m)
    assert per_slot == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert per_slot == pytest.approx(77.4e6, rel=0.015)
    assert family.ssm_update_min_bytes(m, 32) == 32 * 36 * 2 * 2_097_152
    assert family.ssm_update_flops(m, 1) == 5 * 36 * 64 * 64 * 128
    step = family.decode_step_min_bytes(m, [1800] * 32)
    assert step == (2 * family.weight_params(m) + 32 * 2 * per_slot
                    + 32 * 1800 * 8192)
    assert step == pytest.approx(11.8e9, rel=0.01)
    # the program's own configuration object, and what its pool would hold
    mcfg = family.program_config(cfg, m)
    assert (mcfg.kv_pack, mcfg.kv_row_shapes[0]) == (2, (4, 128))
    assert (mcfg.n_cache_layers, mcfg.n_state_layers) == (4, 36)
    assert mcfg.slot_state_shapes["ssm"][0] == (64, 64, 128)
    assert mcfg.slot_state_shapes["conv"][0] == (3 * 4352,)
    from triton_distributed_tpu.models.granite_hybrid import GraniteHybrid
    model = GraniteHybrid(mcfg)
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(
        model.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)))
    assert n == family.weight_params(m)
