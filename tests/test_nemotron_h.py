"""The Nemotron-H block (``models/nemotron_h.py``: every layer ONE mixer,
Mamba-2 over per-slot state, routed relu² experts or attention over paged
rows, in an order that does not repeat) against the benchmark's plain
reference (``perfbench/families/nemotron_h.py``), at tiny float32 sizes on
the CPU: all three kinds, the pattern ``MEM*EMEME`` (no period divides it),
two groups, 8 experts top-2, an expert width (24) that is stored padded.
``paged_attn="gather"`` wherever the fused kernel is not the thing tested.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference, weights
from perfbench.families import nemotron_h as family
from triton_distributed_tpu.layers.moe_mlp import HeldExpertsMoE
from triton_distributed_tpu.models.config import NemotronHConfig
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.nemotron_h import NemotronH, pattern_segments
from triton_distributed_tpu.obs import trace as _trace
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving.batch_engine import BatchEngine
from triton_distributed_tpu.serving.kv_pool import KVPool

SIZES = family.Sizes(
    vocab_size=256, d_model=64, pattern="MEM*EMEME", heads=4, kv_heads=2,
    head_width=16, ssm_heads=4, ssm_head_width=8, ssm_state=16, ssm_conv=4,
    ssm_groups=2, expert_width=24, shared_width=48, router_width=8, held=8,
    lo=0, topk=2, scaling=2.5, norm_topk=True,
    step_range=(1e-3, 1e-1, 1e-4), eps=1e-5, max_length=64, dtype="float32")
SEED = 43
N_M, N_E, N_A = 4, 4, 1                     # layers of each kind
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


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
    """A ``BatchEngine`` at the tests' geometry; engines of one geometry
    share their compiled steps, so a test that builds several compiles
    once."""
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


def letters(segments):
    short = {"mamba": "M", "moe": "E", "attention": "*"}
    return [("".join(short[k] for k in unit), n) for unit, n in segments]


def test_engine_picks_the_model_and_the_walk_is_read_from_the_pattern(served):
    assert isinstance(served.model, NemotronH)
    assert served.model.layer_counts == {"mamba": N_M, "moe": N_E,
                                         "attention": N_A}
    assert letters(served.model.segments) == [("MEM*E", 1), ("ME", 2)]
    # the published 52 layers: 14 layer bodies, the first run five times
    pub = NemotronH(NemotronHConfig(experts_held=16))
    assert pub.config.pattern == PUBLISHED
    assert letters(pub.segments) == [("MEMEM*E", 5), ("ME", 3), ("M*E", 1),
                                     ("ME", 4)]
    assert pub.layer_counts == {"mamba": 23, "moe": 23, "attention": 6}
    for kinds in (pub.config.layer_kinds, tuple("abcab"), tuple("aaaa"),
                  tuple("abcd")):
        segs = pattern_segments(kinds)
        assert sum((unit * n for unit, n in segs), ()) == tuple(kinds)
    assert pattern_segments(tuple("aaaa")) == ((("a",), 4),)
    assert pattern_segments(tuple("abcd")) == ((tuple("abcd"), 1),)


def paged_steps(served, n_slots, n_blocks=24):
    pool = KVPool(served.config, n_blocks=n_blocks, block_size=4,
                  mesh=served.mesh, n_slots=n_slots)
    kw = dict(paged_attn="gather", state_specs=pool.specs)
    return (pool, jax.jit(served._make_sm("dist", paged="prefill", **kw)),
            jax.jit(served._make_sm("dist", paged="decode", **kw)))


TOKENS_A, TOKENS_B = prompts(3, 21, 8)


def logits_of_a_staggered_batch(engine):
    """Three slots through the step functions ``BatchEngine`` compiles, the
    mixed step in its two-block form: sequence a prefills in chunks of 8
    (8, 8, 3) and then decodes; b is admitted one step later (5 tokens, then
    decodes beside a's prefill); slot 1 stays empty. Returns the logits of
    a at positions 18, 19, 20 and of b at 4, 5, 6, 7."""
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

    def counts(aux, live, reset):
        """The seven ``step_stats``: every live token picks 2 experts in
        each expert layer and all 8 are held, none dropped."""
        st = aux["stats"].tolist()
        assert st[0] == st[1] == live * 2 * N_E and st[3] == 0
        assert 0 < st[2] <= 8 * N_E
        assert st[4:] == [live * N_M, reset, live * N_A]

    logits, aux, state = mixed(state, [0, 0, 0], [8, 0, 0], [0, 0, 0],
                               [a[0:8]])
    counts(aux, 8, 1)
    logits, aux, state = mixed(state, [8, 0, 0], [8, 0, 5], [0, 0, 0],
                               [a[8:16], b[0:5]])
    counts(aux, 13, 1)
    got_b.append(logits[2])                                # b position 4
    logits, aux, state = mixed(state, [16, 0, 5], [3, 0, 1], [0, 0, b[5]],
                               [a[16:19]])
    counts(aux, 4, 0)
    got_a.append(logits[0])                                # a position 18
    got_b.append(logits[2])                                # b position 5
    for k in range(2):
        logits, aux, state = dec(
            engine.params,
            jnp.asarray([[a[19 + k]], [0], [b[6 + k]]], jnp.int32), state,
            jnp.asarray([19 + k, 0, 6 + k], jnp.int32), tables,
            jnp.asarray([True, False, True]))
        counts(aux, 2, 0)
        got_a.append(logits[0])
        got_b.append(logits[2])
    assert jax.tree.structure(state) == jax.tree.structure(pool.state)
    return np.asarray(got_a), np.asarray(got_b)


def assert_logits_agree(got, tokens, first):
    """Float32 on both sides, so what separates them is the order of the
    sums (a chunk scan and a kernel against one sequential scan, sorted
    expert tiles against one expert after another, nine layers deep): 2e-5
    on a logit of spread ~1. Computing any sub-layer in bfloat16 (relative
    4e-3) fails it by two orders; leaving one out fails it by four."""
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


# The prefill block (4 rows of 8) as the host could deal it for slots a
# (0) and b (2): ``(slot, cache length before the row, live tokens)``.
CHAINED_DEALS = {
    "one slot, three rows": [(0, 0, 8), (0, 8, 8), (0, 16, 5)],
    "two slots side by side": [(0, 0, 8), (0, 8, 8), (0, 16, 5), (2, 0, 8)],
    "a dead row between": [(0, 0, 8), (0, 8, 8), None, (2, 0, 8)],
    "a run from the arena": [(2, 0, 8), (0, 8, 8), (0, 16, 5)],
}


@pytest.mark.parametrize("deal", sorted(CHAINED_DEALS))
def test_a_slots_rows_in_one_mixed_step_give_what_a_row_a_step_gives(served,
                                                                     deal):
    """The whole model's mixed step with several rows of the block dealt to
    ONE slot (its four state layers chain them; its attention layer appends
    all and reads each to its own end) against the same rows one a step:
    the logits, every state layer's state and window, and the reference's
    full forward pass. "A run from the arena": a's first row went alone, a
    step before."""
    a, b = TOKENS_A, TOKENS_B
    rows = CHAINED_DEALS[deal]
    pool, pre, _ = paged_steps(served, 3)
    assert pool.ensure("a", 21) and pool.ensure("b", 8)
    tables = jnp.asarray(pool.padded_tables(["a", None, "b"]))
    seqs = {0: a, 2: b}

    def mixed(state, rows):
        chunk = np.zeros((4, 8), np.int32)
        dealt = np.tile(np.int32([-1, 0, 0]), (4, 1))
        lens, off = np.zeros(3, np.int32), np.zeros(3, np.int32)
        for k, row in enumerate(rows):
            if row is not None:
                slot, at, n = dealt[k] = row
                chunk[k, :n] = seqs[slot][at:at + n]
                off[slot] = at if lens[slot] == 0 else off[slot]
                lens[slot] += n
        logits, aux, state = pre(
            served.params, (jnp.zeros((3,), jnp.int32), jnp.asarray(chunk),
                            jnp.asarray(dealt)), state, jnp.asarray(off),
            tables, jnp.asarray(lens > 0), jnp.asarray(lens))
        assert aux["stats"].tolist()[4:] == [
            lens.sum() * N_M, sum(r is not None and r[1] == 0 for r in rows),
            lens.sum() * N_A]
        return np.asarray(logits), state

    start = pool.state
    if rows[0] != (0, 0, 8) and (0, 8, 8) in rows:
        _, start = mixed(start, [(0, 0, 8)])
    got, state = mixed(start, rows)
    state1, apart = start, {}
    for row in filter(None, rows):
        logits, state1 = mixed(state1, [row])
        apart[row[0]] = logits[row[0]]
    for slot, logits in apart.items():
        np.testing.assert_allclose(got[slot], logits, atol=2e-5)
    done = {slot: max(at + n for s, at, n in filter(None, rows) if s == slot)
            for slot in apart}
    for slot, end in done.items():
        assert_logits_agree([got[slot]], seqs[slot][:end], end)
    np.testing.assert_allclose(state.ssm, state1.ssm, atol=1e-5)
    np.testing.assert_allclose(state.conv, state1.conv, atol=1e-6)
    np.testing.assert_allclose(state.kv, state1.kv, atol=1e-5)


FAULTS = {
    # the last expert layer's shared expert gives nothing
    "moe": lambda t: dict(t, moe=dict(t["moe"], shared=dict(
        t["moe"]["shared"],
        w_down=t["moe"]["shared"]["w_down"].at[-1].set(0.0)))),
    # one Mamba-2 layer's skip ``D`` gives nothing
    "mamba": lambda t: dict(t, mixer=dict(
        t["mixer"], d_skip=t["mixer"]["d_skip"].at[2].set(0.0))),
    # the attention layer gives nothing
    "attention": lambda t: dict(t, attn=dict(
        t["attn"], w_o=t["attn"]["w_o"] * 0.0)),
}


@pytest.mark.parametrize("kind", sorted(FAULTS))
def test_a_fault_in_a_layer_of_each_kind_no_longer_agrees(served, kind):
    """The comparison sees each kind of layer: the same batch with a part
    of ONE layer of the kind taken out disagrees with the reference."""
    lay = served.params["layers"]
    params = dict(served.params,
                  layers=dict(lay, **{kind: FAULTS[kind](lay[kind])}))
    wrong = Engine(served.config, mesh=served.mesh, params=params,
                   mode="dist")
    got_a, _ = logits_of_a_staggered_batch(wrong)
    with pytest.raises(AssertionError):
        assert_logits_agree(got_a, TOKENS_A, 19)


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_batch_engine_serves_what_the_reference_puts_first(served,
                                                           paged_attn):
    """Requests of several lengths through ``BatchEngine``, one submitted
    after the others have started: every served token is the reference's
    best at its position; the step's span carries the counts of BOTH
    families of counters, and the snapshot the layers by kind."""
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
    assert c["ssm_rows_advanced"] == tokens * N_M
    assert c["kv_rows_appended"] == tokens * N_A
    assert c["ssm_states_reset"] == 4
    assert c["moe_pairs_routed"] == c["moe_pairs_held"] == tokens * 2 * N_E
    assert c["moe_dropped_pairs"] == 0 < c["moe_experts_touched"]
    for name in served.model.step_stats:
        assert sum(r.attrs[name] for r in spans) == c[name]
    snap = be.stats_snapshot()
    assert snap["layers"] == {"mamba": N_M, "moe": N_E, "attention": N_A}
    assert snap["pool"]["slot_state_bytes"] == be.pool.state.ssm.nbytes \
        + be.pool.state.conv.nbytes > 0
    for rid, prompt in zip(reqs, ps):
        assert_served_is_the_references_best(prompt, be.finished[rid].output)


def test_a_preempted_and_readmitted_request_gives_what_an_undisturbed_one_gives(
        served):
    """Preemption frees the slot; the resumed request recomputes its state
    from position 0 (no snapshot is kept) and goes on as if undisturbed."""
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


def test_a_slot_reused_and_a_common_prefix_give_what_each_gives_alone(served):
    """A second request in a slot reads nothing of the first, and the prefix
    cache asked for (the default) adopts no block: a block holds rows, not
    the recurrent state at its boundary."""
    be = batch_engine(served, n_slots=1, prefix_cache=True)
    donor, = prompts(11, 14)
    tail, = prompts(12, 5)
    ra = be.submit(donor, 3)
    rb = be.submit(donor[:12] + tail, 6)
    be.run()
    c = be.metrics.counters
    assert be.prefix_cache is None and be.pool.n_cached == 0
    assert c.get("prefix_cached_tokens", 0) == 0 == c.get("prefix_hits", 0)
    assert c["ssm_states_reset"] == 2
    assert be.finished[ra].output == alone(served, donor, 3)
    out = be.finished[rb].output
    assert out == alone(served, donor[:12] + tail, 6)
    assert_served_is_the_references_best(donor[:12] + tail, out)


def test_the_pool_holds_rows_six_deep_beside_state_twenty_three_deep(served):
    """The geometry is read from the configuration: rows as deep as the
    attention layers, per-slot arenas as deep as the Mamba-2 layers."""
    pool = KVPool(served.config, n_blocks=6, block_size=4, n_slots=3)
    st = pool.state
    assert st.kv.shape == (N_A, 6, 2, 4, 2, 16)
    assert st.ssm.shape == (N_M, 3, 4, 8, 16) and st.ssm.dtype == jnp.float32
    assert st.conv.shape == (N_M, 3, 3 * (32 + 2 * 2 * 16))
    assert pool.kv_fingerprint() == "float32:none:paired:slot[conv+ssm]"
    pub = NemotronHConfig(experts_held=16)
    assert (pub.n_cache_layers, pub.n_state_layers) == (6, 23)
    assert pub.kv_row_shapes == ((2, 128), (2, 128))
    assert pub.slot_state_shapes["ssm"][0] == (64, 64, 128)
    assert pub.slot_state_shapes["conv"] == ((3 * 6144,), jnp.bfloat16)
    assert (pub.moe_d_ff, pub.moe_d_ff_stored) == (1856, 1920)
    with pytest.raises(ValueError, match="do not lie inside"):
        NemotronHConfig(experts_held=16, experts_lo=120)
    with pytest.raises(ValueError, match="pattern names"):
        NemotronHConfig(pattern="MEX")


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
    with pytest.raises(NotImplementedError, match="quantized"):
        KVPool(served.config, n_blocks=6, block_size=4, n_slots=2,
               kv_dtype="int8")
    mesh2 = make_mesh({"tp": 2}, devices=jax.devices()[:2], set_default=False)
    engine = Engine(NemotronHConfig.tiny(), mesh=mesh2, mode="dist")
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


# -- the expert layer ------------------------------------------------------------

def relu2_layer(m):
    return HeldExpertsMoE(
        d_model=m.d_model, d_ff=m.expert_width, n_experts=m.router_width,
        topk=m.topk, n_held=m.held, lo=m.lo, routed_scaling=m.scaling,
        dtype=jnp.float32, activation="relu2")


def test_the_ungated_relu2_form_equals_plain_jnp():
    """``HeldExpertsMoE(activation="relu2")``: TWO matrices an expert, ``w_down
    relu(w_up x)^2``, the shared expert alike, against ``jax.numpy`` written
    out here; zero-padding the width (24 -> 128) changes nothing to the bit
    of the tolerance; the gated form of the same layer differs."""
    m = SIZES
    lw = family.plain_layer(m, jax.random.PRNGKey(5), "experts")
    x = jax.random.normal(jax.random.PRNGKey(6), (19, m.d_model))
    s = jax.nn.sigmoid(x @ lw["router"])
    _, ids = jax.lax.top_k(s + lw["bias"], m.topk)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = m.scaling * w / w.sum(-1, keepdims=True)
    want = jnp.square(jax.nn.relu(x @ lw["s_up"])) @ lw["s_d"]
    for t in range(x.shape[0]):
        for k in range(m.topk):
            e = int(ids[t, k])
            want = want.at[t].add(w[t, k] * (
                jnp.square(jax.nn.relu(x[t] @ lw["e_up"][e])) @ lw["e_d"][e]))
    params = {"router": lw["router"], "bias": lw["bias"],
              "w_up": lw["e_up"], "w_down": lw["e_d"],
              "shared": {"w_up": lw["s_up"], "w_down": lw["s_d"]}}
    layer = relu2_layer(m)
    assert layer.w_in == "w_up"
    got, stats = layer.fwd(params, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert stats.tolist()[:2] == [19 * 2, 19 * 2] and int(stats[3]) == 0
    padded = dict(params,
                  w_up=jnp.pad(lw["e_up"], ((0, 0), (0, 0), (0, 104))),
                  w_down=jnp.pad(lw["e_d"], ((0, 0), (0, 104), (0, 0))))
    wide, _ = dataclasses.replace(layer, d_ff=128).fwd(padded, x)
    np.testing.assert_allclose(wide, want, atol=2e-5)
    # the gated form reads the same first matrix as two halves: another layer
    gated = dataclasses.replace(layer, activation="swiglu", d_ff=12)
    assert gated.w_in == "w_gate_up"
    other, _ = gated.fwd(
        {**params, "w_gate_up": lw["e_up"], "w_down": lw["e_d"][:, :12],
         "shared": {"w_gate_up": lw["s_up"], "w_down": lw["s_d"][:24]}}, x)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2


def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips' shares of four experts each (held 4 of 8, lo 0 and 4; the
    program's layer, told which experts it holds, over the padded matrices
    the family hands the program), the shared expert counted once, equal the
    reference's uncut expert layer over all 8."""
    uncut = SIZES
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(jax.random.PRNGKey(12), (24, uncut.d_model))
    lw = reference.f32(family.plain_layer(uncut, key, "experts"))
    want = family.experts_mixer(uncut, x, lw, "float32")
    total = family.relu2_mlp(x, lw["s_up"], lw["s_d"], "float32")
    held_pairs, ref_total = 0, total
    for lo in (0, 4):
        m = dataclasses.replace(uncut, held=4, lo=lo)
        slw = family.plain_layer(m, key, "experts")
        np.testing.assert_array_equal(slw["e_up"], lw["e_up"][lo:lo + 4])
        stored = family.program_layer(slw, "experts", 128)["moe"]
        assert stored["w_up"].shape == (4, 64, 128)
        assert stored["w_down"].shape == (4, 128, 64)
        y, stats = dataclasses.replace(relu2_layer(m), d_ff=128).routed(
            stored, x)
        total = total + y
        ref_total = ref_total + family.routed_part(
            m, x, reference.f32(slw), "float32")
        held_pairs += int(stats[1])
        assert int(stats[3]) == 0
    assert held_pairs == 24 * 2                   # every pair has one owner
    np.testing.assert_allclose(total, want, atol=5e-5)
    np.testing.assert_allclose(ref_total, want, atol=5e-5)


def test_counts_of_the_published_configuration():
    """The family's counts at NVIDIA-Nemotron-3-Nano-30B-A3B's sizes, cut to
    one chip of eight, against the issue's hand count: 5.875 B parameters
    held (11.75 GB), 49.08 MB of state a slot, 6,144 B of rows a token, a
    decode step of 32 rows at a mean context of 1,800 reading at least
    12.96 GB, TWO matrices an expert touched."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "perfbench/configs/nemotron-3-nano-30b-a3b-ep8.json")) as f:
        cfg = json.load(f)
    m = family.sizes(cfg)
    assert m.pattern == PUBLISHED and m.n_layers == 52
    assert (m.count("ssm"), m.count("experts"), m.count("attention")) == \
        (23, 23, 6)
    assert [i for i in range(52) if family.kind_of(m, i) == "attention"] == \
        [5, 12, 19, 26, 33, 42]
    assert (m.d_inner, m.conv_width, m.held, m.router_width, m.topk) == \
        (4096, 6144, 16, 128, 6)
    assert family.layer_params(m, "ssm") == pytest.approx(38.74e6, rel=1e-3)
    assert family.layer_params(m, "attention") == pytest.approx(23.40e6,
                                                                rel=1e-3)
    assert family.layer_params(m, "experts") == pytest.approx(179.95e6,
                                                              rel=1e-3)
    assert family.weight_params(m) == pytest.approx(5.875e9, rel=1e-3)
    assert family.expert_params(m) == 2 * 2688 * 1856
    per_slot = family.state_bytes_per_slot(m)
    assert per_slot == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert per_slot == pytest.approx(49.08e6, rel=1e-3)
    assert family.kv_bytes_per_token(m) == 6144
    assert family.ssm_update_min_bytes(m, 32) == 32 * 23 * 2 * 2_097_152
    assert family.ssm_update_flops(m, 1) == 5 * 23 * 64 * 64 * 128
    # TWO matrices an expert touched, at the published width
    assert family.moe_ffn_min_bytes(m, 1) == 2 * 2 * 2688 * 1856
    assert family.moe_ffn_flops(m, 1) == 2 * 2 * 2688 * 1856
    pairs, touched = family.moe_expected(m, 32)
    assert pairs == pytest.approx(23 * 32 * 6 / 8)
    assert touched / 23 == pytest.approx(12.56, abs=0.01)    # of 16 held
    step = family.decode_step_min_bytes(m, [1800] * 32)
    assert step == pytest.approx(
        family.fixed_weight_bytes(m) + family.moe_ffn_min_bytes(m, touched)
        + 32 * 2 * per_slot + 32 * 1800 * 6144)
    assert step == pytest.approx(12.96e9, rel=0.02)
    assert family.fixed_weight_bytes(m) == pytest.approx(3.70e9, rel=0.01)
    # one summed context, as the roofline's reader hands it: the fewest rows
    # that could hold it (15 of 32), so fewer states and experts: it reads low
    summed = family.decode_step_min_bytes(m, [32 * 1800])
    assert 0.6 * step < summed < 0.8 * step
    # the program's own configuration object, and what its pool would hold
    mcfg = family.program_config(cfg, m)
    assert (mcfg.n_cache_layers, mcfg.n_state_layers) == (6, 23)
    assert (mcfg.n_held, mcfg.experts_lo, mcfg.n_experts) == (16, 0, 128)
    model = NemotronH(mcfg)
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(
        model.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)))
    padding = 23 * 16 * 2 * 2688 * (1920 - 1856)
    assert n - padding == family.weight_params(m)
    # every published key of the catalog's row stands at its published value
    assert cfg["reduced"] == ["n_routed_experts", "max_position_embeddings"]
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"], cfg["num_hidden_layers"]) == \
        (2688, 1856, 3712, 6, 2.5, 8, 128, 4, 32, 2, 128, 131072, 52)


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
