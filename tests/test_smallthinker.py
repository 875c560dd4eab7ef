"""The SmallThinker block, served from the EXAONE walk made to read four
things from its configuration (``models/exaone_moe.py``: no QK norm, no
shared expert and no dense layer, ReGLU experts chosen by the largest raw
logits with a softmax over the chosen, THE ROUTER READING THE LAYER'S INPUT,
before attention), against the benchmark's plain reference
(``perfbench/families/smallthinker.py``) at tiny float32 sizes on the CPU:
five layers ``full, window, window, window, full``, a window of 6, three
query heads to a key head, 8 experts top-3. ``paged_attn="gather"`` wherever
the fused kernel is not the thing tested.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference, weights
from perfbench.families import smallthinker as family
from triton_distributed_tpu.layers.moe_mlp import MOE_STATS, HeldExpertsMoE
from triton_distributed_tpu.models.config import ExaoneMoeConfig
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.exaone_moe import ExaoneMoe
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving.batch_engine import BatchEngine
from triton_distributed_tpu.serving.kv_pool import KVPool, window_ring_blocks

WINDOW, TOPK, N_LAYERS, N_WINDOW, N_FULL = 6, 3, 5, 3, 2
SIZES = family.Sizes(
    vocab_size=256, d_model=64, n_layers=N_LAYERS, windows=(0, 6, 6, 6, 0),
    ropes=(False, True, True, True, False), heads=6, kv_heads=2, head_dim=16,
    expert_width=32, router_width=8, held=8, lo=0, topk=TOPK, theta=1e4,
    eps=1e-6, max_length=128, dtype="float32")
SEED = 53


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
    donor = _DONORS.setdefault((id(served), kw["paged_attn"]), be)
    if donor is not be:
        be.share_steps_from(donor)
    return be


def prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SIZES.vocab_size, n).tolist() for n in lengths]


def test_the_walk_and_the_four_things_are_read_from_the_configuration(served):
    model = served.model
    assert isinstance(model, ExaoneMoe)
    assert model.layer_counts == {"moe": N_LAYERS, "window": N_WINDOW,
                                  "full": N_FULL}
    assert model.moe_forms == {
        "scoring": "softmax_topk", "activation": "reglu", "shared": False,
        "router_input": "layer_input"}
    # no dense stack, no shared expert, no QK norm: not parameters at all
    assert sorted(served.params) == ["attn", "embed", "final_norm",
                                     "lm_head", "moe"]
    assert sorted(served.params["moe"]) == ["router", "w_down", "w_gate_up"]
    assert sorted(served.params["attn"]["attn"]) == ["w_o", "w_qkv"]
    assert not model.attn["window"].qk_norm and model.attn["window"].rope
    assert not model.attn["full"].rope and model.attn["full"].window is None
    # the published 52 layers: full, window x 3, thirteen times; one body of
    # four layers is traced
    pub = ExaoneMoe(ExaoneMoeConfig.smallthinker())
    assert pub.layer_counts == {"moe": 52, "window": 39, "full": 13}
    assert pub.segments == (((("full", "moe"),) + (("window", "moe"),) * 3,
                             13),)
    c = pub.config
    assert (c.n_heads // c.n_kv_heads, c.window, c.n_held, c.n_experts) == \
        (7, 4096, 64, 64)
    with pytest.raises(ValueError, match="unknown router_input"):
        ExaoneMoeConfig.tiny(router_input="after")
    # the block the class was written for keeps its forms
    assert ExaoneMoe(ExaoneMoeConfig.tiny()).moe_forms == {
        "scoring": "sigmoid", "activation": "swiglu", "shared": True,
        "router_input": "post_attn_norm"}


# -- prefill then decode through the cache, against the reference ----------------

P_ROWS = 3                   # the hand-driven steps' prefill block: 3 rows of 8
TOKENS, = prompts(3, 50)


def logits_through_the_pool(engine):
    """One sequence through the step functions ``BatchEngine`` compiles, in
    slot 1 of two: the mixed step in its two-block form gives it ALL THREE
    rows of the prefill block (24 tokens: several rows of one slot, crossing
    the window of 6 inside ONE step), then two rows (16), then 4 tokens of
    one row, then it decodes: 49 positions through a ring of 6 - 1 + 24
    positions (8 blocks of 4: 32 lines), so the ring wraps. Returns the
    logits at positions 43 (the last mixed step's) and 44..48."""
    pool = KVPool(engine.config, n_blocks=32, block_size=4, max_seq_len=128,
                  mesh=engine.mesh, n_slots=2, max_take=P_ROWS * 8)
    kw = dict(paged_attn="gather", state_specs=pool.specs)
    pre = jax.jit(engine._make_sm("dist", paged="prefill", **kw))
    dec = jax.jit(engine._make_sm("dist", paged="decode", **kw))
    assert pool.state.wkv.shape == (N_WINDOW, 2, 2, 8, 4, 2, 16)
    assert pool.state.kv.shape[0] == N_FULL
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
        assert st[0] == st[1] == take * TOPK * N_LAYERS and st[3] == 0
        assert st[4] == take * N_LAYERS
        off += take
    got.append(logits[1])                                  # position 43
    for k in range(5):
        logits, aux, state = dec(
            engine.params, jnp.asarray([[0], [TOKENS[44 + k]]], jnp.int32),
            state, jnp.asarray([0, 44 + k], jnp.int32), tables,
            jnp.asarray([False, True]))
        assert aux["stats"].tolist()[:2] == [TOPK * N_LAYERS] * 2
        got.append(logits[1])
    return np.asarray(got)


def assert_logits_agree(got, tokens, first):
    """Float32 on both sides, so what separates them is the order of the
    sums (sorted expert tiles against one expert after another, a gathered
    ring against a blocked mask, five layers deep): 2e-5 on a logit of
    spread ~1. Computing any sub-layer in bfloat16 (relative 4e-3) fails it
    by two orders; one key more or fewer in a window, or a router that reads
    the wrong stream, fails it by three."""
    ref = ref_read(tokens + [0], first)
    for i, logits in enumerate(got):
        assert ref["best_token"][i] == int(logits.argmax())
        assert ref["best"][i] == pytest.approx(float(logits.max()), abs=2e-5)
        assert ref["std"][i] == pytest.approx(float(logits.std()), rel=1e-3)
        nxt = (tokens + [0])[first + i]
        assert ref["picked"][i] == pytest.approx(float(logits[nxt]),
                                                 abs=2e-5)


def test_prefill_then_decode_through_a_ring_that_wraps_agrees_on_logits(served):
    assert_logits_agree(logits_through_the_pool(served), TOKENS[:49], 44)


def _with(engine, **changes):
    return Engine(dataclasses.replace(engine.config, **changes),
                  mesh=engine.mesh, params=engine.params, mode="dist")


def _with_windows(engine, w):
    return _with(engine, sliding_windows=tuple(
        w if x else 0 for x in engine.config.sliding_windows))


def _with_weights_scaled(engine):
    wrong = Engine(engine.config, mesh=engine.mesh, params=engine.params,
                   mode="dist")
    wrong.model.__dict__["moe"] = dataclasses.replace(
        wrong.model.moe, routed_scaling=1.1)
    return wrong


FAULTS = {
    "window+1": lambda e: _with_windows(e, WINDOW + 1),
    # the router reading the experts' input, as every older model's does
    "late-router": lambda e: _with(e, router_input="post_attn_norm"),
    "swiglu": lambda e: _with(e, expert_activation="swiglu"),
    "weights-not-summing-to-1": _with_weights_scaled,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_no_longer_agrees(served, fault):
    """A window one key long, the router moved behind attention, SwiGLU for
    ReGLU, routing weights that sum to 1.1: the same steps disagree with the
    reference."""
    got = logits_through_the_pool(FAULTS[fault](served))
    with pytest.raises(AssertionError):
        assert_logits_agree(got, TOKENS[:49], 44)


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_batch_engine_serves_what_the_reference_puts_first(served,
                                                           paged_attn):
    """Requests of several lengths through ``BatchEngine`` (the longest
    wraps its ring of 40 lines; the deal gives a prompt several rows of a
    step; decoding goes on past the window), one submitted after the others
    have started: every served token is the reference's best. Every pair is
    held here: ``moe_pairs_held == moe_pairs_routed`` and none dropped; the
    snapshot names the layers by kind, the forms, the rings and the window
    build at the group of 3."""
    be = batch_engine(served, paged_attn=paged_attn)
    ps = prompts(5, 5, 43, 17, 9)
    reqs = [be.submit(p, 8) for p in ps[:3]]
    for _ in range(3):
        be.step()
    reqs.append(be.submit(ps[3], 8))
    be.run()
    be.pool.check_invariants()
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    assert be.prefix_cache is None and be.prefill_rows == 4
    c = be.metrics.counters
    tokens = sum(len(p) for p in ps) + 4 * 7
    assert c["kv_rows_appended"] == tokens * N_LAYERS
    assert c["moe_pairs_routed"] == c["moe_pairs_held"] == \
        tokens * TOPK * N_LAYERS
    assert c["moe_dropped_pairs"] == 0 < c["moe_experts_touched"]
    assert c["prefill_rows_extra"] > 0          # the deal engages
    snap = be.stats_snapshot()
    assert snap["layers"] == {"moe": N_LAYERS, "window": N_WINDOW,
                              "full": N_FULL}
    assert snap["moe"] == served.model.moe_forms
    assert snap["moe"]["router_input"] == "layer_input"
    assert snap["pool"]["window_bytes"] == be.pool.state.wkv.nbytes > 0
    if paged_attn == "fused":
        named = {k: v for k, v in snap["paged_arithmetic"].items()
                 if k.endswith(f":window{WINDOW}") and "x6x16:" in k}
        assert sorted(named.values()) == ["folded", "per_head"]
    for rid, prompt in zip(reqs, ps):
        out = be.finished[rid].output
        ref = ref_read(prompt + out, len(prompt))
        assert ref["best_token"].tolist() == out
        assert np.all(ref["best"] - ref["picked"] <= 1e-5)


# -- the expert layer's forms -----------------------------------------------------

D, E, FF = 32, 64, 16


def expert_layer(held=E, lo=0, **kw):
    return HeldExpertsMoE(**{**dict(
        d_model=D, d_ff=FF, n_experts=E, topk=6, n_held=held, lo=lo,
        dtype=jnp.float32, activation="reglu", scoring="softmax_topk"),
        **kw})


@pytest.fixture(scope="module")
def expert_weights():
    k = jax.random.split(jax.random.PRNGKey(7), 5)
    return {"router": jax.random.normal(k[0], (D, E)) * D ** -0.5,
            "w_gate_up": jax.random.normal(k[1], (E, D, 2 * FF)) * D ** -0.5,
            "w_down": jax.random.normal(k[2], (E, FF, D)) * FF ** -0.5,
            "x": jax.random.normal(k[3], (19, D)),
            "r": 3.0 * jax.random.normal(k[4], (19, D))}


def written_out(p, x, r, ids=None):
    """The layer as the issue writes it, one token and one expert at a
    time: the six largest of ``r W_r``, softmax over those six, ReGLU."""
    logits = np.asarray(r @ p["router"], np.float64)
    out = np.zeros((x.shape[0], D))
    for t in range(x.shape[0]):
        chosen = np.argsort(-logits[t])[:6]
        w = np.exp(logits[t, chosen] - logits[t, chosen].max())
        w /= w.sum()
        for e, w_e in zip(chosen, w):
            if ids is not None and e not in ids:
                continue
            h = np.asarray(x[t] @ p["w_gate_up"][e], np.float64)
            out[t] += w_e * (np.maximum(h[:FF], 0) * h[FF:]) @ np.asarray(
                p["w_down"][e], np.float64)
    return out


def test_softmax_over_the_chosen_reglu_and_no_shared_expert(expert_weights):
    p = expert_weights
    layer = expert_layer()
    assert (layer.w_in, "shared" in p) == ("w_gate_up", False)
    w, ids = layer.route(p["router"], None, p["r"])
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    logits = np.asarray(p["r"] @ p["router"])
    assert [sorted(row) for row in ids.tolist()] == \
        [sorted(np.argsort(-l)[:6].tolist()) for l in logits]
    # ``fwd`` adds no shared expert: it IS the routed part, and asks for no
    # ``shared`` and no ``bias`` among the parameters
    y, stats = layer.fwd(p, p["x"], None, p["r"])
    np.testing.assert_allclose(y, written_out(p, p["x"], p["r"]), atol=2e-5)
    counts = dict(zip(MOE_STATS, stats.tolist()))
    assert counts["moe_pairs_routed"] == counts["moe_pairs_held"] == 19 * 6
    assert counts["moe_dropped_pairs"] == 0
    assert 0 < counts["moe_experts_touched"] <= E
    # another activation, another score form: other layers
    for other in (dict(activation="swiglu"),
                  dict(scoring="sigmoid", routed_scaling=1.0)):
        z, _ = expert_layer(**other).fwd(
            dict(p, bias=jnp.zeros((E,))), p["x"], None, p["r"])
        assert float(jnp.abs(z - y).max()) > 1e-2
    with pytest.raises(ValueError, match="unknown expert form"):
        expert_layer(activation="gelu")


def test_route_from_alone_decides_the_choice(expert_weights):
    """Perturb the experts' input and the chosen experts stay; perturb the
    router's and they move. Without ``route_from`` the router reads ``x``,
    as every older model's does."""
    p, layer = expert_weights, expert_layer()
    chosen = {}

    def choice(x, r):
        inner = HeldExpertsMoE.route

        def spy(self, router, bias, src):
            chosen["src"] = src
            w, ids = inner(self, router, bias, src)
            chosen["ids"] = ids
            return w, ids

        HeldExpertsMoE.route = spy
        try:
            y, _ = layer.routed(p, x, None, r)
        finally:
            HeldExpertsMoE.route = inner
        return np.sort(np.asarray(chosen["ids"]), axis=-1), y

    ids, y = choice(p["x"], p["r"])
    assert chosen["src"] is p["r"]
    ids_x, y_x = choice(p["x"] + 1.0, p["r"])
    np.testing.assert_array_equal(ids_x, ids)
    assert float(jnp.abs(y_x - y).max()) > 1e-2
    ids_r, _ = choice(p["x"], p["r"][::-1])
    assert (ids_r != ids).any()
    ids_none, _ = choice(p["x"], None)
    assert chosen["src"] is p["x"]
    assert (ids_none != ids).any()


def test_four_shares_of_sixteen_add_up_and_the_share_of_64_is_the_layer(
        expert_weights):
    """Four chips' shares of 16 experts each (the program's layer, told
    which experts it holds; softmax weights over ALL six chosen, whoever
    holds them) add up to the uncut layer, every pair with one owner; the
    share of all 64, which is what the configuration holds, IS the layer."""
    p = expert_weights
    want = written_out(p, p["x"], p["r"])
    whole, stats = expert_layer().routed(p, p["x"], None, p["r"])
    np.testing.assert_allclose(whole, want, atol=2e-5)
    assert stats.tolist()[0] == stats.tolist()[1] == 19 * 6
    total, held_pairs = 0.0, 0
    for lo in range(0, E, 16):
        share = dict(p, w_gate_up=p["w_gate_up"][lo:lo + 16],
                     w_down=p["w_down"][lo:lo + 16])
        y, st = expert_layer(held=16, lo=lo).routed(share, p["x"], None,
                                                    p["r"])
        np.testing.assert_allclose(
            y, written_out(p, p["x"], p["r"], set(range(lo, lo + 16))),
            atol=2e-5)
        total, held_pairs = total + y, held_pairs + int(st[1])
        assert int(st[0]) == 19 * 6 and int(st[3]) == 0
    assert held_pairs == 19 * 6
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_the_reference_family_routes_from_the_layers_input():
    """The plain reference on its own: the routed part weights by the
    stream it is told to route from, its shares of 16 add up, and the layer
    as a whole moves when only the routers' stream is swapped."""
    m = dataclasses.replace(SIZES, router_width=64, held=64, topk=6)
    key = jax.random.PRNGKey(11)
    lw = reference.f32(family.plain_layer(m, key))
    x = jax.random.normal(jax.random.PRNGKey(12), (24, m.d_model))
    u = jax.random.normal(jax.random.PRNGKey(13), (24, m.d_model))
    want = family.routed_part(m, x, u, lw, "float32")
    total = 0.0
    for lo in range(0, 64, 16):
        sm = dataclasses.replace(m, held=16, lo=lo)
        slw = reference.f32(family.plain_layer(sm, key))
        np.testing.assert_array_equal(slw["e_gu"], lw["e_gu"][lo:lo + 16])
        total = total + family.routed_part(sm, x, u, slw, "float32")
    np.testing.assert_allclose(total, want, atol=5e-5)
    w, ids = family.routing(m, x, lw["router"])
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    assert float(jnp.abs(family.routed_part(m, u, u, lw, "float32")
                         - want).max()) > 1e-2


# -- the published configuration --------------------------------------------------

def test_counts_of_the_published_configuration():
    """The family's counts at SmallThinker-21BA3B-Instruct's sizes, cut to
    layers 0-7 with every expert, against the issue's hand count: 3.967 B
    parameters held (7.93 GB), 4,096... 2,048 B of rows a token a layer, a
    ring of 284 blocks a slot a layer (1.79 GB for six layers of 32 slots),
    1.34 GB of arenas for the two full layers, a decode step of 32 rows
    reading at least 9.4 GB."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "perfbench/configs/smallthinker-21ba3b-l8.json")) as f:
        cfg = json.load(f)
    m = family.sizes(cfg)
    assert m.windows == (0, 4096, 4096, 4096) * 2
    assert m.ropes == (False, True, True, True) * 2
    assert (m.n_window, m.n_full, m.window, m.n_layers) == (6, 2, 4096, 8)
    assert (m.held, m.router_width, m.topk, m.vocab_size, m.max_length) == \
        (64, 64, 6, 151_936, 16_384)
    assert m.row_bytes == 2048
    assert family.attn_params(m) == 2560 * 4608 + 3584 * 2560
    assert family.expert_params(m) == 3 * 2560 * 768
    assert family.params_held(m) == pytest.approx(3.967e9, rel=1e-3)
    assert 2 * family.params_held(m) == pytest.approx(7.93e9, rel=1e-3)
    pairs, touched = family.moe_expected(m, 32)
    assert pairs == 8 * 32 * 6
    assert touched / 8 == pytest.approx(61.25, abs=0.01)        # of 64
    contexts = [7400] * 32
    step = family.decode_step_min_bytes(m, contexts)
    assert step == pytest.approx(
        family.weight_bytes_read(m, touched) + 2 * 32 * 7400 * 2048
        + family.window_attn_min_bytes(m, 32))
    assert family.window_attn_min_bytes(m, 32) == 6 * 32 * 4096 * 2048
    assert family.window_attn_min_bytes(m, 32) == pytest.approx(1.61e9,
                                                                rel=2e-3)
    assert family.window_attn_flops(m, 1) == 4 * 6 * 28 * 128 * 4096
    assert step == pytest.approx(9.48e9, rel=0.01)
    # ONE summed context, as the step roofline's reader hands it: the fewest
    # rows it can be of (15 of 16,384): fewer bytes than the step's, never
    # more, and most of them
    assert family.rows_at_least(m, sum(contexts)) == 15
    summed = family.decode_step_min_bytes(m, [sum(contexts)])
    assert 0.75 * step < summed < step
    assert family.decode_step_min_bytes(m, [1000]) > \
        family.decode_step_min_bytes(m, [0]) > 0
    # the program's own configuration object, and what its pool holds
    mcfg = family.program_config(cfg, m)
    assert (mcfg.n_cache_layers, mcfg.n_window_layers, mcfg.window) == \
        (2, 6, 4096)
    assert (mcfg.n_held, mcfg.n_experts, mcfg.n_shared_experts) == (64, 64, 0)
    assert mcfg.layer_kinds == ((("full", "moe"),)
                                + (("window", "moe"),) * 3) * 2
    n = sum(int(np.prod(leaf[0])) for leaf in jax.tree.leaves(
        ExaoneMoe(mcfg).param_shapes(),
        is_leaf=lambda x: isinstance(x, tuple)))
    assert n - 2560 * (1 + 2 * 8) == family.params_held(m)
    from triton_distributed_tpu.serving.kv_pool import paged_state_shapes

    fleet = cfg["serve"]["fleet"]
    assert window_ring_blocks(4096, 16, 7 * 64) == 284
    state = paged_state_shapes(
        mcfg, n_blocks=fleet["n_blocks"], block_size=fleet["block_size"],
        n_slots=fleet["n_slots"], max_take=7 * fleet["prefill_chunk"])
    nbytes = {f: int(np.prod(a.shape)) * a.dtype.itemsize
              for f in ("kv", "wkv") if (a := getattr(state, f))}
    assert state.wkv.shape == (6, 32, 2, 284, 16, 4, 128)
    assert (fleet["n_slots"], fleet["n_blocks"]) == (32, 20_480)
    assert nbytes["wkv"] == 6 * 32 * 284 * 16 * 2048
    assert nbytes["wkv"] == pytest.approx(1.79e9, rel=2e-3)
    assert nbytes["kv"] == pytest.approx(1.34e9, rel=2e-3)
    # eight layers of full rows at this context would be 5.4 GB
    assert 2 * family.params_held(m) + sum(nbytes.values()) < 11.1e9
