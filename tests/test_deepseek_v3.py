"""The DeepSeek-V3 block (``models/deepseek_v3.py``: latent attention over a
latent paged pool, sigmoid-routed held experts with a shared expert, a
leading dense layer) against the benchmark's plain reference
(``perfbench/families/deepseek_v3.py``), at tiny float32 sizes on the CPU.
``paged_attn="gather"`` wherever the fused kernel is not the thing tested.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference, weights
from perfbench.families import deepseek_v3 as family
from triton_distributed_tpu.kernels import moe_utils
from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.layers.moe_mlp import MOE_STATS, HeldExpertsMoE
from triton_distributed_tpu.models.config import DeepseekV3Config
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving.batch_engine import BatchEngine
from triton_distributed_tpu.serving.kv_pool import KVPool, PagedKVState

SIZES = family.Sizes(
    vocab_size=256, d_model=64, n_layers=3, dense_layers=1, heads=4,
    q_rank=48, kv_rank=32, nope=16, rope=8, v_width=16, dense_width=96,
    expert_width=32, router_width=16, held=8, lo=4, topk=4, shared=1,
    scaling=2.5, norm_topk=True, theta=1e4, eps=1e-6, max_length=64,
    dtype="float32")
SEED = 77


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)


@pytest.fixture(scope="module")
def served(mesh):
    mcfg, params = family.program({"source": "t"}, SIZES, SEED, mesh, {})
    return Engine(mcfg, mesh=mesh, params=params, mode="dist")


def ref_read(tokens, first, m=SIZES):
    w = weights.Weights(family, m, SEED)
    return reference.forward_positions(w, [(tokens, first)])[0]


def test_engine_picks_the_model_from_the_configuration_object(served):
    from triton_distributed_tpu.models.deepseek_v3 import DeepseekV3
    from triton_distributed_tpu.models.qwen import Qwen3

    assert isinstance(served.model, DeepseekV3)
    from triton_distributed_tpu.models.config import ModelConfig

    assert isinstance(Engine(ModelConfig.from_name("tiny"), mesh=served.mesh,
                             mode="xla").model, Qwen3)


def test_prefill_then_decode_through_the_latent_pool_agrees_on_logits(served):
    """Chunked prefill (two steps) then one decode step through the paged
    step functions ``BatchEngine`` compiles, against the reference's one
    full forward pass: the best logit, its token, the next token's logit
    and the row's spread at each position read."""
    m = SIZES
    tokens = np.random.default_rng(3).integers(0, m.vocab_size, 22).tolist()
    pool = KVPool(served.config, n_blocks=16, block_size=4, mesh=served.mesh)
    assert pool.state.latent and pool.state.kv.shape == (3, 16, 4, 128)
    assert pool.ensure("a", 22)
    tables = jnp.asarray(pool.padded_tables(["a", None]))
    mask = jnp.asarray([True, False])
    kw = dict(paged_attn="gather", state_specs=pool.specs)
    pre = jax.jit(served._make_sm("dist", paged="prefill", **kw))
    dec = jax.jit(served._make_sm("dist", paged="decode", **kw))
    state, got = pool.state, []
    for lo, hi in ((0, 12), (12, 20)):            # chunks of 12 and 8
        ids = np.zeros((2, 12), np.int32)
        ids[0, :hi - lo] = tokens[lo:hi]
        logits, aux, state = pre(
            served.params, jnp.asarray(ids), state,
            jnp.asarray([lo, 0], jnp.int32), tables, mask,
            jnp.asarray([hi - lo, 0], jnp.int32))
        assert set(aux) == {"stats"}
        assert jax.tree.structure(state) == jax.tree.structure(pool.state)
    got.append(np.asarray(logits[0]))                       # position 19
    assert int(aux["stats"][-1]) == 8 * m.n_layers          # rows appended
    for pos in (20, 21):
        logits, aux, state = dec(
            served.params, jnp.asarray([[tokens[pos]], [0]], jnp.int32),
            state, jnp.asarray([pos, 0], jnp.int32), tables, mask)
        got.append(np.asarray(logits[0]))                   # positions 20, 21
    stats = aux["stats"]
    assert int(stats[0]) == (m.n_layers - 1) * m.topk       # one live row
    ref = ref_read(tokens + [0], 20)          # reads positions 19, 20, 21
    for i, logits in enumerate(got):
        assert ref["best_token"][i] == int(logits.argmax())
        assert ref["best"][i] == pytest.approx(float(logits.max()), abs=2e-4)
        assert ref["std"][i] == pytest.approx(float(logits.std()), rel=1e-3)
        nxt = (tokens + [0])[20 + i]
        assert ref["picked"][i] == pytest.approx(float(logits[nxt]),
                                                 abs=2e-4)


@pytest.mark.parametrize("paged_attn", ["gather", "fused"])
def test_batch_engine_serves_what_the_reference_puts_first(served,
                                                           paged_attn):
    """Requests of several lengths through ``BatchEngine`` (admission,
    chunked prefill beside decode rows, the latent pool's block tables):
    every served token is the reference's best at its position."""
    be = BatchEngine(served, n_slots=4, n_blocks=48, block_size=4,
                     prefill_chunk=8, paged_attn=paged_attn)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, SIZES.vocab_size, n).tolist()
               for n in (5, 11, 17)]
    reqs = [be.submit(p, 6) for p in prompts]
    be.run()
    be.pool.check_invariants()
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    c = be.metrics.counters
    tokens = sum(len(p) for p in prompts) + 3 * 5
    assert c["latent_rows_appended"] == tokens * SIZES.n_layers
    assert c["moe_pairs_routed"] == tokens * 2 * SIZES.topk
    assert 0 < c["moe_pairs_held"] < c["moe_pairs_routed"]
    assert 0 < c["moe_experts_touched"] <= 2 * SIZES.held * (
        c["decode_steps"] + c["prefill_steps"])
    assert c["moe_dropped_pairs"] == 0
    for rid, prompt in zip(reqs, prompts):
        out = be.finished[rid].output
        ref = ref_read(prompt + out, len(prompt))
        assert ref["best_token"].tolist() == out
        assert np.all(ref["best"] - ref["picked"] <= 1e-5)


def test_absorbed_attention_equals_the_expanded_form(served):
    """The served (absorbed) latent attention against the block as it is
    written: keys and values expanded per head from the latent."""
    m, attn = SIZES, served.model.attn
    p = jax.tree.map(lambda a: a[1], served.params["layers"]["attn"])
    B, L = 2, 7
    x = jax.random.normal(jax.random.PRNGKey(1), (B, L, m.d_model))
    state = PagedKVState(kv=jnp.zeros((8, 4, attn.cache_row)))
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    block = nn.TokenBlock(0, L, jnp.zeros((B,), jnp.int32), tables, None,
                          None)
    out, state = attn.fwd(p, x.reshape(B * L, -1), state, blocks=(block,),
                          paged_attn="gather")
    out = out.reshape(B, L, -1)
    assert state.kv.ndim == 3      # one arena of rows, no planes
    # the cache row: the normalised latent, then the rotated key, then zeros
    rows = np.asarray(state.kv).reshape(2, 16, -1)[:, :L]
    assert np.all(rows[..., m.cache_width:] == 0) and np.any(rows != 0)

    cq = nn.rms_norm(x @ p["w_qa"], p["q_a_norm"], m.eps)
    q = (cq @ p["w_qb"]).reshape(B, L, m.heads, m.nope + m.rope)
    ckv = x @ p["w_kva"]
    c = nn.rms_norm(ckv[..., :m.kv_rank], p["kv_a_norm"], m.eps)
    np.testing.assert_allclose(rows[..., :m.kv_rank], c, atol=1e-6)
    pos = jnp.arange(L)
    q_r = jax.vmap(lambda t: family.rope_interleaved(t, pos, m.theta))(
        q[..., m.nope:])
    k_r = jax.vmap(lambda t: family.rope_interleaved(t, pos, m.theta))(
        ckv[:, :, None, m.kv_rank:])
    k_nope = jnp.einsum("blc,hnc->blhn", c, p["w_kvb_k"])
    v = jnp.einsum("blc,hcv->blhv", c, p["w_kvb_v"])
    s = (jnp.einsum("blhn,bshn->bhls", q[..., :m.nope], k_nope)
         + jnp.einsum("blhr,bsr->bhls", q_r, k_r[:, :, 0]))
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                  s * (m.nope + m.rope) ** -0.5, -jnp.inf)
    o = jnp.einsum("bhls,bshv->blhv", jax.nn.softmax(s, axis=-1), v)
    want = o.reshape(B, L, -1) @ p["w_o"]
    np.testing.assert_allclose(out, want, atol=2e-5)


@pytest.mark.parametrize("L", [1, 6], ids=["decode", "chunk"])
def test_the_latent_kernel_equals_the_gather_path(L):
    """The fused block walk (interpreter) over a stacked latent arena: each
    block read once, used as keys and as values; ragged rows, a dead slot,
    tables that share nothing."""
    rng = np.random.default_rng(L)
    B, H, W, V, bs, nb, layers = 3, 4, 128, 96, 4, 24, 2
    pool = jnp.asarray(rng.standard_normal((layers, nb, bs, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, L, H, W)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb).reshape(B, 8), jnp.int32)
    offset = jnp.asarray([9, 0, 17], jnp.int32)
    seq_lens = None if L == 1 else jnp.asarray([L, 3, 2], jnp.int32)
    mask = jnp.asarray([True, True, False])
    kw = dict(v_dim=V, scale=0.1, slot_mask=mask, seq_lens=seq_lens, layer=1)
    want = nn.latent_attn_with_cache(q, pool, tables, offset,
                                     paged_attn="gather", **kw)
    got = nn.latent_attn_with_cache(q, pool, tables, offset,
                                    paged_attn="fused", interpret=True, **kw)
    assert got.shape == (B, L, H, V)
    live = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)
    if seq_lens is not None:                 # padding query rows give zeros
        assert np.all(np.asarray(got)[1, 3:] == 0)


def test_routing_is_top_k_of_score_plus_bias_weighted_by_the_score():
    """A hand-written top-k; a bias that changes the choice and leaves the
    weights those of the unbiased scores."""
    layer = HeldExpertsMoE(d_model=8, d_ff=4, n_experts=6, topk=2, n_held=6,
                           routed_scaling=2.5)
    x = jnp.eye(8)[:3]
    logits = np.array([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0],
                       [0.0, 0.1, 0.2, 0.3, 0.4, 0.5],
                       [1.0, 1.0, -3.0, 0.9, 0.0, 0.0]], np.float32)
    router = jnp.zeros((8, 6)).at[:3].set(logits)
    s = 1 / (1 + np.exp(-logits))
    w, ids = layer.route(router, jnp.zeros(6), x)
    assert ids.tolist() == [[0, 1], [5, 4], [0, 1]]
    for r in range(3):
        chosen = s[r, ids[r]]
        np.testing.assert_allclose(w[r], 2.5 * chosen / chosen.sum(),
                                   rtol=1e-6)
    bias = jnp.asarray([0, 0, 0, 0, 0, 0.9], jnp.float32)   # lifts expert 5
    wb, idb = layer.route(router, bias, x)
    assert idb.tolist() == [[5, 0], [5, 4], [5, 0]]         # choice changed
    for r in range(3):                                      # weights: no bias
        chosen = s[r, idb[r]]
        np.testing.assert_allclose(wb[r], 2.5 * chosen / chosen.sum(),
                                   rtol=1e-6)


@pytest.mark.parametrize("n", [5, 200], ids=["tile16", "tile128"])
def test_no_pair_is_dropped_when_every_row_picks_the_same_experts(n):
    """The worst routing: a bias makes every row choose the same ``topk``
    held experts. Every pair is computed, none dropped, and the result is
    those experts' dense sum."""
    layer = HeldExpertsMoE(d_model=16, d_ff=8, n_experts=12, topk=3,
                           n_held=6, lo=2, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {
        "router": jax.random.normal(ks[0], (16, 12)) * 0.1,
        "bias": jnp.zeros(12).at[jnp.asarray([3, 4, 7])].set(5.0),
        "w_gate_up": jax.random.normal(ks[1], (6, 16, 16)) * 0.25,
        "w_down": jax.random.normal(ks[2], (6, 8, 16)) * 0.3}
    x = jax.random.normal(ks[3], (n, 16))
    valid = jnp.arange(n) != 1                 # one padding row routes nowhere
    y, stats = jax.jit(layer.routed)(params, x, valid)
    stats = dict(zip(MOE_STATS, np.asarray(stats).tolist()))
    assert stats == {"moe_pairs_routed": 3 * (n - 1),
                     "moe_pairs_held": 3 * (n - 1), "moe_experts_touched": 3,
                     "moe_dropped_pairs": 0}
    w, ids = layer.route(params["router"], params["bias"], x)
    assert set(np.asarray(ids).ravel().tolist()) == {3, 4, 7}
    want = jnp.zeros_like(x)
    for j in range(3):
        e = ids[:, j] - 2
        h = jnp.einsum("nd,ndf->nf", x, params["w_gate_up"][e])
        act = jax.nn.silu(h[:, :8]) * h[:, 8:]
        want += w[:, j, None] * jnp.einsum("nf,nfd->nd", act,
                                           params["w_down"][e])
    want = jnp.where(valid[:, None], want, 0.0)
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_rows_by_expert_has_room_for_every_pair():
    ids = jnp.asarray([[0, 1], [0, 2], [0, 1], [2, 0]], jnp.int32)
    held = jnp.asarray([[1, 1], [1, 0], [1, 1], [1, 1]], bool)
    row, pair, tile_expert, n_tiles, counts = moe_utils.rows_by_expert(
        ids, held, n_experts=3, tile=2)
    assert counts.tolist() == [4, 2, 1] and int(n_tiles) == 4
    assert pair.shape == (((4 * 2) // 2 + 3) * 2,)
    # expert 0's four rows, expert 1's two, expert 2's one and its padding
    assert pair.tolist()[:8] == [0, 2, 4, 7, 1, 5, 6, 8]
    assert tile_expert.tolist()[:4] == [0, 0, 1, 2]
    assert row[1, 1] == pair.shape[0]              # not held: no row
    assert [int(pair[row[i, j]]) for i, j in ((0, 0), (2, 1), (3, 0))] \
        == [0, 5, 6]


def test_the_grouped_product_follows_tiles_to_their_experts():
    """``group_of``: tiles of rows multiply the weights of the expert each
    belongs to (Pallas kernel under the interpreter against the einsum)."""
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.standard_normal((5, 16, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 3, 128, 256)), jnp.float32)
    group_of = jnp.asarray([0, 0, 2, 2, 1], jnp.int32)
    live = jnp.asarray([1, 1, 1, 0, 0], jnp.int32)
    got = moe_utils.grouped_gemm_skip(rows, w, live, layer_idx=1,
                                      group_of=group_of, block_n=128,
                                      interpret=True, name="moe_grouped_gemm")
    want = jnp.einsum("tcd,tdf->tcf", rows, w[1][group_of])
    np.testing.assert_allclose(got[:3], want[:3], rtol=1e-4, atol=1e-4)
    assert np.all(np.asarray(got[3:]) == 0)          # dead tiles: zeros


def test_the_shares_add_up_to_the_uncut_layer():
    """Sixteen chips' shares of sixteen experts each (the program's layer,
    told which experts it holds), the shared expert counted once, equal the
    reference's uncut layer over all 256 routed experts."""
    uncut = dataclasses.replace(SIZES, router_width=256, held=256, lo=0,
                                topk=8)
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(jax.random.PRNGKey(12), (24, uncut.d_model))
    lw = reference.f32(family.plain_layer(uncut, key, False))
    want = (family.swiglu(x, lw["s_gu"], lw["s_d"], "float32")
            + family.routed_part(uncut, x, lw, "float32"))
    total = family.swiglu(x, lw["s_gu"], lw["s_d"], "float32")
    held_pairs = 0
    for share in range(16):
        m = dataclasses.replace(uncut, held=16, lo=16 * share)
        slw = family.plain_layer(m, key, False)
        np.testing.assert_array_equal(slw["e_gu"],
                                      lw["e_gu"][16 * share:16 * share + 16])
        layer = HeldExpertsMoE(
            d_model=m.d_model, d_ff=m.expert_width, n_experts=256, topk=8,
            n_held=16, lo=m.lo, routed_scaling=m.scaling, dtype=jnp.float32)
        y, stats = layer.routed(
            {"router": slw["router"], "bias": slw["bias"],
             "w_gate_up": slw["e_gu"], "w_down": slw["e_d"]}, x)
        total = total + y
        held_pairs += int(stats[1])
        assert int(stats[3]) == 0
    assert held_pairs == 24 * 8                   # every pair has one owner
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_more_than_one_device_is_refused_by_name():
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2], set_default=False)
    engine = Engine(DeepseekV3Config.tiny(), mesh=mesh, mode="dist")
    pool = KVPool(engine.config, n_blocks=8, block_size=4, mesh=mesh)
    step = jax.jit(engine._make_sm("dist", paged="decode",
                                   paged_attn="gather",
                                   state_specs=pool.specs))
    with pytest.raises(NotImplementedError,
                       match="latent attention under tensor parallelism"):
        step.lower(engine.params, jnp.zeros((2, 1), jnp.int32), pool.state,
                   jnp.zeros((2,), jnp.int32), jnp.zeros((2, 16), jnp.int32),
                   jnp.ones((2,), bool))


def test_latent_pool_copies_a_block_and_states_its_wire_format():
    cfg = DeepseekV3Config.tiny()
    pool = KVPool(cfg, n_blocks=6, block_size=4)
    assert pool.latent and pool.state.latent
    assert pool.kv_fingerprint() == "float32:none:latent128"
    pool.state = dataclasses.replace(
        pool.state, kv=pool.state.kv.at[:, 2].set(7.0))
    pool._copy_block_device(2, 5)
    assert np.all(np.asarray(pool.state.kv[:, 5]) == 7.0)
    assert np.all(np.asarray(pool.state.kv[:, 4]) == 0.0)
    pool.check_invariants()
    with pytest.raises(NotImplementedError, match="quantized"):
        KVPool(cfg, n_blocks=6, block_size=4, kv_dtype="int8")


def test_counts_of_the_published_configuration():
    """The family's counts at JoyAI-LLM-Flash's sizes, against the issue's
    arithmetic: 9.55 GB held with the embedding (which a decode step does
    not read: 9.06 GB without), 46,080 B of latent rows a token."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "perfbench/configs/joyai-llm-flash-ep16.json")) as f:
        cfg = json.load(f)
    m = family.sizes(cfg)
    assert (m.router_width, m.held, m.topk, m.n_layers) == (256, 16, 8, 40)
    assert family.attn_params(m) == 26_345_472
    assert family.expert_params(m) == 4_718_592
    assert family.latent_attn_min_bytes(m, [1]) == 46_080
    held = family.weight_bytes_held(m)
    assert 9.0e9 < held < 9.1e9
    assert family.decode_step_min_bytes(m, [1000, 24]) == held + 1024 * 46_080
    pairs, touched = family.moe_expected(m, 32)
    assert pairs == pytest.approx(39 * 16) and \
        touched == pytest.approx(39 * 16 * (1 - (31 / 32) ** 32))
    assert family.moe_ffn_min_bytes(m, 10) == 10 * 2 * 4_718_592
    mcfg = family.program_config(cfg, m)
    assert (mcfg.cache_width, mcfg.cache_row, mcfg.n_held) == (576, 640, 16)
