"""MoE model family tests — the analog of the reference's
test_ep_moe_inference.py (EP-MoE routing -> a2a dispatch -> grouped expert
GEMMs -> combine, end-to-end through the engine).

Buffers stay small and the EP world is 4 (not 8): the per-device a2a
staging is (world, capacity, hidden) and the single-core interpreter
deadlocks on cross-device-blocking buffers >= 16KB (conftest ceiling).
"""

import jax
from triton_distributed_tpu.runtime.compat import shard_map
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.layers.moe_mlp import MoEMLP
from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.runtime import assert_allclose
from triton_distributed_tpu.runtime.mesh import make_mesh

WORLD = 4


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh({"tp": WORLD}, devices=jax.devices()[:WORLD],
                     set_default=False)


def _layer(**kw):
    defaults = dict(d_model=32, d_ff=16, n_experts=8, topk=2,
                    axis="tp", dtype=jnp.float32)
    defaults.update(kw)
    return MoEMLP(**defaults)


def _np_reference(params, x, layer: MoEMLP):
    """Straight-line numpy implementation of the HF Qwen3-MoE block."""
    xf = np.asarray(x, np.float64)
    router = np.asarray(params["router"], np.float64)
    gu = np.asarray(params["w_gate_up"], np.float64)
    dn = np.asarray(params["w_down"], np.float64)
    logits = xf @ router
    e = np.exp(logits - logits.max(-1, keepdims=True))
    probs = e / e.sum(-1, keepdims=True)
    out = np.zeros_like(xf)
    ff = gu.shape[-1] // 2
    for t in range(xf.shape[0]):
        ids = np.argsort(-probs[t])[: layer.topk]
        w = probs[t][ids]
        if layer.norm_topk_prob:
            w = w / w.sum()
        for wi, eid in zip(w, ids):
            h = xf[t] @ gu[eid]
            gate, up = h[:ff], h[ff:]
            act = gate / (1 + np.exp(-gate)) * up
            out[t] += wi * (act @ dn[eid])
    return out


def test_moe_mlp_dist_matches_xla_and_numpy(mesh4, rng):
    layer = _layer(capacity=32, expert_capacity=64)  # drop-free
    params = layer.init(jax.random.PRNGKey(0), mesh=mesh4)
    x = jnp.asarray(rng.standard_normal((8, 32), dtype=np.float32))

    dist = layer.fwd(params, x, mesh=mesh4, mode="dist")
    xla = layer.fwd(params, x, mesh=mesh4, mode="xla")
    golden = _np_reference(jax.device_get(params), np.asarray(x), layer)
    assert_allclose(dist, xla, atol=1e-5, rtol=1e-5)
    assert_allclose(dist, golden, atol=1e-4, rtol=1e-4)


def test_moe_mlp_drop_stats_surfaced(mesh4, rng):
    """Tight capacities must report their routing overflow through
    return_stats (the capacity-sizing observable), and generous ones must
    report zero."""
    from jax.sharding import PartitionSpec as P

    tight = _layer(capacity=8, expert_capacity=8)
    params = tight.init(jax.random.PRNGKey(2), mesh=mesh4)
    # 128 global tokens = 32/rank x topk 2 = 64 pairs per source rank, but
    # a source can send at most world x capacity = 32 pairs: >= 32 drops
    # per rank by pigeonhole — overflow is deterministic, not seed luck.
    x = jnp.asarray(rng.standard_normal((128, 32), dtype=np.float32))

    def run(layer):
        f = jax.jit(shard_map(
            lambda p, xl: layer.dist_fwd(p, xl, return_stats=True),
            mesh=mesh4, in_specs=(layer.param_specs(), P("tp", None)),
            out_specs=(P("tp", None), P()), check_vma=False))
        _, stats = f(params, x)
        return {k: int(np.asarray(v).ravel()[0]) for k, v in stats.items()}

    roomy = _layer(capacity=256, expert_capacity=512)
    assert sum(run(roomy).values()) == 0
    # run() reads rank 0's shard of the stats; the pigeonhole bound
    # (64 pairs vs world x capacity = 32 sendable) is per rank.
    assert run(tight)["n_dropped_dispatch"] >= 32


@pytest.mark.parametrize("stacked", [False, True])
def test_grouped_gemm_skip_matches_einsum(rng, stacked):
    """The count-aware Pallas grouped GEMM (empty-expert weight-fetch skip)
    must match the einsum golden on the non-empty experts and return zeros
    for empty ones — including leading/trailing/consecutive empties (the
    eff-index clamping cases). The stacked form ((L, E, d, f) weights +
    layer_idx selected in the kernel's index map — the scan-safe path)
    must agree layer for layer."""
    from triton_distributed_tpu.kernels.moe_utils import (
        grouped_gemm,
        grouped_gemm_skip,
    )

    E, cap, d, f = 8, 16, 32, 128
    counts = jnp.asarray([0, 0, 3, 0, 16, 1, 0, 0], jnp.int32)
    grouped = jnp.asarray(rng.standard_normal((E, cap, d)), jnp.float32)
    # Zero the slots beyond each expert's count (the grid contract).
    valid = jnp.arange(cap)[None, :] < counts[:, None]
    grouped = jnp.where(valid[..., None], grouped, 0)
    if stacked:
        L = 3
        w_all = jnp.asarray(rng.standard_normal((L, E, d, f)), jnp.float32)
        for li in range(L):
            got = jax.jit(lambda g, w, c, li=li: grouped_gemm_skip(
                g, w, c, layer_idx=jnp.int32(li),
                interpret=True))(grouped, w_all, counts)
            golden = grouped_gemm(grouped, w_all[li])
            assert_allclose(got, jnp.where(valid[..., None], golden, 0),
                            atol=1e-4, rtol=1e-4)
        return
    w = jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32)
    got = jax.jit(lambda g, w, c: grouped_gemm_skip(g, w, c,
                                                    interpret=True))(
        grouped, w, counts)
    golden = grouped_gemm(grouped, w)
    assert_allclose(got, jnp.where(valid[..., None], golden, 0), atol=1e-4,
                    rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(got[counts == 0]), 0.0)


def test_moe_mlp_router_normalization(mesh4, rng):
    """norm_topk_prob=False must keep the raw softmax mass (HF flag)."""
    layer = _layer(norm_topk_prob=False, capacity=32, expert_capacity=64)
    params = layer.init(jax.random.PRNGKey(1), mesh=mesh4)
    x = jnp.asarray(rng.standard_normal((8, 32), dtype=np.float32))
    out = layer.fwd(params, x, mesh=mesh4, mode="dist")
    golden = _np_reference(jax.device_get(params), np.asarray(x), layer)
    assert_allclose(out, golden, atol=1e-4, rtol=1e-4)


def test_moe_engine_e2e_dist_matches_xla(mesh4):
    """tiny-moe through the WHOLE engine: greedy tokens must agree between
    the a2a dispatch path and the XLA golden path, and serve_scanned must
    agree with serve.

    What only this test shows: the a2a dispatch inside ``Engine``'s two
    loops, token for token against the golden (the layer alone is
    ``test_moe_mlp_dist_matches_xla_and_numpy``'s). It is its own long form:
    the scanned loop generates three tokens, so its body runs a second time
    on its own carry (two lost that, PR 30), and the per-step loop two, a
    prefill and the decode step that reads it; the per-step loop's second
    decode would be a sixth interpreted forward of ten seconds for what the
    scanned loop's second iteration already shows."""
    # Worst-case capacities (factor covers any routing skew): the
    # token-equality assertion needs the drop-free regime.
    config = ModelConfig.from_name("tiny-moe", moe_capacity_factor=64.0)
    key = jax.random.PRNGKey(7)
    dist_engine = Engine(config, mesh=mesh4, mode="dist", key=key,
                         block_n=8)
    xla_engine = Engine(config, mesh=mesh4, mode="xla", key=key,
                        params=dist_engine.params, block_n=8)
    prompt = jnp.asarray(np.arange(WORLD * 4).reshape(WORLD, 4) % 128,
                         jnp.int32)
    # ~10 s a dist forward under the interpreter: five of them.
    t_xla = np.asarray(xla_engine.serve(prompt, gen_len=3))
    t_dist = dist_engine.serve(prompt, gen_len=2)
    np.testing.assert_array_equal(np.asarray(t_dist), t_xla[:, :2])
    t_scan = dist_engine.serve_scanned(prompt, gen_len=3)
    np.testing.assert_array_equal(np.asarray(t_scan), t_xla)


def test_moe_engine_drop_stats_audit(mesh4):
    """Engine.moe_drop_stats (ADVICE r4): zeros at worst-case capacity,
    nonzero once the factor is squeezed — the documented capacity audit."""
    prompt = jnp.asarray(np.arange(WORLD * 4).reshape(WORLD, 4) % 128,
                         jnp.int32)
    roomy = Engine(ModelConfig.from_name("tiny-moe",
                                         moe_capacity_factor=64.0),
                   mesh=mesh4, mode="dist", key=jax.random.PRNGKey(7),
                   block_n=8)
    stats = roomy.moe_drop_stats(prompt)
    assert stats == {"n_dropped_dispatch": 0, "n_dropped_expert": 0}

    # Squeezing via the factor: the 16-row expert-grid minimum
    # (moe_mlp._round16) floors expert capacity, so the overflow must come
    # from the DISPATCH capacity — a longer prompt pushes enough (token, k)
    # pairs at one rank to overflow its _round8'd dispatch block.
    tight = Engine(ModelConfig.from_name("tiny-moe",
                                         moe_capacity_factor=0.25),
                   mesh=mesh4, mode="dist", key=jax.random.PRNGKey(7),
                   params=roomy.params, block_n=8)
    long_prompt = jnp.asarray(
        np.arange(WORLD * 16).reshape(WORLD, 16) % 128, jnp.int32)
    stats = tight.moe_drop_stats(long_prompt)
    assert stats["n_dropped_dispatch"] + stats["n_dropped_expert"] > 0

    dense = Engine(ModelConfig.from_name("tiny"), mesh=mesh4, mode="dist",
                   key=jax.random.PRNGKey(0), block_n=8)
    with pytest.raises(ValueError, match="MoE"):
        dense.moe_drop_stats(prompt)


def test_moe_ar_mode_rejected(mesh4):
    config = ModelConfig.from_name("tiny-moe")
    engine = Engine(config, mesh=mesh4, mode="ar",
                    key=jax.random.PRNGKey(0), block_n=8)
    with pytest.raises(ValueError, match="MoE"):
        engine.serve(jnp.ones((WORLD, 2), jnp.int32), gen_len=1)


def test_moe_presets():
    c = ModelConfig.from_name("qwen3-30b-a3b")
    assert c.n_experts == 128 and c.n_experts_per_tok == 8
    assert c.moe_d_ff == 768 and c.d_model == 2048
    c2 = ModelConfig.from_name("qwen3-235b-a22b")
    assert c2.n_experts == 128 and c2.moe_d_ff == 1536
