"""Quantized KV cache tests (ISSUE 20 tentpole): int8/fp8 wire-dtype
pools with per-row scales, dequantized inside the fused paged-attention
kernel.

The load-bearing guarantees (docs/serving.md, "Quantized KV cache"):
  1. rowmax:v1 scheme — per-(token row, kv head) symmetric absmax
     quantization; appends never requantize existing rows, zero rows
     stay exact zeros;
  2. pool discipline — scale arenas partition with their blocks (CoW
     copies move scales with wire rows, truncate releases both),
     ``check_invariants`` proves it, and adoption across wire
     fingerprints is refused with both fingerprints named;
  3. byte model — ``perf_model`` bills wire-width pool traffic plus the
     scale arena, pinning int8 KV bytes at ~0.5x the bf16 bill on both
     the fused and gather paths;
  4. resources — the registered ``paged.*.kvq`` variants (+probe) prove
     clean at world 2/4/8 and the quantized VMEM staging footprint is
     SMALLER than the f32 pool's at serving geometry;
  5. checkpoint identity — pool geometry (and so the checkpoint
     manifest) carries the wire dtype; restore refuses a fleet rebuilt
     in a different KV mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.analysis import registry as _reg
from triton_distributed_tpu.analysis import resources
from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.resilience import load_checkpoint
from triton_distributed_tpu.runtime import perf_model as pm
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import Fleet, KVPool, RadixPrefixCache


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


# -- 1. the rowmax:v1 scheme --------------------------------------------------


@pytest.mark.parametrize("wire,qmax", [(jnp.int8, 127.0),
                                       (jnp.float8_e4m3fn, 448.0)])
def test_quantize_roundtrip_properties(wire, qmax):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 5, 4, 16)) * 7.0, jnp.float32)
    q, s = nn.quantize_kv_rows(x, wire)
    assert q.shape == x.shape and q.dtype == jnp.dtype(wire)
    assert s.shape == x.shape[:-1] and s.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(s), np.max(np.abs(np.asarray(x)), axis=-1) / qmax,
        rtol=1e-6)
    back = nn.dequantize_kv_rows(q, s)
    # symmetric absmax: elementwise error bounded by one quantization
    # step of the row's own scale (int8 rounds; fp8 has ~2^-3 mantissa)
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.asarray(s)[..., None] * (0.51 if wire == jnp.int8 else 0.0)
    if wire == jnp.int8:
        assert (err <= bound + 1e-7).all()
    else:
        assert (err <= np.abs(np.asarray(x)) * 0.07 + 1e-7).all()
    # all-zero rows: scale 0, exact-zero reconstruction (no NaN/inf)
    z = jnp.zeros((2, 3, 16), jnp.float32)
    qz, sz = nn.quantize_kv_rows(z, wire)
    assert float(jnp.max(jnp.abs(sz))) == 0.0
    np.testing.assert_array_equal(np.asarray(nn.dequantize_kv_rows(qz, sz)),
                                  np.asarray(z))


def test_quantize_rejects_unknown_wire_dtype():
    with pytest.raises(ValueError, match="wire dtype"):
        nn.quantize_kv_rows(jnp.zeros((1, 4)), jnp.int32)


# -- 2. pool discipline -------------------------------------------------------


def _qpool(config, kv_dtype="int8", n_blocks=8, block_size=4):
    pool = KVPool(config, n_blocks=n_blocks, block_size=block_size,
                  max_seq_len=32, kv_dtype=kv_dtype)
    return pool, RadixPrefixCache(pool)


@pytest.mark.parametrize("kv_dtype,wire", [("int8", jnp.int8),
                                           ("fp8", jnp.float8_e4m3fn)])
def test_pool_quant_lifecycle(setup, kv_dtype, wire):
    _, config, _ = setup
    pool, cache = _qpool(config, kv_dtype)
    st = pool.state
    # one arena of wire rows, K plane and V plane of a block side by side,
    # and one of scales in the same layout
    assert st.kv.dtype == jnp.dtype(wire) and st.kv.shape[2] == 2
    assert st.kv_scale is not None
    assert st.kv_scale.shape == st.kv.shape[:-1]        # the arena minus dh
    assert st.kv_scale.dtype == jnp.float32
    assert pool.kv_fingerprint() == (
        f"{jnp.dtype(wire).name}:rowmax:v1:paired")
    assert pool.geometry()["kv_dtype"] == jnp.dtype(wire).name
    toks = list(range(10))
    assert pool.ensure("a", 10)
    assert cache.insert("a", toks) == 3
    pool.release("a")
    pool.check_invariants()
    m = cache.match(toks, max_len=9)
    assert pool.ensure("b", 10, adopt=m.blocks, cow_src=m.cow_src)
    pool.check_invariants()
    pool.release("b")
    pool.check_invariants()


def test_unquantized_pool_has_no_scale_arenas(setup):
    _, config, _ = setup
    pool = KVPool(config, n_blocks=4, block_size=4, max_seq_len=32)
    assert pool.state.kv_scale is None
    assert pool.kv_fingerprint().endswith(":none:paired")
    pool.check_invariants()


def test_mixed_fingerprint_adoption_rejected(setup):
    """A cached block recorded under a FOREIGN wire fingerprint (an old
    scheme version, a restored-from-elsewhere arena) must be refused at
    adoption, naming both fingerprints — its bytes are garbage under
    this pool's (dtype, scheme)."""
    _, config, _ = setup
    pool, cache = _qpool(config)
    toks = list(range(8))
    assert pool.ensure("a", 8)
    cache.insert("a", toks)
    pool.release("a")
    m = cache.match(toks, max_len=7)
    stale = "int8:rowmax:v0"
    pool._cached_fp[m.blocks[0]] = stale
    with pytest.raises(ValueError) as ei:
        pool.ensure("b", 8, adopt=m.blocks, cow_src=m.cow_src)
    assert stale in str(ei.value)
    assert pool.kv_fingerprint() in str(ei.value)
    pool.check_invariants()                    # refusal mutated nothing
    # healing the record makes the same adoption legal again
    pool._cached_fp[m.blocks[0]] = pool.kv_fingerprint()
    m2 = cache.match(toks, max_len=7)
    assert pool.ensure("b", 8, adopt=m2.blocks, cow_src=m2.cow_src)
    pool.check_invariants()


def test_cow_copies_scale_rows_with_wire_rows(setup):
    """The CoW block must carry the source block's scale rows — a wire
    row without its scale dequantizes to garbage."""
    _, config, _ = setup
    pool, cache = _qpool(config)
    toks = list(range(6))
    assert pool.ensure("a", 6)
    src = pool.table("a")[1]
    st = pool.state
    pool.state = type(st)(
        kv=st.kv.at[:, src, 0].set(7).at[:, src, 1].set(-3),
        kv_scale=st.kv_scale.at[:, src, 0].set(0.125)
        .at[:, src, 1].set(2.5))
    cache.insert("a", toks)
    pool.release("a")
    m = cache.match(toks, max_len=5)
    assert m.cow_src == src
    assert pool.ensure("b", 6, adopt=m.blocks, cow_src=m.cow_src)
    dst = pool.table("b")[1]
    assert dst != src
    st = pool.state
    for arena in (st.kv, st.kv_scale):           # both planes of each
        np.testing.assert_array_equal(np.asarray(arena[:, dst]),
                                      np.asarray(arena[:, src]))
    assert np.all(np.asarray(st.kv[:, dst, 0]) == 7)
    assert np.all(np.asarray(st.kv[:, dst, 1]) == -3)
    pool.release("b")
    pool.check_invariants()


def test_truncate_on_quantized_pool_keeps_partition(setup):
    """Rollback over a quantized pool: private tail blocks free (their
    scale rows go with them — the next owner overwrites both), adopted
    blocks decref only, invariants hold throughout."""
    _, config, _ = setup
    pool, cache = _qpool(config)
    toks = list(range(8))
    assert pool.ensure("warm", 8)
    cache.insert("warm", toks)
    pool.release("warm")
    m = cache.match(toks, max_len=8)
    assert pool.ensure("b", 9, adopt=m.blocks, cow_src=m.cow_src)
    free0 = pool.n_free
    assert pool.truncate("b", 8) == 1          # private tail: a real free
    assert pool.n_free == free0 + 1
    assert pool.truncate("b", 4) == 0          # adopted: decref only
    assert pool.n_cached == 2
    pool.check_invariants()
    pool.release("b")
    pool.check_invariants()


# -- 3. the byte model --------------------------------------------------------


def _kv_only(total, B, L, Hq, dh, itemsize):
    return total - B * L * Hq * dh * (itemsize + 4)


@pytest.mark.parametrize("L,q_tile", [(1, None), (8, 4)])
def test_perf_model_int8_halves_fused_kv_bytes(L, q_tile):
    B, mb, bs, Hkv, dh, Hq = 4, 4, 8, 2, 64, 4
    kw = dict(n_q_heads=Hq, L=L, q_tile=q_tile)
    base = pm.paged_attn_bytes(B, mb, bs, Hkv, dh, itemsize=2, **kw)
    kvq = pm.paged_attn_bytes(B, mb, bs, Hkv, dh, itemsize=2,
                              kv_itemsize=1, kv_scales=True, **kw)
    r = _kv_only(kvq, B, L, Hq, dh, 2) / _kv_only(base, B, L, Hq, dh, 2)
    # per KV row: (dh*1 + 4) / (dh*2) at dh=64 -> 68/128
    assert r == pytest.approx(68 / 128)
    assert 0.5 <= r <= 0.55


def test_perf_model_gather_first_touch_is_wire_width():
    """The gather oracle reads the pool at wire width but materializes a
    compute-dtype view (written once, read once) — only 1 of its 3 KV
    touches shrinks, and the model says exactly that."""
    B, mb, bs, Hkv, dh, Hq = 2, 4, 8, 2, 64, 4
    S = mb * bs
    kw = dict(n_q_heads=Hq, method="gather")
    base = pm.paged_attn_bytes(B, mb, bs, Hkv, dh, itemsize=2, **kw)
    kvq = pm.paged_attn_bytes(B, mb, bs, Hkv, dh, itemsize=2,
                              kv_itemsize=1, kv_scales=True, **kw)
    view_row = 2 * Hkv * dh * 2
    assert base == B * 1 * Hq * dh * 6 + B * S * 3 * view_row
    assert kvq == (B * 1 * Hq * dh * 6
                   + B * S * (2 * Hkv * (dh + 4) + 2 * view_row))
    assert kvq < base


def test_step_hbm_bytes_drop_under_quantization():
    config = ModelConfig.from_name("tiny")
    rows = [(1, 24), (8, 16)]
    base = pm.step_hbm_bytes(config, rows, block_size=4, itemsize=4)
    kvq = pm.step_hbm_bytes(config, rows, block_size=4, itemsize=4,
                            kv_itemsize=1, kv_scales=True)
    weights = float(pm.matmul_params(config)) * 4
    assert kvq < base
    assert kvq - weights < base - weights      # the KV term shrank
    # same rows, same flops: quantization moves bytes only
    assert pm.step_flops(config, rows) == pm.step_flops(config, rows)


# -- 4. resources: registered variants + footprint ----------------------------


_KVQ_KERNELS = ("paged.decode.kvq", "paged.prefill.kvq",
                "paged.decode.kvq+probe", "paged.prefill.kvq+probe")


@pytest.mark.parametrize("world", (2, 4, 8))
def test_kvq_kernel_variants_prove_clean(world):
    bad = {}
    for name in _KVQ_KERNELS:
        for dtype in ("int8", "float8_e4m3fn"):
            fs = resources.check_kernel(name, world, dict(dtype=dtype))
            if fs:
                bad[(name, dtype)] = [str(f) for f in fs]
    assert not bad, bad


def test_kvq_vmem_staging_shrinks_at_serving_geometry():
    """At a serving-scale tile (32 kv heads, dh=128, bs=16) the int8
    staging buffers + their f32 scale strips fit in LESS VMEM than the
    f32 pool's staging — the headroom the autotuner's bigger quantized
    tiles spend."""
    kw = dict(tile_blocks=2, bs=16, n_kv=32, g=1, dh=128, max_blocks=4)
    base = resources.footprint(
        _reg.get("paged.decode").build(1, dtype="float32", **kw))
    kvq = resources.footprint(
        _reg.get("paged.decode.kvq").build(1, dtype="int8", **kw))
    assert kvq.vmem_bytes < base.vmem_bytes, (kvq, base)


# -- 5. checkpoint identity ---------------------------------------------------


def test_checkpoint_geometry_carries_kv_dtype(setup, tmp_path):
    _, _config, engine = setup
    kw = dict(n_replicas=2, n_slots=2, n_blocks=16, block_size=4,
              prefill_chunk=8)
    f1 = Fleet.build(engine, kv_dtype="int8", **kw)
    f1.submit([1, 2, 3], 4, req_id="r0")
    ck = str(tmp_path / "ck")
    f1.checkpoint(ck)
    state, _man = load_checkpoint(ck)
    assert state["pool_geometry"]["kv_dtype"] == "int8"
    with pytest.raises(ValueError, match="geometry"):
        Fleet.restore(ck, engine, **kw)        # bf16/f32 pool: refused
    f2 = Fleet.restore(ck, engine, kv_dtype="int8", **kw)
    assert f2.replicas[0].engine.pool.kv_fingerprint() == \
        "int8:rowmax:v1:paired"
