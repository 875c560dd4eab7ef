"""AG-GEMM tests — analog of the reference's test_ag_gemm.py (golden:
allgather + matmul), 8-way on the virtual CPU mesh.

Shapes obey the interpreter's per-buffer ceiling (conftest docstring): with
world=8, m=8, K=128, n_local=128 the largest buffer is the gathered-A staging
(8*8*128*4B = 4KB/slot, 32KB total in HBM staging is fine — the ceiling bites
on *VMEM/input* buffers; keep each under 12KB).
"""

import jax
from triton_distributed_tpu.runtime.compat import shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_distributed_tpu.kernels.allgather_gemm import (
    AGGEMMConfig,
    ag_gemm,
    ag_gemm_device,
    ag_gemm_single_chip,
)
from triton_distributed_tpu.runtime import assert_allclose

WORLD = 8


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape, dtype=np.float32), dtype)


def _ab(rng, M, K, N, dtype=jnp.float32):
    return _rand(rng, (M, K), dtype), _rand(rng, (K, N), dtype)


def test_ag_gemm_vs_golden(mesh8, rng):
    M, K, N = 8 * WORLD, 32, 128 * WORLD
    a, b = _ab(rng, M, K, N)
    out = ag_gemm(a, b, mesh=mesh8, config=AGGEMMConfig(block_n=128))
    golden = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(out, golden)


def test_ag_gemm_bf16(mesh8, rng):
    M, K, N = 4 * WORLD, 64, 128 * WORLD
    a, b = _ab(rng, M, K, N, jnp.bfloat16)
    out = ag_gemm(a, b, mesh=mesh8, config=AGGEMMConfig(block_n=128))
    assert out.dtype == jnp.bfloat16
    golden = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(out, golden, atol=0.5, rtol=0.05)


def test_ag_gemm_multiple_n_tiles(mesh8, rng):
    M, K, N = 8 * WORLD, 16, 256 * WORLD
    a, b = _ab(rng, M, K, N)
    out = ag_gemm(a, b, mesh=mesh8, config=AGGEMMConfig(block_n=128))
    golden = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(out, golden)


def test_ag_gemm_sharded_inputs(mesh8, rng):
    """Inputs physically sharded over the mesh (not replicated) work too."""
    M, K, N = 8 * WORLD, 32, 128 * WORLD
    a, b = _ab(rng, M, K, N)
    a = jax.device_put(a, NamedSharding(mesh8, P("tp", None)))
    b = jax.device_put(b, NamedSharding(mesh8, P(None, "tp")))
    out = ag_gemm(a, b, mesh=mesh8, config=AGGEMMConfig(block_n=128))
    golden = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(out, golden)


@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 128, 384)])
def test_single_chip_matmul(rng, shape):
    M, K, N = shape
    a, b = _ab(rng, M, K, N)
    out = ag_gemm_single_chip(a, b, block_m=128, block_n=128, block_k=64)
    assert_allclose(out, np.asarray(a) @ np.asarray(b))


def test_single_chip_bad_blocks_raise(rng):
    a, b = _ab(rng, 100, 128, 128)
    with pytest.raises(ValueError, match="not divisible"):
        ag_gemm_single_chip(a, b, block_m=64, auto_block=False)


def test_single_chip_auto_block_fits_odd_n(rng):
    a, b = _ab(rng, 128, 128, 320)  # 320 not divisible by default 512->320
    out = ag_gemm_single_chip(a, b)
    assert_allclose(out, np.asarray(a) @ np.asarray(b))


def test_world1_ragged_k_delegates_not_raises(rng):
    """The world==1 degenerate paths must keep the automatic XLA delegation
    on shapes with no MXU-aligned divisor (e.g. the smoke shape's per-rank
    K 3696) — passing config.block_n down would make the blocks 'explicit'
    and turn delegation into a ValueError (r2 review finding)."""
    from jax.sharding import Mesh

    from triton_distributed_tpu.kernels.gemm_reduce_scatter import gemm_rs_device

    a, b = _ab(rng, 16, 132, 128)  # K=132: no 128-aligned divisor <= default
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("tp",))

    def run(fn):
        return jax.jit(shard_map(
            fn, mesh=mesh1, in_specs=(P(None, None), P(None, None)),
            out_specs=P(None, None), check_vma=False))(a, b)

    golden = np.asarray(a) @ np.asarray(b)
    assert_allclose(run(lambda al, bl: ag_gemm_device(al, bl, axis="tp")),
                    golden)
    assert_allclose(run(lambda al, bl: gemm_rs_device(al, bl, axis="tp")),
                    golden)


def test_ag_gemm_2d_vs_golden(rng):
    """Inter-slice AG-GEMM on a (dcn=2, ici=4) mesh: intra-slice A gathered
    inside the Pallas overlap kernel, inter-slice A blocks via the
    slice-level ppermute ring — vs the dense golden (the reference's
    inter-node AG-GEMM dispatch, allgather.py:554)."""
    from triton_distributed_tpu.kernels.allgather_gemm import ag_gemm_2d_device
    from triton_distributed_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"dcn": 2, "ici": 4}, set_default=False)
    M, K, N = 8 * 4, 32, 8 * 128   # dcn-major M sharding, N over full world
    a, b = _ab(rng, M, K, N)

    def f(al, bl):
        return ag_gemm_2d_device(al, bl, ici_axis="ici", dcn_axis="dcn",
                                 config=AGGEMMConfig(block_n=128))

    out = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(("dcn", "ici"), None), P(None, ("dcn", "ici"))),
        out_specs=P(None, ("dcn", "ici")),
        check_vma=False,
    ))(a, b)
    assert_allclose(out, np.asarray(a) @ np.asarray(b))


def test_fused_matmul_step(rng):
    """c + a @ (b + s) fused in one kernel with c donated (the bench arm /
    k-split accumulation building block)."""
    from triton_distributed_tpu.kernels.allgather_gemm import fused_matmul_step

    M, K, N = 16, 256, 128
    a, b = _ab(rng, M, K, N)
    c = jnp.asarray(rng.standard_normal((M, N), dtype=np.float32))
    for bk in (None, 128):
        got = jax.jit(lambda c, a, b, bk=bk: fused_matmul_step(
            c, a, b, 0.75, block_m=8, block_n=128, block_k=bk))(c, a, b)
        golden = (np.asarray(c) +
                  np.asarray(a) @ (np.asarray(b) + np.float32(0.75)))
        assert got.dtype == jnp.float32
        assert_allclose(got, golden)


def test_ag_gemm_loopback(rng):
    """Self-loopback overlap kernel (staging + per-segment DMA waits +
    segment grid on one device) computes a plain matmul."""
    from triton_distributed_tpu.kernels.allgather_gemm import ag_gemm_loopback

    M, K, N = 64, 32, 128
    a, b = _ab(rng, M, K, N)
    got = jax.jit(lambda a, b: ag_gemm_loopback(
        a, b, segments=8, config=AGGEMMConfig(block_n=128)))(a, b)
    assert_allclose(got, np.asarray(a) @ np.asarray(b))


def test_ag_gemm_segmented_bare(rng):
    """The decomposition arm (loopback grid without staging) is a plain
    matmul."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        ag_gemm_segmented_bare,
    )

    M, K, N = 64, 32, 128
    a, b = _ab(rng, M, K, N)
    got = jax.jit(lambda a, b: ag_gemm_segmented_bare(
        a, b, segments=8, config=AGGEMMConfig(block_n=128)))(a, b)
    assert_allclose(got, np.asarray(a) @ np.asarray(b))


def test_ag_gemm_loopback_split_tail(rng):
    """The round-5 overlap/tail split: overlap_cols < n routes the tail
    columns through ``matmul_tail_into`` (pass-through assembly over the
    STAGED gathered A — the staging buffer doubles as the gathered
    operand)."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        ag_gemm_loopback,
        ag_gemm_segmented_bare,
    )

    M, K, N = 64, 32, 384
    a, b = _ab(rng, M, K, N)
    cfg = AGGEMMConfig(block_n=128, overlap_cols=128)
    golden = np.asarray(a) @ np.asarray(b)
    got = jax.jit(lambda a, b: ag_gemm_loopback(
        a, b, segments=8, config=cfg))(a, b)
    assert_allclose(got, golden)
    got = jax.jit(lambda a, b: ag_gemm_segmented_bare(
        a, b, segments=8, config=cfg))(a, b)
    assert_allclose(got, golden)


def test_ag_gemm_device_split_tail(mesh8, rng):
    """Device-path split: the overlap kernel computes only overlap_cols
    columns, the tail rides the gathered-A staging output."""
    M, K, N = 8 * WORLD, 32, 256 * WORLD
    a, b = _ab(rng, M, K, N)
    out = ag_gemm(a, b, mesh=mesh8,
                  config=AGGEMMConfig(block_n=128, overlap_cols=128))
    golden = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(out, golden)


def test_matmul_tail_into(rng):
    """The split's assembly kernel: c rides through to columns
    [0, col_start), b[:, col_start:] is computed via the offset index map
    (no slice materialization), one full-width output."""
    from triton_distributed_tpu.kernels.allgather_gemm import matmul_tail_into

    M, K, N = 64, 128, 384
    a, b = _ab(rng, M, K, N)
    c = jnp.asarray(rng.standard_normal((M, 128), dtype=np.float32))
    got = jax.jit(lambda c, a, b: matmul_tail_into(c, a, b, 128,
                                                   block_n=128))(c, a, b)
    golden = np.asarray(a) @ np.asarray(b)
    assert_allclose(got[:, 128:], golden[:, 128:])
    assert_allclose(got[:, :128], np.asarray(c))


# -- the layer-stacked weight operand (a model's lax.scan body) -------------


def _at_traced_layer(call, layer):
    """``call(li)`` with ``li`` a TRACED () int32 equal to ``layer``: the
    one step of a ``lax.scan`` over ``[layer]``, so that the index a
    stacked kernel reads is dynamic, as it is in a model's layer scan."""
    return jax.lax.scan(lambda c, li: (c, call(li)), 0,
                        jnp.array([layer], jnp.int32))[1][0]


_STACKED_DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
_STACKED_LAYERS = pytest.mark.parametrize(
    "layer", [0, 1, 2], ids=["first", "middle", "last"])
# How the overlap kernel gets its weight tiles: RESIDENT (each copied once
# by the kernel, where the call's tiles fit its VMEM: every shape here) or
# through the pipeline's BlockSpec (what a weight too large to hold takes).
_STACKED_FETCH = pytest.mark.parametrize(
    "layer,fetch", [(0, "resident"), (1, "resident"), (2, "resident"),
                    (1, "pipeline")],
    ids=["first", "middle", "last", "middle-pipeline"])


def _fetch(monkeypatch, fetch):
    if fetch == "pipeline":
        from triton_distributed_tpu.kernels import common

        monkeypatch.setattr(common, "RESIDENT_WEIGHT_VMEM_CAP", 0)


@_STACKED_DTYPES
@_STACKED_FETCH
@pytest.mark.parametrize("overlap_cols", [None, 128],
                         ids=["whole", "split_tail"])
def test_ag_gemm_device_stacked_is_the_matrix_form(mesh8, rng, monkeypatch,
                                                   overlap_cols, layer, fetch,
                                                   dtype):
    """``ag_gemm_device`` over the stack (3, K, n_local) at a traced layer
    is BITWISE the 2-D form on ``b[layer]``, with and without the split
    tail (the stack per device is 12 KB in float32: the interpreter's
    ceiling)."""
    _fetch(monkeypatch, fetch)
    K, n_local = (8, 128) if overlap_cols is None else (4, 256)
    a = _rand(rng, (8 * WORLD, K), dtype)
    stack = _rand(rng, (3, K, n_local * WORLD), dtype)
    cfg = AGGEMMConfig(block_n=128, overlap_cols=overlap_cols)

    def stacked(al, bl):
        return _at_traced_layer(lambda li: ag_gemm_device(
            al, bl, axis="tp", config=cfg, layer=li), layer)

    def matrix(al, bl):
        return ag_gemm_device(al, bl[layer], axis="tp", config=cfg)

    got, want = (jax.jit(shard_map(
        f, mesh=mesh8, in_specs=(P("tp", None), P(None, None, "tp")),
        out_specs=P(None, "tp"), check_vma=False))(a, stack)
        for f in (stacked, matrix))
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert_allclose(
        got, np.asarray(a, np.float32) @ np.asarray(stack[layer], np.float32),
        atol=0.5, rtol=0.05)


@_STACKED_DTYPES
@_STACKED_LAYERS
def test_matmul_tail_into_stacked_is_the_matrix_form(rng, layer, dtype):
    """``matmul_tail_into`` reads its tail's tiles out of the stack at a
    traced layer: BITWISE the 2-D form on ``b[layer]``, pass-through
    columns included."""
    from triton_distributed_tpu.kernels.allgather_gemm import matmul_tail_into

    M, K, N = 64, 128, 384
    a, stack, c = (_rand(rng, shape, dtype)
                   for shape in ((M, K), (3, K, N), (M, 128)))
    got = jax.jit(lambda c, a, b: _at_traced_layer(
        lambda li: matmul_tail_into(c, a, b, 128, block_n=128, layer=li),
        layer))(c, a, stack)
    want = jax.jit(lambda c, a, b: matmul_tail_into(
        c, a, b, 128, block_n=128))(c, a, stack[layer])
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(got[:, :128], np.float32),
                                  np.asarray(c, np.float32))


@pytest.mark.parametrize("kernel", ["ag_gemm_device", "matmul_tail_into"])
def test_stacked_weight_that_disagrees_raises_as_the_matrix_does(rng, kernel):
    """A stack whose (K, N) disagrees with ``a`` raises what the 2-D form
    raises, and a stack without its layer (or a layer without a stack)
    is refused."""
    from jax.sharding import Mesh

    from triton_distributed_tpu.kernels.allgather_gemm import matmul_tail_into

    a, b = _ab(rng, 16, 128, 256)
    c = jnp.zeros((16, 128), jnp.float32)
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("tp",))

    def call(b, **kw):
        if kernel == "matmul_tail_into":
            return matmul_tail_into(c, a, b, 128, block_n=128, **kw)
        return jax.jit(shard_map(
            lambda al, bl: ag_gemm_device(al, bl, axis="tp", **kw),
            mesh=mesh1, in_specs=(P(), P()), out_specs=P(),
            check_vma=False))(a, b)

    stack = jnp.stack([b] * 3)
    for bad, kw in ((b[:64], {}), (stack[:, :64], {"layer": 1})):
        with pytest.raises(ValueError, match="K mismatch"):
            call(bad, **kw)
    for bad, kw in ((stack, {}), (b, {"layer": 1})):
        with pytest.raises(ValueError, match="layer must be passed"):
            call(bad, **kw)
    if kernel == "matmul_tail_into":  # N: the tail is not whole tiles
        for bad, kw in ((b[:, :192], {}), (stack[:, :, :192], {"layer": 1})):
            with pytest.raises(ValueError, match="not multiples"):
                call(bad, **kw)
