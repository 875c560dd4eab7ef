"""GEMM-RS tests — analog of the reference's test_gemm_rs.py (golden:
matmul + reduce_scatter), 8-way on the virtual CPU mesh (small shapes per
the conftest interpreter ceiling)."""

import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
    GEMMRSConfig,
    gemm_rs,
)
from triton_distributed_tpu.runtime import assert_allclose
from triton_distributed_tpu.runtime.compat import shard_map

WORLD = 8


def _ab(rng, M, K, N, dtype=jnp.float32):
    a = jnp.asarray(rng.standard_normal((M, K), dtype=np.float32), dtype)
    b = jnp.asarray(rng.standard_normal((K, N), dtype=np.float32), dtype)
    return a, b


def test_gemm_rs_vs_golden(mesh8, rng):
    M, K, N = 4 * WORLD, 16 * WORLD, 128
    a, b = _ab(rng, M, K, N)
    out = gemm_rs(a, b, mesh=mesh8, config=GEMMRSConfig(block_n=128))
    golden = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(out, golden, atol=1e-4, rtol=1e-4)


def test_gemm_rs_multi_tile(mesh8, rng):
    M, K, N = 2 * WORLD, 8 * WORLD, 256
    a, b = _ab(rng, M, K, N)
    out = gemm_rs(a, b, mesh=mesh8, config=GEMMRSConfig(block_n=128))
    golden = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(out, golden, atol=1e-4, rtol=1e-4)


def test_gemm_rs_bf16(mesh8, rng):
    M, K, N = 2 * WORLD, 8 * WORLD, 128
    a, b = _ab(rng, M, K, N, jnp.bfloat16)
    out = gemm_rs(a, b, mesh=mesh8, config=GEMMRSConfig(block_n=128))
    assert out.dtype == jnp.bfloat16
    golden = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(out, golden, atol=1.0, rtol=0.1)


def test_gemm_rs_2d_vs_golden(rng):
    """Inter-slice GEMM-RS on a (dcn=2, ici=4) mesh: intra-slice partials
    pushed-as-computed inside the Pallas kernel, inter-slice reduction via
    the slice-level ring (add-and-forward ppermute) — vs the dense golden
    (the reference's 2D reduce-scatter, reduce_scatter.py:45,:605)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        gemm_rs_2d_device,
    )
    from triton_distributed_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"dcn": 2, "ici": 4}, set_default=False)
    M, K, N = 32, 16 * 8, 128   # K dcn-major over the full world; M % 8 == 0
    a, b = _ab(rng, M, K, N)

    def f(al, bl):
        return gemm_rs_2d_device(al, bl, ici_axis="ici", dcn_axis="dcn",
                                 config=GEMMRSConfig(block_n=128))

    out = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(None, ("dcn", "ici")), P(("dcn", "ici"), None)),
        out_specs=P(("dcn", "ici"), None),
        check_vma=False,
    ))(a, b)
    golden = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(out, golden, atol=1e-4, rtol=1e-4)


def test_gemm_rs_bad_m_raises(mesh8, rng):
    a, b = _ab(rng, 12, 8 * WORLD, 128)  # M=12 not divisible by 8
    with pytest.raises(Exception):
        gemm_rs(a, b, mesh=mesh8, config=GEMMRSConfig(block_n=128))


def test_gemm_rs_loopback(rng):
    """Self-loopback overlap kernel (per-tile parity pushes + staging fold
    on one device) computes (sum of A row blocks) @ B."""
    import jax

    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        gemm_rs_loopback,
    )

    M, K, N = 64, 32, 256
    a, b = _ab(rng, M, K, N)
    got = jax.jit(lambda a, b: gemm_rs_loopback(
        a, b, segments=8, config=GEMMRSConfig(block_n=128)))(a, b)
    golden = (np.asarray(a, np.float32).reshape(8, 8, K).sum(0)
              @ np.asarray(b, np.float32))
    assert_allclose(got, golden, atol=1e-4, rtol=1e-4)


def test_gemm_rs_loopback_single_tile(rng):
    """n_tiles == 1 exercises the drain-only path (no t>=2 reclaims)."""
    import jax

    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        gemm_rs_loopback,
    )

    M, K, N = 16, 32, 128
    a, b = _ab(rng, M, K, N)
    got = jax.jit(lambda a, b: gemm_rs_loopback(
        a, b, segments=2, config=GEMMRSConfig(block_n=128)))(a, b)
    golden = (np.asarray(a, np.float32).reshape(2, 8, K).sum(0)
              @ np.asarray(b, np.float32))
    assert_allclose(got, golden, atol=1e-4, rtol=1e-4)


# -- the layer-stacked weight operand (a model's lax.scan body) -------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "layer,fetch", [(0, "resident"), (1, "resident"), (2, "resident"),
                    (1, "pipeline")],
    ids=["first", "middle", "last", "middle-pipeline"])
def test_gemm_rs_device_stacked_is_the_matrix_form(mesh8, rng, monkeypatch,
                                                   layer, fetch, dtype):
    """``gemm_rs_device`` over the stack (3, k_local, N) at a TRACED layer
    (the one step of a ``lax.scan`` over ``[layer]``, as in a model's layer
    scan) is BITWISE the 2-D form on ``b[layer]``; two column tiles, the
    stack per device 12 KB in float32 (the interpreter's ceiling). The
    weight's tiles RESIDENT (each copied once by the kernel, where B whole
    fits its VMEM: every shape here) or through the pipeline's BlockSpec
    (what a weight too large to hold takes)."""
    import jax

    from triton_distributed_tpu.kernels import common

    if fetch == "pipeline":
        monkeypatch.setattr(common, "RESIDENT_WEIGHT_VMEM_CAP", 0)
    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        gemm_rs_device,
    )

    M, K, N = 2 * WORLD, 4 * WORLD, 256
    a, stack = (jnp.asarray(rng.standard_normal(shape, dtype=np.float32),
                            dtype) for shape in ((M, K), (3, K, N)))
    cfg = GEMMRSConfig(block_n=128)

    def stacked(al, bl):
        return jax.lax.scan(
            lambda c, li: (c, gemm_rs_device(al, bl, axis="tp", config=cfg,
                                             layer=li)),
            0, jnp.array([layer], jnp.int32))[1][0]

    def matrix(al, bl):
        return gemm_rs_device(al, bl[layer], axis="tp", config=cfg)

    got, want = (jax.jit(shard_map(
        f, mesh=mesh8, in_specs=(P(None, "tp"), P(None, "tp", None)),
        out_specs=P("tp", None), check_vma=False))(a, stack)
        for f in (stacked, matrix))
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert_allclose(
        got, np.asarray(a, np.float32) @ np.asarray(stack[layer], np.float32),
        atol=1.0, rtol=0.1)


def test_gemm_rs_stacked_weight_that_disagrees_raises_as_the_matrix_does(rng):
    """A stack whose K disagrees with ``a`` raises what the 2-D form
    raises (both at trace time, before any kernel is built), and a stack
    without its layer (or a layer without a stack) is refused."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        gemm_rs_device,
    )

    a, b = _ab(rng, 16, 128, 128)
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("tp",))

    def call(b, **kw):
        return jax.jit(shard_map(
            lambda al, bl: gemm_rs_device(al, bl, axis="tp", **kw),
            mesh=mesh1, in_specs=(P(), P()), out_specs=P(),
            check_vma=False))(a, b)

    stack = jnp.stack([b] * 3)
    for bad, kw in ((b[:64], {}), (stack[:, :64], {"layer": 1})):
        with pytest.raises(ValueError, match="K mismatch"):
            call(bad, **kw)
    for bad, kw in ((stack, {}), (b, {"layer": 1})):
        with pytest.raises(ValueError, match="layer must be passed"):
            call(bad, **kw)
