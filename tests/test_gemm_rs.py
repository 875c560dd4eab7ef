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


def _fresh_gemm_rs(a, b, mesh):
    """``gemm_rs`` traced anew: its jitted builder is cached by ``(mesh,
    axis, config, interpret)`` and the walk is chosen when it traces."""
    from triton_distributed_tpu.kernels import gemm_reduce_scatter as gr

    gr._build_gemm_rs.cache_clear()
    return gemm_rs(a, b, mesh=mesh, config=GEMMRSConfig(block_n=128))


def _ab(rng, M, K, N, dtype=jnp.float32):
    a = jnp.asarray(rng.standard_normal((M, K), dtype=np.float32), dtype)
    b = jnp.asarray(rng.standard_normal((K, N), dtype=np.float32), dtype)
    return a, b


WALKS = ["one_pass", "two_axis"]


def _take_walk(monkeypatch, walk, M, K, N, dtype, *, world=WORLD, bn=128):
    """``gemm_rs_device`` chooses its walk from its operands' shapes alone;
    every shape the interpreter can hold fits the ONE pass, so the grid
    ``(destination, column tile)`` is reached by lowering the VMEM a kernel
    may ask for to one byte under what the one pass of this shape needs
    (B's tiles stay resident: the two-axis walk needs less)."""
    from triton_distributed_tpu.kernels import common
    from triton_distributed_tpu.kernels import gemm_reduce_scatter as gr

    if walk == "one_pass":
        return
    m, isz = M // world, jnp.dtype(dtype).itemsize
    need = gr._one_pass_vmem(world, m, K // world, N, bn,
                             gr._tiles_a_piece(m, bn, isz, N // bn), isz, isz)
    monkeypatch.setattr(common, "RESIDENT_WEIGHT_VMEM_CAP", need - 1)


def _methods(fn):
    """The comm ledger's ``method`` of every ``gemm_rs`` record traced
    while ``fn`` runs: the name of the walk each call took."""
    from triton_distributed_tpu.obs import comm_ledger

    with comm_ledger.gathering() as records:
        out = fn()
    return out, [r.method for r in records if r.collective == "gemm_rs"]


METHOD = {"one_pass": "device_one_pass", "two_axis": "device"}


def _golden(a, b):
    return np.asarray(a, np.float32) @ np.asarray(b, np.float32)


def _check_walk(mesh, rng, monkeypatch, walk, M, K, N, dtype=jnp.float32,
                **tol):
    """``gemm_rs`` on seeded operands under ``walk``: the ledger names it
    and the result is the dense golden's."""
    a, b = _ab(rng, M, K, N, dtype)
    _take_walk(monkeypatch, walk, M, K, N, dtype)
    out, methods = _methods(lambda: _fresh_gemm_rs(a, b, mesh))
    assert methods == [METHOD[walk]]
    assert out.dtype == dtype
    assert_allclose(out, _golden(a, b), **(tol or dict(atol=1e-4, rtol=1e-4)))


@pytest.mark.parametrize("walk", WALKS)
def test_gemm_rs_vs_golden(mesh8, rng, monkeypatch, walk):
    _check_walk(mesh8, rng, monkeypatch, walk, 4 * WORLD, 16 * WORLD, 128)


@pytest.mark.parametrize("walk", WALKS)
def test_gemm_rs_multi_tile(mesh8, rng, monkeypatch, walk):
    _check_walk(mesh8, rng, monkeypatch, walk, 2 * WORLD, 8 * WORLD, 256)


@pytest.mark.parametrize("walk", WALKS)
def test_gemm_rs_bf16(mesh8, rng, monkeypatch, walk):
    _check_walk(mesh8, rng, monkeypatch, walk, 2 * WORLD, 8 * WORLD, 128,
                jnp.bfloat16, atol=1.0, rtol=0.1)


@pytest.mark.parametrize("piece,steps", [(1, 3), (32 * 2 ** 10, 1)],
                         ids=["a-push-a-tile", "one-piece"])
def test_gemm_rs_one_pass_pieces(rng, monkeypatch, piece, steps):
    """The one pass groups a peer's column tiles into pieces of at most
    ``PUSH_PIECE_BYTES``: a push a tile over three steps (the third
    reclaims the first's send slot), or all of a destination's columns in
    one piece; on a mesh of four, three column tiles."""
    import jax
    from jax.sharding import Mesh

    from triton_distributed_tpu.kernels import gemm_reduce_scatter as gr

    monkeypatch.setattr(gr, "PUSH_PIECE_BYTES", piece)
    M, K, N = 8, 32, 384
    assert N // 128 // gr._tiles_a_piece(M // 4, 128, 4, N // 128) == steps
    a, b = _ab(rng, M, K, N)
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("tp",))
    out, methods = _methods(lambda: _fresh_gemm_rs(a, b, mesh4))
    assert methods == ["device_one_pass"]
    assert_allclose(out, _golden(a, b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gemm_rs_walks_agree(mesh8, rng, monkeypatch, dtype):
    """The two walks on the same operands: the same partial products pushed
    in the same dtype and folded in the same fixed rank order, so they
    agree to the golden's tolerance (a 64-row product may round as a
    16-row one does, or not: bitwise is not promised)."""
    M, K, N = 2 * WORLD, 8 * WORLD, 256
    a, b = _ab(rng, M, K, N, dtype)
    got = {}
    for walk in WALKS:
        with monkeypatch.context() as mp:
            _take_walk(mp, walk, M, K, N, dtype)
            got[walk], methods = _methods(
                lambda: _fresh_gemm_rs(a, b, mesh8))
        assert methods == [METHOD[walk]]
    tol = dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 \
        else dict(atol=1.0, rtol=0.1)
    assert_allclose(got["one_pass"], np.asarray(got["two_axis"], np.float32),
                    **tol)
    assert_allclose(got["one_pass"], _golden(a, b), **tol)


def test_gemm_rs_ledger_counts_the_calls_of_each_walk(mesh8, rng, monkeypatch):
    """The enabled ledger keeps a series a ``method``: a program whose
    scan runs the one pass three times, and one whose two calls keep the
    grid ``(destination, column tile)``, read apart in its snapshot with
    the same bytes a call (traced only: nothing runs)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        gemm_rs_device,
    )
    from triton_distributed_tpu.obs import comm_ledger

    M, K, N = 2 * WORLD, 8 * WORLD, 256
    a, b = _ab(rng, M, K, N)
    cfg = GEMMRSConfig(block_n=128)

    def once(al, bl):
        return gemm_rs_device(al, bl, axis="tp", config=cfg)

    def scanned(al, bl):
        with comm_ledger.repeated(3):
            return jax.lax.scan(lambda c, _: (c + once(al, bl), None),
                                jnp.zeros((M // WORLD, N), a.dtype), None,
                                length=3)[0]

    def trace(f):
        jax.jit(shard_map(f, mesh=mesh8, in_specs=(P(None, "tp"),
                                                   P("tp", None)),
                          out_specs=P("tp", None), check_vma=False)
                ).trace(a, b)

    with comm_ledger.ledger(reset_first=True):
        trace(scanned)
        with monkeypatch.context() as mp:
            _take_walk(mp, "two_axis", M, K, N, a.dtype)
            trace(lambda al, bl: once(al, bl) + once(al, bl))
        series = {e.method: e for e in comm_ledger.get_ledger().get("gemm_rs")}
    assert {m: e.traced_calls for m, e in series.items()} == {
        "device_one_pass": 3, "device": 2}
    assert series["device_one_pass"].bytes_total / 3 == \
        series["device"].bytes_total / 2 > 0


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
