"""Tutorial 08 — GEMM-RS: overlapping GEMM with ReduceScatter.

What you learn (TPU edition of the reference's tutorial 08 — the other half
of the TP pair):

* The problem: row-parallel TP matmul (A sharded on K, B sharded on K)
  produces full-(M, N) partials that must be summed across devices and
  scattered by M. Matmul-then-reduce-scatter serializes; the reference
  overlaps by having the producer GEMM ``notify`` per-tile barriers while
  an RS consumer on a second stream scatters tiles as they complete.
* The TPU redesign (one Pallas kernel, one algorithm, two loop nests
  chosen from the operands' shapes). With few rows (the shapes here, and a
  served model's decode and mixed steps: A whole and the own block fit
  VMEM) the kernel walks the column tiles ONCE: a weight tile is copied
  once, meets the MXU once for every destination's rows, and each peer's
  rows of the result are pushed over ICI to their owner in pieces of a few
  tiles (async DMA from VMEM slots double-buffered per peer) while later
  tiles are multiplied. At prefill-sized M the grid walks ``(destination,
  column tile)`` in swizzled order ``dst = (me + 1 + s) % world``, REMOTE
  segments first, a tile pushed the moment its product leaves the MXU.
  Either way the own rows come last, folding arrivals in a FIXED global
  rank order (bitwise rank-independent sums).
* All world-1 pushes are in flight while the MXU computes later tiles —
  same hiding argument as AG-GEMM, mirrored.
* Across slices: ``gemm_rs_2d_device`` runs a ring reduce-scatter over the
  DCN axis at slice-block granularity (add-and-forward ppermute), with the
  intra-slice kernel doing the heavy lifting per hop.

Run:  python tutorials/08-overlapping-gemm-reduce-scatter.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bootstrap import force_virtual_mesh  # noqa: E402

force_virtual_mesh(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from triton_distributed_tpu.kernels import GEMMRSConfig, gemm_rs  # noqa: E402
from triton_distributed_tpu.kernels.gemm_reduce_scatter import (  # noqa: E402
    gemm_rs_2d_device,
)
from triton_distributed_tpu.runtime.mesh import make_mesh  # noqa: E402

WORLD = 8


def main():
    mesh = make_mesh({"tp": WORLD})
    rng = np.random.default_rng(0)

    M, K, N = 4 * WORLD, 16 * WORLD, 128
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)  # sharded on K
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)  # sharded on K
    golden = np.asarray(a) @ np.asarray(b)

    out = gemm_rs(a, b, mesh=mesh, config=GEMMRSConfig(block_n=128))
    np.testing.assert_allclose(np.asarray(out), golden, atol=1e-3, rtol=1e-3)
    print("  gemm_rs ok (push-as-computed, fixed-order reduction)")

    mesh2d = make_mesh({"dcn": 2, "ici": 4}, set_default=False)

    def f2d(al, bl):
        return gemm_rs_2d_device(al, bl, ici_axis="ici", dcn_axis="dcn",
                                 config=GEMMRSConfig(block_n=128))

    out2d = jax.jit(jax.shard_map(
        f2d, mesh=mesh2d,
        in_specs=(P(None, ("dcn", "ici")), P(("dcn", "ici"), None)),
        out_specs=P(("dcn", "ici"), None), check_vma=False))(a, b)
    np.testing.assert_allclose(np.asarray(out2d), golden, atol=1e-3,
                               rtol=1e-3)
    print("  gemm_rs_2d ok (DCN ring reduce-scatter around the kernel)")
    print("tutorial 08 ok: GEMM-RS overlap op + 2D variant")


if __name__ == "__main__":
    main()
