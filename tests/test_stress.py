"""Stress / straggler / race-detection harness.

Analog of the reference's stress suite
(``test/stress/stress_test_ag_gemm.py``:78 — randomized-M loop with
straggler injection via ``sleep_async`` utils.py:1010 / ``_run_straggler``
allreduce.py:146) and of running under ``compute-sanitizer``
(scripts/launch.sh:169). The overlap kernels' whole point is tolerating
inter-device skew: every test injects rank-proportional compute delays
(``runtime.utils.straggler_delay``) ahead of the kernel and checks results
against the dense golden over randomized shapes; the race-detect pass runs
the collective set under ``InterpretParams(detect_races=True)`` — the
interpreter's vector-clock data-race detector (runtime/platform.py).

Shapes honor the conftest interpreter per-buffer ceiling (<=12KB).
"""

import jax
from triton_distributed_tpu.runtime.compat import axis_size as _axis_size
from triton_distributed_tpu.runtime.compat import shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.runtime.utils import straggler_delay

WORLD = 8
# Rank-proportional skew: rank r runs r * SKEW_STEPS dummy matmul rounds
# before entering the kernel (rank 7 enters far behind rank 0).
SKEW_STEPS = 40


def _skew(x_local, axis="tp", scale=SKEW_STEPS):
    me = jax.lax.axis_index(axis)
    return straggler_delay(x_local, me * scale)


def _run8(f, mesh, in_specs, out_specs, *args):
    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))(*args)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_stress_ag_gemm_random_shapes_with_stragglers(mesh8, seed):
    """Randomized (m, K, n_local) AG-GEMM with rank-proportional skew on the
    A shard: the consumer must wait out the slow ranks' segments and still
    match the dense golden (reference stress_test_ag_gemm.py:78)."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AGGEMMConfig,
        ag_gemm_device,
    )

    rng = np.random.default_rng(seed)
    for _ in range(3):
        m = int(rng.choice([8, 16]))
        K = int(rng.choice([16, 32]))
        n_local = 128
        a = jnp.asarray(rng.standard_normal((WORLD * m, K)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((K, WORLD * n_local)),
                        jnp.float32)

        def f(al, bl):
            al = _skew(al)
            return ag_gemm_device(al, bl, axis="tp",
                                  config=AGGEMMConfig(block_n=128))

        out = _run8(f, mesh8, (P("tp", None), P(None, "tp")),
                    P(None, "tp"), a, b)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(a) @ np.asarray(b),
            rtol=1e-4, atol=1e-4)


# Rows a device each walk of GEMM-RS draws. The one pass holds two send
# slots A PEER ((world - 1) x 2 x m x 128 floats: 57 KB at 8 rows; at 16
# the interpreter hangs, see the ceiling in conftest.py).
GEMM_RS_ROWS = {"two_axis": [8, 16], "one_pass": [4, 8]}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("walk", sorted(GEMM_RS_ROWS))
def test_stress_gemm_rs_random_shapes_with_stragglers(mesh8, monkeypatch,
                                                      walk, seed):
    """Randomized (M, k_local) GEMM-RS under rank-proportional skew, each of
    its two walks: the grid ``(destination, column tile)``, reached as
    ``test_gemm_rs._take_walk`` reaches it (every shape the interpreter
    holds fits the one pass), and the one pass over the column tiles."""
    from test_gemm_rs import METHOD, _methods, _take_walk
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMRSConfig,
        gemm_rs_device,
    )

    rng = np.random.default_rng(seed)
    for _ in range(3):
        M = WORLD * int(rng.choice(GEMM_RS_ROWS[walk]))
        k_local = int(rng.choice([8, 16]))
        n = 128
        a = jnp.asarray(rng.standard_normal((M, WORLD * k_local)),
                        jnp.float32)
        b = jnp.asarray(rng.standard_normal((WORLD * k_local, n)),
                        jnp.float32)

        def f(al, bl):
            al = _skew(al)
            return gemm_rs_device(al, bl, axis="tp",
                                  config=GEMMRSConfig(block_n=128))

        with monkeypatch.context() as mp:
            _take_walk(mp, walk, M, WORLD * k_local, n, a.dtype)
            out, methods = _methods(lambda: _run8(
                f, mesh8, (P(None, "tp"), P("tp", None)), P("tp", None),
                a, b))
        assert methods == [METHOD[walk]]
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(a) @ np.asarray(b),
            rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("seed", [0, 1])
def test_stress_a2a_random_counts_with_stragglers(mesh8, seed):
    """Randomized occupancy EP a2a under skew: chunked predicated sends must
    pair with the receiver's predicated waits regardless of entry order."""
    from triton_distributed_tpu.kernels.ep_all_to_all import (
        AllToAllContext,
        fast_all_to_all,
    )

    rng = np.random.default_rng(seed)
    cap, hidden = 16, 16
    ctx = AllToAllContext(capacity=cap, hidden=hidden, axis="tp")
    for _ in range(3):
        toks = jnp.asarray(
            rng.standard_normal((WORLD, WORLD, cap, hidden)), jnp.float32)
        counts = jnp.asarray(rng.integers(0, cap + 1, (WORLD, WORLD)),
                             jnp.int32)

        def f(t, c):
            t0 = _skew(t[0])
            out, cnts = fast_all_to_all(t0, c[0], ctx=ctx)
            return out[None], cnts[None]

        out, rcounts = _run8(f, mesh8, (P("tp"), P("tp")),
                             (P("tp"), P("tp")), toks, counts)
        out, rcounts = np.asarray(out), np.asarray(rcounts)
        expected = np.transpose(np.asarray(toks), (1, 0, 2, 3))
        np.testing.assert_array_equal(rcounts, np.asarray(counts).T)
        for r in range(WORLD):
            for p in range(WORLD):
                n_valid = rcounts[r, p]
                np.testing.assert_allclose(
                    out[r, p, :n_valid], expected[r, p, :n_valid],
                    rtol=1e-6)


def test_stress_ll_allgather_epochs_with_stragglers(mesh8):
    """Successive LL-allgather epochs under rank-proportional skew: the
    epoch-parity-indexed recv semaphores must keep adjacent epochs' pushes
    from satisfying each other's waits (the r2 advisor's high finding)."""
    from triton_distributed_tpu.kernels.ll_allgather import (
        ll_all_gather_device,
        make_ll_staging,
    )
    from triton_distributed_tpu.runtime.symm import clear_workspaces

    m, feat = 4, 16
    clear_workspaces()
    ws = make_ll_staging((m, feat), jnp.float32, mesh=mesh8, name="t_stress")

    def f(xs, stg, ep):
        x = _skew(xs[0], scale=25)
        out, stg = ll_all_gather_device(x, stg[0], ep, axis="tp")
        return out, stg[None]

    run = jax.jit(shard_map(
        f, mesh=mesh8,
        in_specs=(P("tp"), P("tp"), P()),
        out_specs=(P(), P("tp")),
        check_vma=False), donate_argnums=(1,))

    rng = np.random.default_rng(0)
    stg = ws.array
    for epoch in range(5):
        x = jnp.asarray(rng.standard_normal((WORLD, m, feat)), jnp.float32)
        out, stg = run(x, stg, jnp.asarray(epoch, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(x).reshape(WORLD * m, feat),
            rtol=1e-6)


def test_stress_2d_overlap_ops_with_stragglers():
    """The inter-slice (DCN ring) variants under rank-proportional skew on a
    (dcn=2, ici=4) mesh: the intra-slice kernels must wait out slow ranks at
    every ring step and both ops must match the dense goldens."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AGGEMMConfig,
        ag_gemm_2d_device,
    )
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMRSConfig,
        gemm_rs_2d_device,
    )
    from triton_distributed_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"dcn": 2, "ici": 4}, set_default=False)
    rng = np.random.default_rng(0)

    def skew2d(x):
        g = (jax.lax.axis_index("dcn") * _axis_size("ici")
             + jax.lax.axis_index("ici"))
        return straggler_delay(x, g * SKEW_STEPS)

    # AG-GEMM 2D: skew on the A shard.
    M, K, N = 8 * 4, 16, 8 * 128
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)

    def f_ag(al, bl):
        return ag_gemm_2d_device(skew2d(al), bl, ici_axis="ici",
                                 dcn_axis="dcn",
                                 config=AGGEMMConfig(block_n=128))

    out = jax.jit(shard_map(
        f_ag, mesh=mesh,
        in_specs=(P(("dcn", "ici"), None), P(None, ("dcn", "ici"))),
        out_specs=P(None, ("dcn", "ici")), check_vma=False))(a, b)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(a) @ np.asarray(b),
                               atol=1e-3, rtol=1e-3)

    # GEMM-RS 2D: skew on the K-shard operands.
    M2, K2, N2 = 32, 16 * 8, 128
    a2 = jnp.asarray(rng.standard_normal((M2, K2)), jnp.float32)
    b2 = jnp.asarray(rng.standard_normal((K2, N2)), jnp.float32)

    def f_rs(al, bl):
        return gemm_rs_2d_device(skew2d(al), bl, ici_axis="ici",
                                 dcn_axis="dcn",
                                 config=GEMMRSConfig(block_n=128))

    out2 = jax.jit(shard_map(
        f_rs, mesh=mesh,
        in_specs=(P(None, ("dcn", "ici")), P(("dcn", "ici"), None)),
        out_specs=P(("dcn", "ici"), None), check_vma=False))(a2, b2)
    np.testing.assert_allclose(np.asarray(out2),
                               np.asarray(a2) @ np.asarray(b2),
                               atol=1e-3, rtol=1e-3)


def test_collectives_race_detect(mesh8, capfd):
    """One pass of the collective set under the interpreter's vector-clock
    race detector (InterpretParams(detect_races=True)) — the
    compute-sanitizer analog. The detector PRINTS "RACE DETECTED" (it does
    not raise), so the assertion is on captured output."""
    from jax.experimental.pallas import tpu as pltpu

    from triton_distributed_tpu.kernels.allgather import (
        a2a_all_gather,
        ring_all_gather,
    )
    from triton_distributed_tpu.kernels.allreduce import oneshot_all_reduce
    from triton_distributed_tpu.kernels.reduce_scatter import (
        oneshot_reduce_scatter,
    )

    params = pltpu.InterpretParams(detect_races=True)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((WORLD, 8, 16)), jnp.float32)
    xr = jnp.asarray(rng.standard_normal((WORLD, WORLD * 8, 16)),
                     jnp.float32)

    for name, f, arg, out_spec in [
        ("ring_ag", lambda v: ring_all_gather(v[0], axis="tp",
                                              interpret=params), x, P()),
        ("a2a_ag", lambda v: a2a_all_gather(v[0], axis="tp",
                                            interpret=params), x, P()),
        ("oneshot_ar", lambda v: oneshot_all_reduce(v[0], axis="tp",
                                                    interpret=params), x,
         P()),
        ("oneshot_rs", lambda v: oneshot_reduce_scatter(
            v[0], axis="tp", interpret=params)[None], xr, P("tp")),
    ]:
        out = _run8(f, mesh8, P("tp"), out_spec, arg)
        assert np.isfinite(np.asarray(out)).all(), name
    captured = capfd.readouterr()
    assert "RACE DETECTED" not in captured.out + captured.err, (
        captured.out + captured.err)
