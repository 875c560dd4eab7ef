"""Tier-1 enforcement of the comm-safety analyzer (``analysis/`` +
``tools/comm_check.py``): every registered kernel must trace clean at
world 2/4/8, every seeded mutant must be caught with the right hazard
class, the AST companion pass must flag the Python-visible mistakes, and
the shmem/dma_sems semantic contracts must hold.

Everything here runs the abstract interpreter on CPU — no TPU, no Pallas
interpreter, no 8-device mesh needed (conftest's mesh is harmless)."""

import textwrap

import numpy as np
import pytest

import jax

from triton_distributed_tpu.analysis import (ast_checks, checks, comm_graph,
                                             events, registry)
from triton_distributed_tpu.analysis.registry import Buf, Sem, TraceSpec
from triton_distributed_tpu.kernels import common
from triton_distributed_tpu.language import shmem

from tools import comm_check

WORLDS = (2, 4, 8)


# ---------------------------------------------------------------------------
# Tentpole: every registered kernel is clean; every mutant is caught.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("world", WORLDS)
def test_all_registered_kernels_clean(world):
    entries = registry.all_kernels()
    assert len(entries) >= 12, [e.name for e in entries]
    bad = {}
    for e in entries:
        if world not in e.worlds:
            continue
        vs = checks.check_kernel(e.name, world)
        if vs:
            bad[e.name] = [str(v) for v in vs]
    assert not bad, bad


MUTANT_EXPECT = {
    # dropped send drain: undrained send increments (balance leak) and the
    # DMA's send side never awaited.
    "mutant.ag_ring_drop_wait_send": {"sem-balance", "dma-completion"},
    # double notify with a world-1 wait: +world-1 stale signals per rank.
    "mutant.barrier_double_notify": {"sem-balance"},
    # consumer waits the wrong recv slot: the wait can never be fed.
    "mutant.ll_ag_recv_slot_off_by_one": {"deadlock"},
}


@pytest.mark.parametrize("name", sorted(MUTANT_EXPECT))
@pytest.mark.parametrize("world", (2, 4))
def test_mutants_are_caught(name, world):
    vs = checks.check_kernel(name, world)
    assert vs, f"{name} world={world}: analyzer found nothing"
    got = {v.check for v in vs}
    assert got & MUTANT_EXPECT[name], (
        f"{name} world={world}: expected one of {MUTANT_EXPECT[name]}, "
        f"got {got}: " + "; ".join(str(v) for v in vs))


def test_cli_sweep_is_clean(capsys):
    rc = comm_check.main(["--world", "2", "--world", "4", "--world", "8",
                          "--no-ast"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "all comm-safety checks clean" in out


@pytest.mark.parametrize("name", sorted(MUTANT_EXPECT))
def test_cli_flags_each_mutant(name, capsys):
    rc = comm_check.main(["--kernel", name, "--world", "2", "--no-ast"])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert "violation" in out.lower()


def test_cli_unknown_kernel_is_usage_error(capsys):
    assert comm_check.main(["--kernel", "no.such.kernel"]) == 2


def test_cli_list_names_hidden_mutants(capsys):
    assert comm_check.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "ag.ring" in out
    assert "mutant.ag_ring_drop_wait_send" in out and "[hidden]" in out


def test_ast_pass_clean_on_this_repo():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert ast_checks.check_tree(root) == []


# ---------------------------------------------------------------------------
# AST companion pass on synthetic sources.
# ---------------------------------------------------------------------------


def test_ast_flags_discarded_dma_without_any_wait():
    src = textwrap.dedent("""\
        def kernel(x_ref, o_ref, send, recv, axis, peer):
            common.remote_copy(x_ref, o_ref, send, recv, axis, peer)
            o_ref[...] = x_ref[...]
    """)
    fs = ast_checks.check_source(src, "k.py")
    assert [f.rule for f in fs] == ["discarded-dma"]
    assert fs[0].line == 2


def test_ast_allows_discarded_dma_when_function_drains():
    # The ag_gemm pattern: bare remote_copy in a nested closure, drained by
    # a re-derived wait_send in a sibling closure of the SAME function.
    src = textwrap.dedent("""\
        def kernel(x_ref, o_ref, send, recv, axis, peer):
            def _startup():
                common.remote_copy(x_ref, o_ref, send, recv, axis, peer)
            def _drain():
                common.wait_send(x_ref, send)
    """)
    assert ast_checks.check_source(src, "k.py") == []


def test_ast_allows_stashed_handles():
    src = textwrap.dedent("""\
        def kernel(x_ref, o_ref, send, recv, axis, peer):
            dma = shmem.putmem_nbi(x_ref, o_ref, peer, send, recv)
            return dma
    """)
    assert ast_checks.check_source(src, "k.py") == []


def test_ast_flags_python_rank_escapes():
    src = textwrap.dedent("""\
        def kernel(axis, world):
            for s in range(jax.lax.axis_index(axis)):
                pass
            if my_pe() == 0:
                pass
    """)
    fs = ast_checks.check_source(src, "k.py")
    assert {f.rule for f in fs} == {"python-rank"}
    assert len(fs) == 2  # the range() escape and the `if` test


def test_ast_reports_syntax_error_as_finding():
    fs = ast_checks.check_source("def broken(:\n", "bad.py")
    assert [f.rule for f in fs] == ["parse-error"]


# ---------------------------------------------------------------------------
# Satellite: dma_sems slot-count validation.
# ---------------------------------------------------------------------------


def test_dma_sems_accepts_int_and_tuple():
    assert common.dma_sems(3) is not None
    assert common.dma_sems((2, 4)) is not None


@pytest.mark.parametrize("bad", [0, -1, (0,), (2, 0)])
def test_dma_sems_rejects_non_positive_counts(bad):
    with pytest.raises(ValueError, match="world - 1"):
        common.dma_sems(bad)


def test_dma_sems_rejects_non_int_dims():
    with pytest.raises(ValueError, match="non-integer"):
        common.dma_sems((1.5,))
    with pytest.raises(ValueError, match="concrete Python ints"):
        common.dma_sems(("tp",))


# ---------------------------------------------------------------------------
# Satellite: shmem semantic contracts, checked through the tracer.
# ---------------------------------------------------------------------------


def _trace(body, world=2, extra_sems=()):
    spec = TraceSpec(
        body=body,
        args=[Buf("o", (8, 128)), Sem("sig"), *extra_sems],
        kwargs=dict(axis="tp", world=world),
    )
    trace = events.trace_kernel(spec, world)
    sim = comm_graph.simulate(trace.logs)
    return checks.check_trace(trace, sim, kernel="test", world=world)


def test_signal_wait_until_consumes_exactly_once():
    # 3 signals to the right neighbor, one wait of 3: balanced and clean.
    def body(o_ref, sig, *, axis, world):
        del o_ref
        peer = shmem.remote_rank(1, axis=axis)
        for _ in range(3):
            shmem.signal_op(sig, peer, axis=axis)
        shmem.signal_wait_until(sig, 3)

    assert _trace(body) == []


def test_signal_wait_until_decrements_so_rewait_deadlocks():
    # The NVSHMEM-ported mistake: waiting the same value twice assumes the
    # cell still reads 3 after the first wait. TPU waits consume — the
    # second wait can never be satisfied and the analyzer must call it.
    def body(o_ref, sig, *, axis, world):
        del o_ref
        peer = shmem.remote_rank(1, axis=axis)
        for _ in range(3):
            shmem.signal_op(sig, peer, axis=axis)
        shmem.signal_wait_until(sig, 3)
        shmem.signal_wait_until(sig, 3)  # BUG under consuming semantics

    vs = _trace(body)
    assert vs and {v.check for v in vs} == {"deadlock"}, [str(v) for v in vs]


def test_quiet_with_zero_handles_is_noop():
    assert shmem.quiet() is None

    # And inside a traced kernel it records nothing and stays clean.
    def body(o_ref, sig, *, axis, world):
        del sig
        shmem.quiet()
        o_ref[0, 0] = 1.0

    assert _trace(body) == []


def test_quiet_drains_given_handles():
    # Symmetric ring: each rank puts x into its neighbor's o, quiet()s the
    # send side, then awaits its own arrival. Balanced and race-free — any
    # missing drain would surface as dma-completion/sem-balance.
    def body(o_ref, sig, x_ref, ssem, rsem, *, axis, world):
        del sig
        peer = shmem.remote_rank(1, axis=axis)
        dma = shmem.putmem_nbi(x_ref, o_ref, peer, ssem, rsem, axis=axis)
        shmem.quiet(dma)
        dma.wait_recv()

    vs = _trace(body, extra_sems=(Buf("x", (8, 128)), Sem("ssem"),
                                  Sem("rsem")))
    assert vs == [], [str(v) for v in vs]


@pytest.mark.parametrize("early", [False, True], ids=["after-wait", "early"])
def test_copies_into_both_planes_of_a_slot_interleave_without_a_race(early):
    """A copy into rows of BOTH planes of a staging slot (the paged walk's
    one copy a block) is two runs of bytes with the other blocks' rows
    between them: the race check holds a strided copy to its runs, not to
    their bounding box, so a second block's copy in flight does not race a
    read of the first block's arrived rows, and a read of rows whose copy is
    still in flight is still caught."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def body(o_ref, sig, x_ref, stage, sems, *, axis, world):
        del sig
        copies = [pltpu.make_async_copy(
            x_ref.at[i], stage.at[:, pl.ds(4 * i, 4)], sems.at[i])
            for i in (0, 1)]
        for c in copies:
            c.start()
        if early:
            o_ref[0, 0] = stage[0, 4, 0]        # block 1's rows: in flight
        copies[0].wait()
        # block 0's rows of plane 1 lie INSIDE the box of block 1's copy
        # (plane 0 rows 4-7 .. plane 1 rows 4-7) and are none of its bytes
        o_ref[0, 1] = stage[1, 0, 0]
        copies[1].wait()

    vs = _trace(body, extra_sems=(Buf("x", (2, 2, 4, 128)),
                                  Buf("stage", (2, 8, 128), space="vmem"),
                                  Sem("sems", (2,))))
    assert {v.check for v in vs} == ({"buffer-race"} if early else set()), \
        [str(v) for v in vs]


# ---------------------------------------------------------------------------
# Tracer/registry plumbing.
# ---------------------------------------------------------------------------


def test_registry_rejects_duplicate_names():
    registry.get("ag.ring")  # force the lazy module load
    with pytest.raises(ValueError, match="duplicate"):
        registry.register("ag.ring")(lambda world: None)


def test_registry_get_unknown_lists_known():
    with pytest.raises(KeyError, match="ag.ring"):
        registry.get("definitely-not-registered")


def test_trace_error_is_a_violation_not_a_crash():
    name = "mutant.test_trace_error"
    if name not in registry._REGISTRY:
        @registry.register(name, hidden=True)
        def _build(world):
            def body(o_ref, *, world):
                o_ref[99, 0] = 1.0  # out of bounds

            return TraceSpec(body=body, args=[Buf("o", (8, 128))],
                             kwargs=dict(world=world))

    vs = checks.check_kernel(name, 2)
    assert [v.check for v in vs] == ["trace-error"], [str(v) for v in vs]
    assert "out of bounds" in vs[0].detail


def test_tracer_restores_patched_surface():
    # After a trace, the real jax/pallas symbols must be back.
    before = (jax.lax.axis_index, jax.lax.fori_loop)

    def body(o_ref, sig, *, axis, world):
        del sig
        o_ref[0, 0] = float(jax.lax.axis_index(axis))

    _trace(body)
    assert (jax.lax.axis_index, jax.lax.fori_loop) == before


def test_program_id_semantics_support_logical_not():
    # Regression: ``~(s == k)`` must be a logical not (np.bool_), not
    # Python's bitwise ~ on a bool (which is truthy for both values).
    recorded = []

    def body(o_ref, sig, *, axis, world):
        del sig
        import jax.experimental.pallas as pl
        s = pl.program_id(0)
        is_own = s == 1

        @pl.when(~is_own)
        def _not_own():
            recorded.append(int(s))

    spec = TraceSpec(body=body, args=[Buf("o", (8, 128)), Sem("sig")],
                     grid=(2,), kwargs=dict(axis="tp", world=2))
    events.trace_kernel(spec, 2)
    assert set(recorded) == {0}
