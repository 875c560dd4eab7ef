"""Resource & layout analyzer tests (ISSUE 8 tentpole): VMEM/SMEM budgets
against the chip model, Mosaic tile legality, out-of-bounds bboxes,
grid-coverage of declared-covered outputs, the seeded resource mutants,
the CLI gate, and the autotuner config-pruner wiring."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from tools import resource_check
from triton_distributed_tpu.analysis import (
    checks,
    events,
    layout,
    registry,
    resources,
)
from triton_distributed_tpu.analysis.registry import (
    Buf,
    KernelEntry,
    Sem,
    TraceSpec,
)
from triton_distributed_tpu.runtime import perf_model

WORLDS = (2, 4, 8)


def _entry(name, build, worlds=WORLDS):
    return KernelEntry(name=name, build=build, worlds=tuple(worlds),
                       module=__name__, hidden=True)


# ---------------------------------------------------------------------------
# Tentpole acceptance: every registered kernel (incl. +probe) sweeps clean.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("world", WORLDS)
def test_all_registered_kernels_resource_clean(world):
    entries = registry.all_kernels()
    assert any(e.name.endswith("+probe") for e in entries)
    bad = {}
    for e in entries:
        if world not in e.worlds:
            continue
        fs = resources.check_resources(e, world)
        if fs:
            bad[e.name] = [str(f) for f in fs]
    assert not bad, bad


# ---------------------------------------------------------------------------
# Seeded resource mutants: each caught with the expected finding class,
# while the comm-safety checker stays green (the bug is a resource bug).
# ---------------------------------------------------------------------------


RESOURCE_MUTANT_EXPECT = {
    "mutant.vmem_blowup_tile": "vmem-budget",
    "mutant.misaligned_bf16_tile": "tile-align",
    "mutant.grid_undercoverage": "grid-coverage",
}


@pytest.mark.parametrize("name", sorted(RESOURCE_MUTANT_EXPECT))
def test_resource_mutants_are_caught(name):
    fs = resources.check_kernel(name, 2)
    assert fs, f"{name}: resource analyzer found nothing"
    got = {f.check for f in fs}
    assert RESOURCE_MUTANT_EXPECT[name] in got, (
        f"{name}: expected {RESOURCE_MUTANT_EXPECT[name]}, got {got}: "
        + "; ".join(str(f) for f in fs))
    # comm-clean by construction: only the resource layer may flag these.
    assert checks.check_kernel(name, 2) == []


# ---------------------------------------------------------------------------
# layout.py units
# ---------------------------------------------------------------------------


def test_min_tile_by_dtype():
    assert layout.min_tile(np.float32) == (8, 128)
    assert layout.min_tile(np.dtype(jnp.bfloat16)) == (16, 128)
    assert layout.min_tile(np.int8) == (32, 128)


def test_padded_nbytes_rounds_up_to_tile():
    # (8, 128) f32 is already tile-shaped: no padding.
    assert layout.padded_nbytes((8, 128), np.float32) == 8 * 128 * 4
    # Last dim 100 pads to 128; second-minor 5 pads to the 8-sublane tile.
    assert layout.padded_nbytes((5, 100), np.float32) == 8 * 128 * 4
    # bf16 second-minor pads to 16 sublanes.
    assert layout.padded_nbytes((5, 128), jnp.bfloat16) == 16 * 128 * 2
    # 1-D vectors pad to a full lane row; 0-D is one element.
    assert layout.padded_nbytes((3,), np.float32) == 128 * 4
    assert layout.padded_nbytes((), np.float32) == 4


def test_tile_misalignment():
    assert layout.tile_misalignment((8, 128), np.float32) is None
    assert layout.tile_misalignment((8, 256), np.float32) is None
    # Sub-tile dims are padded by Mosaic, not misaligned.
    assert layout.tile_misalignment((4, 100), np.float32) is None
    # Last dim above a tile but not a multiple of it: flagged.
    assert layout.tile_misalignment((8, 192), jnp.bfloat16) is not None
    # Second-minor dim above the sublane tile but not a multiple.
    assert layout.tile_misalignment((24, 128), jnp.bfloat16) is not None
    # <2-D shapes have no (sublane, lane) layout to misalign.
    assert layout.tile_misalignment((192,), jnp.bfloat16) is None


def test_coverage_gap_machinery():
    assert layout.merge_intervals([(0, 4), (4, 8), (10, 12)]) == [
        (0, 8), (10, 12)]
    assert layout.coverage_gaps([(0, 8), (10, 12)], 16) == [
        (8, 10), (12, 16)]
    assert layout.coverage_gaps([(0, 16)], 16) == []
    assert layout.coverage_gaps([], 4) == [(0, 4)]


# ---------------------------------------------------------------------------
# footprint: byte accounting + budget clamping
# ---------------------------------------------------------------------------


def test_footprint_accounting_and_budget_clamp():
    spec = TraceSpec(
        body=lambda *a, **k: None,
        args=[
            Buf("h", (1024, 128), np.float32),                # hbm: free
            Buf("v", (8, 128), np.float32, space="vmem"),     # 4 KiB
            Buf("s", (7,), np.int32, space="smem"),           # 28 B raw
            Sem("sems", (3,)),
        ])
    fp = resources.footprint(spec)
    assert fp.vmem_bytes == 8 * 128 * 4
    assert fp.smem_bytes == 28 + 3 * resources.SEM_SLOT_BYTES
    assert fp.sem_slots == 3
    # Chip VMEM (128 MiB on v5e) clamps to Mosaic's 16 MiB scoped window.
    assert fp.vmem_budget == 16 * 2**20
    # A smaller chip model lowers the budget below the Mosaic window.
    tiny = perf_model.Hardware(
        **{**{f.name: getattr(perf_model.detect_hardware(), f.name)
              for f in perf_model.Hardware.__dataclass_fields__.values()},
           "vmem_bytes": 2 * 2**20, "smem_bytes": 16})
    fp2 = resources.footprint(spec, tiny)
    assert fp2.vmem_budget == 2 * 2**20
    assert fp2.smem_budget == 16  # 40 B of SMEM use now over budget
    fs = resources.check_resources(
        _entry("t.smem_over", lambda w: spec), 2, hardware=tiny,
        trace=False)
    assert {f.check for f in fs} == {"smem-budget"}


# ---------------------------------------------------------------------------
# OOB bboxes from the event trace
# ---------------------------------------------------------------------------


def test_oob_access_is_flagged():
    def body(x_ref, o_ref):
        o_ref[pl.ds(0, 8)] = x_ref[pl.ds(0, 8)]
        _ = x_ref[pl.ds(4, 8)]  # reads rows [4, 12) of an 8-row buffer

    def build(world):
        return TraceSpec(body=body, ranks=1,
                         args=[Buf("x", (8, 128)), Buf("o", (8, 128))])

    fs = resources.check_resources(_entry("t.oob", build), 2)
    oob = [f for f in fs if f.check == "oob-bbox"]
    assert oob and oob[0].buf == "x", [str(f) for f in fs]
    assert "read" in oob[0].detail and "past declared shape" in oob[0].detail


# ---------------------------------------------------------------------------
# Satellite: dtype-width bboxes — int8/bf16/f32 refs produce byte-correct
# read/write extents in the event logs.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,itemsize", [
    (np.int8, 1), (jnp.bfloat16, 2), (np.float32, 4)])
def test_event_bboxes_are_dtype_width_aware(dtype, itemsize):
    row = 128 * itemsize  # bytes per (128,)-lane row

    def body(b_ref):
        b_ref[pl.ds(2, 4)] = b_ref[pl.ds(0, 4)] if itemsize != 2 else 0
        _ = b_ref[pl.ds(1, 3)]

    spec = TraceSpec(body=body, ranks=1,
                     args=[Buf("b", (8, 128), np.dtype(dtype))])
    tr = events.trace_kernel(spec, 2)
    assert not tr.oob
    evs = [(e.kind, e.lo, e.hi) for e in tr.logs[0]
           if e.kind in ("read", "write") and e.buf == "b"]
    assert ("write", 2 * row, 6 * row) in evs, evs
    assert ("read", 1 * row, 4 * row) in evs, evs
    ext = layout.write_extents(tr)
    assert ext[("b", 0)] == [(2 * row, 6 * row)]


# ---------------------------------------------------------------------------
# Config-parameterized checking + the autotuner pruner hook
# ---------------------------------------------------------------------------


def test_paged_decode_config_sensitivity():
    ok = resources.check_kernel(
        "paged.decode", 1,
        dict(tile_blocks=2, bs=16, n_kv=2, dh=128, max_blocks=4,
             dtype="float32"), trace=False)
    assert ok == []
    blown = resources.check_kernel(
        "paged.decode", 1,
        dict(tile_blocks=2048, bs=16, n_kv=8, dh=128, max_blocks=2048,
             dtype="bfloat16"), trace=False)
    assert {f.check for f in blown} == {"vmem-budget"}


@pytest.mark.parametrize("kernel,arenas", [("paged.decode", 1),
                                           ("paged.decode.kvq", 2),
                                           ("paged.latent", 1)])
def test_paged_footprint_bills_two_staging_slots(kernel, arenas):
    """The walk's fetch pipeline stages every arena twice (tile n + 1 lands
    while tile n is computed; the K+V arena's slot holds both planes of the
    tile) and owns one semaphore a (slot, arena): the
    spec declares what the kernel allocates, so doubling the tile doubles
    twice the staging."""
    geometry = dict(bs=16, max_blocks=64)
    spec = {t: registry.get(kernel).build(1, tile_blocks=t, **geometry)
            for t in (8, 16)}
    stage = [a for a in spec[8].args if a.name.endswith("_stage")]
    assert len(stage) == arenas and all(a.shape[0] == 2 for a in stage)
    sems, = [a for a in spec[8].args if isinstance(a, Sem)]
    assert sems.shape == (2, arenas)
    one_slot = sum(layout.padded_nbytes(a.shape[1:], a.dtype) for a in stage)
    grown = (resources.footprint(spec[16]).vmem_bytes
             - resources.footprint(spec[8]).vmem_bytes)
    assert grown == 2 * one_slot


def test_paged_prefill_config_sensitivity():
    """The (tile_blocks, q_tile) config space: a sane prefill config is
    clean, and blowing up either axis trips the VMEM budget — the same
    closure the ContextualAutotuner pruner uses for L > 1."""
    ok = resources.check_kernel(
        "paged.prefill", 1,
        dict(tile_blocks=2, bs=16, n_kv=2, dh=128, max_blocks=4,
             dtype="float32", L=8, q_tile=4), trace=False)
    assert ok == []
    for cfg in (dict(tile_blocks=2048, q_tile=4),     # kv staging blows
                dict(tile_blocks=2, q_tile=4096)):    # q/acc staging blows
        blown = resources.check_kernel(
            "paged.prefill", 1,
            dict(bs=16, n_kv=8, dh=128, max_blocks=2048,
                 dtype="bfloat16", L=4096, **cfg), trace=False)
        assert "vmem-budget" in {f.check for f in blown}, cfg


def test_config_pruner_closure_feeds_autotuner(tmp_path, monkeypatch):
    """End-to-end: a ContextualAutotuner wired with the resources config
    pruner never compiles a VMEM-blowing paged.decode tile."""
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    from triton_distributed_tpu.runtime import autotuner

    autotuner.clear_cache()
    geometry = dict(bs=16, n_kv=8, dh=128, max_blocks=2048,
                    dtype="bfloat16")
    pruner = resources.config_pruner(
        "paged.decode", 1,
        lambda tile: dict(tile_blocks=int(tile), **geometry))
    assert pruner(2048) and pruner(2048)[0].check == "vmem-budget"
    assert pruner(1) == []

    compiled = []

    def make_thunk(tile):
        compiled.append(tile)
        return lambda: float(tile)

    monkeypatch.setattr(autotuner, "perf_thunk",
                        lambda thunk, **kw: thunk())
    tuner = autotuner.ContextualAutotuner("t_paged_prune", [2048, 1, 2],
                                          pruner=pruner)
    assert tuner.tune(make_thunk, "g") == 1
    assert compiled == [1, 2]  # 2048 rejected before any compile
    autotuner.clear_cache()


def test_build_failure_is_a_finding_not_a_crash():
    def build(world):
        raise RuntimeError("bad geometry")

    fs = resources.check_resources(_entry("t.badbuild", build), 2)
    assert [f.check for f in fs] == ["resource-trace-error"]


# ---------------------------------------------------------------------------
# CLI gate (tools/resource_check.py)
# ---------------------------------------------------------------------------


def test_cli_sweep_is_clean(capsys):
    rc = resource_check.main(["--world", "2"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "all resource & layout checks clean" in out
    assert "| `paged.decode` |" in out


@pytest.mark.parametrize("name", sorted(RESOURCE_MUTANT_EXPECT))
def test_cli_flags_each_resource_mutant(name, capsys):
    rc = resource_check.main(["--kernel", name, "--world", "2"])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert RESOURCE_MUTANT_EXPECT[name] in out


def test_cli_usage_errors():
    assert resource_check.main(["--kernel", "no.such.kernel"]) == 2
    assert resource_check.main(["--world", "0"]) == 2
    assert resource_check.main(["--hardware", "no-such-chip"]) == 2


def test_cli_hardware_and_report(tmp_path, capsys):
    report = tmp_path / "resources.md"
    rc = resource_check.main(["--kernel", "ag.ring", "--world", "2",
                              "--hardware", "tpu v4",
                              "--report", str(report)])
    assert rc == 0
    assert "Resource & layout report" in report.read_text()
    capsys.readouterr()


def test_cli_list_names_hidden_mutants(capsys):
    assert resource_check.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "mutant.vmem_blowup_tile" in out and "[hidden]" in out
