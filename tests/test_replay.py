"""Deterministic replay & what-if observatory tests (obs/replay.py).

The load-bearing guarantees (docs/observability.md, "Replay & what-if"):
  1. always-on recording — every ``Fleet.build`` attaches a ``ServeTrace``
     by default; arrivals carry the tenant and the fleet-step anchor,
     the knob configuration is captured, and memory stays bounded (a
     trace that dropped arrivals REFUSES to replay rather than silently
     replaying a prefix);
  2. bit-identical baseline — replaying a recorded trace through the
     real Fleet/BatchEngine anchored on the recorded step indices
     reproduces the live run exactly: same output tokens per request,
     zero lost, zero retraces (donor step-sharing keeps trace_counts
     {1,1});
  3. counterfactuals — altered configs replay against the baseline's
     virtual arrival times; the planted strictly-better config (lifting
     the throttled prefill budget) ranks FIRST on goodput-under-SLO and
     the ranked markdown report is byte-identical across independent
     harnesses;
  4. cost model — least-squares calibration recovers planted affine
     coefficients from >= MIN_CALIB_STEPS samples and falls back to the
     stock model on short/degenerate traces;
  5. persistence — dump()/load() round-trips a trace (calibration sums
     included); ``from_journal`` rebuilds arrivals + golden outputs from
     a schema-2 write-ahead journal alone, and still loads schema-1
     journals (arrivals collapse to step 0);
  6. elastic recording — spawn()/retire() mid-run never step the
     monotone work counters backwards, and the trace recorded across the
     resize still replays bit-identically.
"""

import json
import types

import jax
import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.obs.replay import (
    MIN_CALIB_STEPS,
    STOCK_COEFFS,
    CostModel,
    ReplayHarness,
    ServeTrace,
    WhatIfConfig,
    WhatIfReport,
    _quantile,
)
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import Fleet


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


def _build(engine, **kw):
    kw.setdefault("n_replicas", 2)
    kw.setdefault("n_slots", 3)
    kw.setdefault("n_blocks", 16)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    # Recording and replay are host-side schedule and virtual time; the
    # plain path keeps the dozens of fleets this file drives off the
    # interpreter. No case stays on "fused".
    return Fleet.build(engine, **{**PLAIN_PATH, **kw})


def _drive(fleet, config, *, n_requests=8, seed=0, gap=2, gen=5,
           mid_run=None):
    """Deterministic step-anchored workload: request k submits once the
    fleet clock passes ``gap*k``; optional ``mid_run(fleet, k)`` hook
    fires after each submit wave (spawn/retire injection point)."""
    rng = np.random.default_rng(seed)
    specs = [rng.integers(1, config.vocab_size,
                          size=int(rng.integers(4, 9))).tolist()
             for _ in range(n_requests)]
    k = 0
    while k < n_requests or not all(
            rep.empty or rep.state == "DEAD" for rep in fleet.replicas):
        while k < n_requests and gap * k <= fleet.n_steps:
            fleet.submit(specs[k], gen, tenant=("acme", "globex")[k % 2])
            k += 1
            if mid_run is not None:
                mid_run(fleet, k)
        fleet.step()
        assert fleet.n_steps < 1500, "workload did not settle"
    assert fleet.check_invariants()
    assert not fleet.failed
    return fleet.serve_trace.finalize(fleet)


@pytest.fixture(scope="module")
def recorded(setup):
    """One throttled recorded run shared by the read-only tests: the
    prefill budget is squeezed to 2 so the full-budget counterfactual is
    a planted strict improvement."""
    _, config, engine = setup
    fleet = _build(engine, seed=0)
    for rep in fleet.replicas:
        rep.engine.prefill_budget = 2
    trace = _drive(fleet, config)
    return fleet, trace


# -- recording ---------------------------------------------------------------


def test_recording_always_on_and_arrivals(recorded):
    fleet, trace = recorded
    assert fleet.serve_trace is trace
    assert len(trace.arrivals) == 8 and trace.dropped_arrivals == 0
    for i, a in enumerate(trace.arrivals):
        assert a["seq"] == i
        assert a["tenant"] in ("acme", "globex")
        assert a["at_step"] >= 0 and a["prompt"]
    # Arrivals anchor on a MONOTONE step clock.
    steps = [a["at_step"] for a in trace.arrivals]
    assert steps == sorted(steps)
    assert trace.n_steps == fleet.n_steps > 0


def test_recording_captures_config_and_outputs(recorded):
    fleet, trace = recorded
    cfg = trace.config
    assert cfg["n_replicas"] == 2
    assert cfg["prefill_budget"] == 2          # the throttle was live
    assert cfg["controller"] is False
    assert set(cfg["router"]) == {"w_cache", "w_headroom", "w_queue",
                                  "slo_penalty"}
    assert trace.outputs and len(trace.outputs) == 8
    assert trace.failed == {}
    assert trace.final_stats["finished"] == 8
    assert trace.build_spec is not None


def test_bounded_memory_refuses_dropped_replay():
    tr = ServeTrace(max_arrivals=1)
    req = types.SimpleNamespace(req_id="r0", prompt=[1, 2],
                                max_new_tokens=2, priority=0,
                                tenant=None, submit_t=0.0)
    tr.on_submit(req, 0)
    tr.on_submit(types.SimpleNamespace(**{**vars(req), "req_id": "r1"}), 1)
    assert len(tr.arrivals) == 1 and tr.dropped_arrivals == 1
    with pytest.raises(ValueError, match="dropped 1 arrival"):
        ReplayHarness(tr)


# -- cost model --------------------------------------------------------------


def test_cost_model_stock_fallback_short_trace():
    cm = ServeTrace().cost_model()
    assert cm.source == "stock" and cm.n_samples == 0
    assert (cm.c0, cm.c_prefill, cm.c_decode, cm.c_spec) == STOCK_COEFFS


def test_cost_model_calibration_recovers_planted_coeffs():
    """Feed the normal-equation accumulators an exact affine relation;
    the fit must recover it and report itself calibrated."""
    tr = ServeTrace()
    rng = np.random.default_rng(0)
    true = (2.0, 0.1, 0.05, 0.01)
    for _ in range(2 * MIN_CALIB_STEPS):
        d = rng.integers(0, 9, size=3).astype(np.float64)
        x = np.array([1.0, *d])
        dt = true[0] + true[1] * d[0] + true[2] * d[1] + true[3] * d[2]
        tr._xtx += np.outer(x, x)
        tr._xty += dt * x
        tr._n_samples += 1
    cm = tr.cost_model()
    assert cm.source == "calibrated"
    assert cm.n_samples == 2 * MIN_CALIB_STEPS
    got = (cm.c0, cm.c_prefill, cm.c_decode, cm.c_spec)
    np.testing.assert_allclose(got, true, rtol=1e-6)
    # step_cost is the affine evaluation of those coefficients.
    assert cm.step_cost(10, 4, 2) == pytest.approx(
        2.0 + 0.1 * 10 + 0.05 * 4 + 0.01 * 2)


def test_cost_model_degenerate_fit_falls_back():
    """A negative-intercept fit is noise, not a service rate — stock."""
    tr = ServeTrace()
    for _ in range(2 * MIN_CALIB_STEPS):
        x = np.array([1.0, 1.0, 0.0, 0.0])
        tr._xtx += np.outer(x, x)
        tr._xty += -0.5 * x          # dt < 0 forces c0 <= 0
        tr._n_samples += 1
    assert tr.cost_model().source == "stock"


# -- persistence -------------------------------------------------------------


def test_dump_load_roundtrip(recorded):
    _, trace = recorded
    blob = json.loads(json.dumps(trace.dump()))
    tr2 = ServeTrace.load(blob)
    assert tr2.arrivals == trace.arrivals
    assert tr2.outputs == trace.outputs
    assert tr2.config == trace.config
    assert tr2.final_stats == trace.final_stats
    # Calibration sums ride the dump: the loaded trace fits the SAME
    # cost model.
    assert tr2.cost_model().as_dict() == trace.cost_model().as_dict()
    # A loaded trace has no in-memory build spec — the harness demands
    # explicit engine/kwargs rather than guessing.
    with pytest.raises(ValueError, match="build spec"):
        ReplayHarness(tr2)


def test_from_journal_schema2(setup, tmp_path):
    """A schema-2 WAL alone rebuilds arrivals (tenant + step anchor) and
    golden outputs matching the live trace."""
    _, config, engine = setup
    fleet = _build(engine, seed=3, n_replicas=1)
    path = str(tmp_path / "journal.jsonl")
    fleet.attach_journal(path)
    live = _drive(fleet, config, n_requests=4, gen=3, seed=3)
    fleet.journal.close()
    tr = ServeTrace.from_journal(path)
    assert [(a["req_id"], a["prompt"], a["tenant"], a["at_step"])
            for a in tr.arrivals] == \
           [(a["req_id"], a["prompt"], a["tenant"], a["at_step"])
            for a in live.arrivals]
    assert all(a["arrival_t"] is not None for a in tr.arrivals)
    assert tr.outputs == live.outputs
    assert tr.failed == {}
    assert tr.cost_model().source == "stock"   # no ledger data in a WAL


def test_from_journal_schema1_backcompat(tmp_path):
    """Submit frames without the schema-2 arrival stamp still load:
    arrivals collapse to step 0, order preserved via seq."""
    from triton_distributed_tpu.resilience import RequestJournal

    path = str(tmp_path / "j.jsonl")
    with RequestJournal(path) as j:
        j.append("submit", req_id="r0", prompt=[1, 2], max_new_tokens=3,
                 priority=0, arrival_seq=0)
        j.append("submit", req_id="r1", prompt=[3], max_new_tokens=2,
                 priority=0, arrival_seq=1)
        for tok in (7, 8):
            j.append("emit", req_id="r0", tok=tok)
        j.append("finish", req_id="r0", n_tokens=2)
        j.append("fail", req_id="r1", error="boom")
    tr = ServeTrace.from_journal(path)
    assert [a["req_id"] for a in tr.arrivals] == ["r0", "r1"]
    assert all(a["at_step"] == 0 and a["tenant"] is None
               and a["arrival_t"] is None for a in tr.arrivals)
    assert tr.outputs == {"r0": [7, 8]}
    assert tr.failed == {"r1": "boom"}


# -- replay ------------------------------------------------------------------


def test_baseline_replay_bit_identical(recorded):
    fleet, trace = recorded
    h = ReplayHarness(trace, donor=fleet.replicas[0].engine)
    base = h.baseline()
    assert base.matches_trace
    assert base.lost == 0 and base.retraces == 0
    assert base.outputs == trace.outputs
    assert base.n_steps > 0 and base.vt_total > 0.0
    # Every request got a virtual timeline the report can rank on.
    assert set(base.arrival_vt) == {a["seq"] for a in trace.arrivals}
    assert len(base.ttfts()) == len(trace.arrivals)
    assert h.baseline() is base                 # memoized anchor


def _stock_harness(trace, donor):
    """The trace's own cost model is a least-squares fit of this machine's
    wall clock over ~20 steps (the first two include compilation), so
    which config it favours changes with the load. The plant is defined
    under the stock model: per-step overhead dominates, fewer steps win."""
    h = ReplayHarness(trace, donor=donor)
    h.cost = CostModel(*STOCK_COEFFS)
    return h


def test_counterfactual_ranks_planted_winner(recorded):
    fleet, trace = recorded
    donor = fleet.replicas[0].engine
    h = _stock_harness(trace, donor)
    configs = [WhatIfConfig(name="full-prefill", prefill_budget=8),
               WhatIfConfig(name="one-replica", n_replicas=1)]
    report = h.sweep(configs)
    win = report.winner()
    assert win["name"] == "full-prefill" and win["rank"] == 1
    assert win["d_goodput"] > 0.0              # strictly better
    assert all(row["lost"] == 0 and row["retraces"] == 0
               for row in report.rows)
    # Ranked rows carry signed deltas vs the baseline and the config
    # that produced them.
    assert win["config"] == {"name": "full-prefill", "prefill_budget": 8}
    assert {r["rank"] for r in report.rows} == {1, 2}
    # Byte-identical report across INDEPENDENT harnesses (fresh fleets,
    # fresh virtual clocks) — the determinism the gate watches.
    md2 = _stock_harness(trace, donor).sweep(configs).to_markdown()
    assert report.to_markdown() == md2
    assert "| 1 | full-prefill |" in md2
    assert "## Per-tenant modeled cost" in md2


def test_spawn_retire_under_recording(setup):
    """Satellite: resizing the fleet mid-recording — spawn() after the
    3rd submit, retire(0) after the 5th — never steps the monotone work
    counters backwards, and the recorded trace STILL replays
    bit-identically on a clean fixed-size fleet."""
    _, config, engine = setup
    fleet = _build(engine, seed=1)
    for rep in fleet.replicas:
        rep.engine.prefill_budget = 2
    moved = {"spawn": False, "retire": False}

    def mid_run(f, k):
        if k == 3 and not moved["spawn"]:
            f.spawn()
            moved["spawn"] = True
        if k == 5 and not moved["retire"]:
            f.retire(0)
            moved["retire"] = True

    trace = _drive(fleet, config, n_requests=6, gen=3, seed=1,
                   mid_run=mid_run)
    assert moved["spawn"] and moved["retire"]
    assert any(rep.state == "DEAD" for rep in fleet.replicas)
    # Monotone counters across the resize: every recorded per-step work
    # delta is non-negative (DEAD replicas stay in the sum).
    for row in trace.recent_steps:
        assert row["prefill_tokens"] >= 0
        assert row["decode_rows"] >= 0
        assert row["spec_proposed_tokens"] >= 0
    assert len(trace.arrivals) == 6 and trace.outputs
    # The donor must be a survivor (replica 0 is DEAD).
    donor = next(rep.engine for rep in fleet.replicas
                 if rep.state != "DEAD")
    base = ReplayHarness(trace, donor=donor).baseline()
    assert base.matches_trace and base.lost == 0 and base.retraces == 0


def test_replay_step_guard_raises(recorded):
    fleet, trace = recorded
    h = ReplayHarness(trace, donor=fleet.replicas[0].engine, max_steps=1)
    with pytest.raises(RuntimeError, match="exceeded 1 steps"):
        h.baseline()


# -- report plumbing ---------------------------------------------------------


def test_quantile_nearest_rank():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert _quantile(vals, 0.5) == 3.0
    assert _quantile(vals, 0.99) == 5.0
    assert _quantile(vals, 0.0) == 1.0
    assert _quantile([], 0.5) == 0.0


def test_report_slo_override_and_ranking(recorded):
    """Explicit SLO bounds replace the baseline-derived defaults; an
    impossible TTFT bound zeroes every goodput."""
    fleet, trace = recorded
    h = ReplayHarness(trace, donor=fleet.replicas[0].engine)
    report = h.sweep([WhatIfConfig(name="full-prefill", prefill_budget=8)],
                     ttft_slo=1e-12, tbt_slo=1e-12)
    assert report.slo == {"ttft": 1e-12, "tbt": 1e-12}
    assert report.baseline["goodput"] == 0.0
    assert all(r["goodput"] == 0.0 for r in report.rows)
    blob = report.as_dict()
    assert set(blob) == {"slo", "cost_model", "baseline", "rows"}
    assert blob["cost_model"]["source"] in ("stock", "calibrated")


def test_whatif_config_as_dict_names_only_moved_knobs():
    c = WhatIfConfig(name="x", prefill_budget=4)
    assert c.as_dict() == {"name": "x", "prefill_budget": 4}
    full = WhatIfConfig(name="y", n_replicas=3, prefix_cache=False,
                        controller=True, engine_kwargs={"seed": 1})
    d = full.as_dict()
    assert d == {"name": "y", "n_replicas": 3, "prefix_cache": False,
                 "controller": True}      # engine_kwargs stays internal
    assert WhatIfReport.build(
        types.SimpleNamespace(ttfts=lambda: [], tbts=lambda: [],
                              requests={}, vt_total=1.0, mfu=0.0,
                              mbu=0.0, incidents=0, tenant_cost=[],
                              retraces=0, matches_trace=True, lost=0,
                              failed={}, n_steps=0, name="baseline"),
        []).rows == []
