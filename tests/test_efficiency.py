"""Efficiency ledger (ISSUE 15): per-step attribution exactness under a
virtual clock, tenant-tag propagation across a seeded fleet kill+requeue
with conserved cost totals, bounded-memory behavior, the window sum/mean
accessors against a numpy reference, the roofline metric classes, and the
fleet_efficiency report's determinism + exit codes. The ledger is host
logic: the fleet here takes the plain path (``conftest.PLAIN_PATH``)."""

import importlib.util
import json
import math
import pathlib

import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.obs.efficiency import (
    BUCKETS,
    FRAC_TOL,
    EfficiencyLedger,
)
from triton_distributed_tpu.obs.window import WindowRing

_SMOKE = pathlib.Path(__file__).parent.parent / "scripts" / "serve_smoke.py"


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


def _ledger(**kw):
    kw.setdefault("peak_flops", 100.0)
    kw.setdefault("hbm_bw", 100.0)
    kw.setdefault("clock", FakeClock())
    return EfficiencyLedger(**kw)


# --- attribution exactness (virtual step clock) ----------------------------


def test_attribution_exact_fractions():
    """With peak = bw = 100/s, a 1 s step with 20 flops, 30 bytes and
    0.1 s of comm decomposes EXACTLY: 0.2/0.3/0.1 modeled, 0.4 stall,
    0 bubble — and the fractions sum to exactly 1.0."""
    led = _ledger()
    led.step_begin(now=10.0)
    att = led.step_end(flops=20.0, hbm_bytes=30.0, comm_s=0.1, tokens=4,
                       tenants={"a": 3, "b": 1}, now=11.0)
    assert att.fracs == {"compute": 0.2, "hbm": 0.3, "comm": 0.1,
                         "stall": 0.4, "bubble": 0.0}
    assert att.frac_sum == 1.0
    assert att.interval_s == 1.0 and att.wall_s == 1.0
    assert sum(att.seconds.values()) == pytest.approx(1.0, abs=1e-12)


def test_attribution_bubble_and_clamp():
    """The inter-step gap becomes bubble; modeled compute clamps to the
    measured wall (never over-accounts); windowed and lifetime MFU agree
    under the virtual clock because both divide accounted seconds."""
    led = _ledger()
    led.step_begin(now=10.0)
    led.step_end(flops=20.0, hbm_bytes=30.0, comm_s=0.1, now=11.0)
    # 0.5 s host gap, then a step whose modeled flops (200 -> 2 s at peak)
    # exceed the 1 s wall: compute clamps to the wall, nothing left over.
    led.step_begin(now=11.5)
    att = led.step_end(flops=200.0, hbm_bytes=50.0, now=12.5)
    assert att.seconds["bubble"] == 0.5
    assert att.seconds["compute"] == 1.0
    assert att.seconds["hbm"] == 0.0 and att.seconds["stall"] == 0.0
    assert att.fracs["bubble"] == pytest.approx(0.5 / 1.5)
    assert abs(att.frac_sum - 1.0) <= FRAC_TOL
    # Windowed == lifetime: 220 flops over 2.5 accounted seconds at peak
    # 100/s.
    assert led.mfu(60.0, now=12.5) == pytest.approx(220.0 / 250.0)
    assert led.lifetime_mfu() == pytest.approx(220.0 / 250.0)
    assert led.lifetime_bubble_frac() == pytest.approx(0.5 / 2.5)
    # The gap landed in the worst-bubble ring with its [t0, t1] interval.
    worst = led.stats()["worst_bubble"]
    assert worst[0]["bubble_s"] == 0.5
    assert (worst[0]["t0"], worst[0]["t1"]) == (11.0, 11.5)


def test_attribution_degenerate_and_residue():
    """A zero-length interval bills the unit fraction to stall (nothing to
    attribute); awkward float intervals still telescope to 1.0 within
    FRAC_TOL on every retained step."""
    led = _ledger()
    led.step_begin(now=5.0)
    att = led.step_end(flops=1.0, hbm_bytes=1.0, now=5.0)
    assert att.fracs["stall"] == 1.0 and att.frac_sum == 1.0
    t = 5.0
    for i in range(200):
        t += 0.01 * (i % 7 + 1) / 3.0          # awkward float gaps
        led.step_begin(now=t)
        t += 0.001 * (i % 11 + 1) / 7.0        # awkward float walls
        led.step_end(flops=0.013 * i, hbm_bytes=0.029 * i,
                     comm_s=1e-5 * i, now=t)
    assert led.frac_sum_ok
    for att in led.recent:
        assert abs(att.frac_sum - 1.0) <= FRAC_TOL


def test_stall_detail_refines_never_reclassifies():
    led = _ledger()
    led.step_begin(now=0.0)
    att = led.step_end(flops=10.0, hbm_bytes=10.0, now=1.0,
                       stall_summary={"pct_dma_wait": 50.0,
                                      "pct_sem_spin": 25.0})
    # stall = 1.0 - 0.1 - 0.1 = 0.8 s, split 50/25/25 — the detail sums
    # back to the stall bucket, it never changes the bucket itself.
    assert att.seconds["stall"] == pytest.approx(0.8)
    d = att.stall_detail
    assert d["dma_wait_s"] == pytest.approx(0.4)
    assert d["sem_spin_s"] == pytest.approx(0.2)
    assert d["other_s"] == pytest.approx(0.2)
    assert (d["dma_wait_s"] + d["sem_spin_s"] + d["other_s"]
            == pytest.approx(att.seconds["stall"]))


def test_tenant_billing_token_weighted():
    led = _ledger()
    led.step_begin(now=0.0)
    led.step_end(flops=20.0, hbm_bytes=30.0, tokens=4,
                 tenants={"a": 3, "b": 1}, now=1.0)
    rows = {r["tenant"]: r for r in led.tenant_table()}
    assert rows["a"]["tokens"] == 3 and rows["b"]["tokens"] == 1
    assert rows["a"]["flop_s"] == pytest.approx(0.75 * 0.2)
    assert rows["b"]["flop_s"] == pytest.approx(0.25 * 0.2)
    assert rows["a"]["cost_frac"] == pytest.approx(0.75)
    # Conservation: billed tokens and flop-seconds sum to the step totals.
    assert sum(r["tokens"] for r in rows.values()) == 4
    assert (sum(r["flop_s"] for r in rows.values())
            == pytest.approx(0.2))


# --- bounded memory --------------------------------------------------------


def test_bounded_memory_soak():
    """keep_steps / worst_k / max_tenants all cap; overflow tenants bill
    to ~overflow so token totals stay conserved."""
    led = _ledger(keep_steps=16, worst_k=4, max_tenants=4)
    t = 0.0
    for i in range(500):
        t += 0.01 + (i % 5) * 0.001            # varying bubbles
        led.step_begin(now=t)
        t += 0.002
        led.step_end(flops=1.0, hbm_bytes=1.0, tokens=2,
                     tenants={f"tenant-{i}": 2}, now=t)
    assert led.steps == 500 and led.frac_sum_ok
    assert len(led.recent) == 16
    assert len(led.stats()["worst_bubble"]) == 4
    rows = led.tenant_table()
    assert len(rows) == 5                      # 4 named + ~overflow
    over = {r["tenant"]: r for r in rows}[EfficiencyLedger.OVERFLOW_TENANT]
    assert over["tokens"] == 2 * (500 - 4)
    assert sum(r["tokens"] for r in rows) == 1000
    # The perfdb sample stays flat and bounded too.
    sample = led.perfdb_sample()
    assert sample["tenant_count"] == 5.0
    assert sample["eff_frac_sum_violations"] == 0.0


# --- fleet: tenant tags survive kill+requeue, totals conserve --------------


def test_fleet_tenant_conservation_across_requeue():
    """One tenant, two replicas, a seeded replica kill: every request
    still completes (the tag rides the requeue), billing happened where
    the work ran (the dead replica's ledger keeps its share), and the
    merged tenant table equals the sum of the per-replica tables."""
    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.resilience import (
        default_fleet_chaos_plan,
        faults,
    )
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving import Fleet

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1],
                     set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    fleet = Fleet.build(engine, n_replicas=2, n_slots=4, n_blocks=32,
                        block_size=4, prefill_chunk=8, fail_threshold=2,
                        **PLAIN_PATH)
    rng = np.random.default_rng(0)
    n_req = 16
    with faults.plan(default_fleet_chaos_plan(0, kill_replica=0,
                                              kill_after=6)):
        for i in range(n_req):
            prompt = rng.integers(0, config.vocab_size,
                                  size=int(rng.integers(3, 9))).tolist()
            fleet.submit(prompt, max_new_tokens=4, req_id=f"r{i}",
                         tenant="acme")
        fleet.run(max_steps=100000)
    fleet.check_invariants()
    assert len(fleet.failed) == 0
    assert len(fleet.finished) == n_req
    fm = fleet.metrics.as_dict()
    assert fm.get("replica_quarantines", 0) >= 1

    ledgers = [rep.engine.efficiency for rep in fleet.replicas]
    tables = [led.tenant_table() for led in ledgers]
    # Work ran on both replicas before/after the kill.
    assert sum(1 for tb in tables if tb) == 2
    for tb in tables:
        assert {r["tenant"] for r in tb} <= {"acme"}
    merged = EfficiencyLedger.merge_tenant_tables(tables)
    assert [r["tenant"] for r in merged] == ["acme"]
    # Conservation: the merge equals the per-replica sums exactly.
    assert merged[0]["tokens"] == sum(r["tokens"] for tb in tables
                                      for r in tb)
    assert merged[0]["flop_s"] == pytest.approx(
        sum(r["flop_s"] for tb in tables for r in tb))
    assert merged[0]["tokens"] > 0
    assert merged[0]["cost_frac"] == pytest.approx(1.0)

    # The fleet snapshot and perfdb sample carry the same rollup.
    snap = fleet.stats_snapshot()
    eff = snap["efficiency"]
    assert eff["aggregate"]["frac_sum_ok"]
    assert eff["aggregate"]["steps"] == sum(led.steps for led in ledgers)
    assert [r["tenant"] for r in eff["tenants"]] == ["acme"]
    assert eff["tenants"][0]["tokens"] == merged[0]["tokens"]
    json.dumps(snap, default=str)
    sample = fleet.perfdb_sample()
    assert sample["tenant_tokens{tenant=acme}"] == float(
        merged[0]["tokens"])
    assert "mfu" in sample and "bubble_frac" in sample


def test_aggregate_recomputes_ratios_from_totals():
    """Fleet MFU is flops-over-accounted-peak across replicas — never an
    average of per-replica ratios (a 10x-longer replica dominates)."""
    a, b = _ledger(), _ledger()
    a.step_begin(now=0.0)
    a.step_end(flops=50.0, hbm_bytes=0.0, now=1.0)       # mfu 0.5 over 1 s
    b.step_begin(now=0.0)
    b.step_end(flops=100.0, hbm_bytes=0.0, now=10.0)     # mfu 0.1 over 10 s
    agg = EfficiencyLedger.aggregate([a, b])
    assert agg["mfu"] == pytest.approx(150.0 / (100.0 * 11.0), abs=1e-6)
    assert agg["steps"] == 2
    assert abs(sum(agg["fracs"].values()) - 1.0) <= 1e-5


# --- satellite: window sum/mean vs numpy reference -------------------------


def test_window_sum_mean_numpy_reference():
    """sum()/mean() agree with a numpy reference at the ring's documented
    bucket granularity, across many (window, now) combinations, from a
    constant-memory ring."""
    bucket_s, n_buckets = 0.5, 64
    ring = WindowRing(bucket_s=bucket_s, n_buckets=n_buckets, bounds=None,
                      clock=lambda: 0.0)
    rng = np.random.default_rng(1)
    ts = np.sort(rng.uniform(0.0, 30.0, size=400))
    vs = rng.uniform(-2.0, 5.0, size=400)
    for t, v in zip(ts, vs):
        ring.observe(float(v), now=float(t))

    def ref(window_s, now):
        p_now = int(now / bucket_s)
        n_back = max(1, math.ceil(window_s / bucket_s))
        oldest = p_now - n_back + 1
        periods = (ts / bucket_s).astype(int)
        sel = vs[(periods >= oldest) & (periods <= p_now)]
        return sel

    for window_s in (0.5, 1.0, 3.3, 10.0, 30.0):
        for now in (5.0, 15.2, 29.9, 30.0):
            sel = ref(window_s, now)
            assert ring.sum(window_s, now=now) == pytest.approx(
                float(sel.sum()), abs=1e-9)
            expect_mean = float(sel.mean()) if sel.size else 0.0
            assert ring.mean(window_s, now=now) == pytest.approx(
                expect_mean, abs=1e-9)
    # Empty window: zero, not NaN.
    assert ring.mean(1.0, now=500.0) == 0.0
    assert ring.sum(1.0, now=500.0) == 0.0


# --- satellite: roofline metric classes ------------------------------------


def test_roofline_metric_classes():
    from triton_distributed_tpu.obs.roofline import metric_class

    assert metric_class("mfu") == "compute"
    assert metric_class("mbu") == "hbm"
    assert metric_class("bubble_frac") == "host"
    assert metric_class("lifetime_mbu") == "hbm"
    # Pre-existing classes unchanged by the new head rules.
    assert metric_class("ttft_p99_s") == "serving"
    assert metric_class("paged_attn_decode_bytes_ratio") == "hbm"
    # Regression pin: unmatched names stay "unknown", never guessed.
    assert metric_class("totally_novel_metric_xyz") == "unknown"


def test_perfdb_directions_for_efficiency_metrics():
    from triton_distributed_tpu.obs.perfdb import metric_direction

    assert metric_direction("mfu") == 1
    assert metric_direction("mbu") == 1
    # "bubble_frac" would read higher-better via the "_frac" hint; the
    # lower-better override must win.
    assert metric_direction("bubble_frac") == -1


# --- satellite: fleet_efficiency report ------------------------------------


def _fe():
    from tools import fleet_efficiency
    return fleet_efficiency


def test_fleet_efficiency_report_deterministic(capsys):
    fe = _fe()
    snap = fe._demo_snapshot()
    r1 = fe.render_report(snap)
    r2 = fe.render_report(fe._demo_snapshot())
    assert r1 == r2
    for section in ("# Fleet efficiency", "Where the time went",
                    "Per replica", "Tenant cost ranking",
                    "Worst host bubbles"):
        assert section in r1
    # Blackbox correlation: the demo's backpressure event falls inside the
    # worst bubble's [t0, t1] gap and is attributed to it.
    assert "backpressure" in r1
    assert fe.main(["--demo"]) == 0
    capsys.readouterr()


def test_fleet_efficiency_exit_codes(tmp_path, capsys):
    fe = _fe()
    # 1: the bubble gate trips on the demo's 11% aggregate bubble.
    assert fe.main(["--demo", "--max-bubble-frac", "0.05"]) == 1
    # 1: a frac-sum violation in the snapshot is an accounting bug.
    snap = fe._demo_snapshot()
    snap["efficiency"]["aggregate"]["frac_sum_ok"] = False
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(snap))
    assert fe.main(["--snapshot", str(p)]) == 1
    # 2: unreadable input / no efficiency block.
    assert fe.main(["--snapshot", str(tmp_path / "missing.json")]) == 2
    q = tmp_path / "noeff.json"
    q.write_text(json.dumps({"counters": {}}))
    assert fe.main(["--snapshot", str(q)]) == 2
    capsys.readouterr()


def test_fleet_efficiency_renders_engine_shape():
    """An ENGINE snapshot (flat ledger stats, no per-replica rollup) must
    render through the same report path."""
    fe = _fe()
    led = _ledger()
    led.step_begin(now=1.0)
    led.step_end(flops=20.0, hbm_bytes=30.0, tokens=2,
                 tenants={"solo": 2}, now=2.0)
    report = fe.render_report({"efficiency": led.stats()})
    assert "MFU 20.0%" in report
    assert "solo" in report


# --- satellite: serve_smoke --efficiency arm (tier 1) ----------------------


def test_serve_smoke_efficiency_arm():
    """The --efficiency arm: a short loaded run must end with the ledger's
    contract intact — main() itself raises on zero MFU, frac-sum breakage,
    or bubble_frac >= 1. The ledger is the host's: the arm takes the plain
    path where the fused kernel would be interpreted
    (``serve_smoke._host_arm_attn``)."""
    spec = importlib.util.spec_from_file_location("serve_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = mod.main(2.5, rate_hz=6.0, seed=0, efficiency=True)
    eff = m["efficiency"]
    assert eff["steps"] > 0
    assert eff["frac_sum_ok"] is True
    assert 0.0 <= eff["bubble_frac"] < 1.0
    assert abs(sum(eff["fracs"].values()) - 1.0) <= 1e-5
    assert set(eff["fracs"]) == set(BUCKETS)
    assert m["trace_count_decode"] == 1
    assert m["trace_count_prefill"] == 1
