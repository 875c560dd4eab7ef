"""Unit tests for the incident engine (triton_distributed_tpu/obs/incident):
detector precision on clean pseudo-noise, recall + bounded detect latency on
level shifts, CUSUM drift capture and its capped clear latency, the
sticky-window echo freeze, counter-kind CRITICAL trips, deterministic
byte-identical replay, cursor-based triage ranking against fake evidence
sources, SLO-breach integration, the cross-replica merge, and the bounded
incident ring. All pure-host: no jax, no clocks — every test drives
``observe()`` with an explicit sample sequence.
"""

import json
import random

import pytest

from triton_distributed_tpu.obs.incident import (
    CRITICAL,
    WARN,
    IncidentEngine,
    SignalSpec,
    default_signals,
)
from triton_distributed_tpu.resilience.faults import FaultEvent


def _level_engine(**kw):
    """One level signal with a short warmup so tests stay fast. The
    baseline is fed constant 0.01s samples; scale floors at
    rel_floor * 0.01 = 0.005, so the 6-sigma line sits at +0.03."""
    spec = SignalSpec("lat", direction=1, min_samples=16, baseline_n=64,
                      **kw)
    return IncidentEngine(signals=[spec]), spec


def _feed(eng, name, values):
    opened = []
    for v in values:
        inc = eng.observe({name: v})
        if inc is not None:
            opened.append(inc)
    return opened


# ---------------------------------------------------------------------------
# precision: clean traces open nothing
# ---------------------------------------------------------------------------


def test_clean_pseudo_noise_opens_nothing():
    eng = IncidentEngine()  # the full stock serving signal set
    rng = random.Random(0)
    for _ in range(400):
        n = rng.random()
        eng.observe({
            "tbt_p99_s": 0.012 + 0.001 * n,
            "queue_wait_p99_s": 0.003 + 0.002 * n,
            "mfu": 0.42 - 0.02 * n,
            "mbu": 0.55 - 0.02 * n,
            "bubble_frac": 0.02 + 0.01 * n,
            "accept_rate": 0.7 - 0.05 * n,
            "achieved_over_est": 1.1 + 0.1 * n,
            "requests_failed": 0.0,
            "quarantines": 0.0,
            "requeues": 0.0,
        })
    assert eng.n_opened == 0
    assert eng.stats()["total"] == 0
    assert eng.stats()["severity_level"] == 0


def test_single_spike_below_trip_after_opens_nothing():
    eng, spec = _level_engine()
    base = [0.01 + 1e-5 * (i % 3) for i in range(40)]
    # Two isolated anomalous samples — under trip_after=3 — then recovery.
    # The per-sample CUSUM cap matters here: even a giant spike contributes
    # at most z_thresh - k per sample, so its residual can't keep
    # "anomalous" alive through the recovery and defeat trip_after.
    _feed(eng, "lat", base + [0.2, 0.21] + base)
    assert eng.n_opened == 0


# ---------------------------------------------------------------------------
# recall: level shift trips, latency bounded by trip_after
# ---------------------------------------------------------------------------


def test_level_shift_trips_with_bounded_latency():
    eng, spec = _level_engine()
    base = [0.01 + 1e-5 * (i % 3) for i in range(40)]
    shift = [0.1 + 1e-4 * i for i in range(12)]  # varied, not echoes
    opened = _feed(eng, "lat", base + shift)
    assert len(opened) == 1
    inc = opened[0]
    assert inc.kind == "anomaly"
    assert inc.severity == WARN
    assert inc.step_first_anomaly == 40
    assert inc.detect_latency_steps == spec.trip_after
    d = inc.signals["lat"]
    assert d["kind"] == "level"
    assert d["baseline"] == pytest.approx(0.01, abs=1e-4)
    assert d["value"] >= 0.1
    assert d["deviation"] == pytest.approx(d["value"] - d["baseline"],
                                           abs=1e-6)


def test_direction_minus_one_trips_on_drop_only():
    # rel_floor lowered so the bounded [0,1] ratio can actually reach 6
    # sigma on a drop (the stock specs keep the conservative default).
    spec = SignalSpec("mfu", direction=-1, min_samples=16, rel_floor=0.1)
    eng = IncidentEngine(signals=[spec])
    base = [0.4 + 1e-4 * (i % 3) for i in range(40)]
    # Upward excursion on a lower-is-anomalous signal: must NOT trip.
    _feed(eng, "mfu", base + [0.9 + 1e-4 * i for i in range(8)])
    assert eng.n_opened == 0
    # Downward excursion: trips.
    opened = _feed(eng, "mfu", [0.05 + 1e-4 * i for i in range(8)])
    assert len(opened) == 1


def test_incident_closes_after_clear_hysteresis():
    eng, spec = _level_engine()
    base = [0.01 + 1e-5 * (i % 3) for i in range(40)]
    shift = [0.1 + 1e-4 * i for i in range(6)]
    opened = _feed(eng, "lat", base + shift)
    assert len(opened) == 1 and opened[0].open
    # Varied recovery samples (identical repeats would freeze — see the
    # echo test) close it after clear_after consecutive clean samples.
    _feed(eng, "lat", [0.01 + 1e-5 * (i % 5) for i in range(40)])
    assert not opened[0].open
    assert eng.n_closed == 1
    assert eng.n_open == 0


# ---------------------------------------------------------------------------
# CUSUM: slow drift caught; cap bounds clear latency
# ---------------------------------------------------------------------------


def test_cusum_catches_subthreshold_drift():
    eng, spec = _level_engine()
    base = [0.01 + 1e-5 * (i % 3) for i in range(40)]
    _feed(eng, "lat", base)
    # A sustained ~4.5-sigma elevation: under z_thresh=6 per sample, so
    # the z test alone never fires, but CUSUM accumulates ~1.5 per step
    # and crosses h=24 in ~16 steps.
    drift = [0.0325 + 1e-5 * (i % 7) for i in range(30)]
    opened = _feed(eng, "lat", drift)
    assert len(opened) == 1, "CUSUM missed a sub-threshold sustained drift"
    assert opened[0].step_first_anomaly >= 40 + 10


def test_cusum_cap_bounds_clear_latency():
    eng, spec = _level_engine()
    base = [0.01 + 1e-5 * (i % 3) for i in range(40)]
    _feed(eng, "lat", base)
    det = eng._detectors["lat"]
    # A LONG giant excursion: without the cap the sum would grow with
    # excursion length (~15/step here for 120 steps) and take hundreds of
    # clean steps to decay below h.
    _feed(eng, "lat", [0.1 + 1e-4 * (i % 9) for i in range(120)])
    assert det.cusum <= 2.0 * spec.cusum_h
    assert eng.n_open == 1
    # Recovery: cusum drains at k per clean-scored step from at most 2h,
    # then clear_after clean samples close — bounded regardless of the
    # 120-step excursion above.
    bound = int(2.0 * spec.cusum_h / spec.cusum_k) + spec.clear_after + 2
    recovery = [0.01 + 1e-5 * (i % 5) for i in range(bound)]
    _feed(eng, "lat", recovery)
    assert eng.n_open == 0, (
        f"incident still open {bound} steps after recovery "
        f"(cusum={det.cusum:.1f}) — the cap is not bounding clear latency")


# ---------------------------------------------------------------------------
# echo freeze: a sticky rolling-quantile repeat is not fresh evidence
# ---------------------------------------------------------------------------


def test_identical_echoes_never_trip():
    eng, spec = _level_engine()
    base = [0.01 + 1e-5 * (i % 3) for i in range(40)]
    _feed(eng, "lat", base)
    # One environmental spike pins a rolling p99 window: the SAME float
    # repeats every step until the spike ages out. trip_after=3 must not
    # be defeated by those repeats.
    _feed(eng, "lat", [0.2] * 50)
    assert eng.n_opened == 0
    det = eng._detectors["lat"]
    assert det.anom_streak == 1  # frozen at the first observation
    # The spike ages out; fresh healthy samples resume normal scoring.
    _feed(eng, "lat", [0.01 + 1e-5 * (i % 5) for i in range(10)])
    assert det.anom_streak == 0
    assert eng.n_opened == 0


def test_varied_excursion_is_not_frozen():
    # The converse guard: a real excursion perturbs the quantile every
    # step, so freezing must not eat it.
    eng, spec = _level_engine()
    _feed(eng, "lat", [0.01 + 1e-5 * (i % 3) for i in range(40)])
    opened = _feed(eng, "lat", [0.2 + 1e-4 * i for i in range(6)])
    assert len(opened) == 1


# ---------------------------------------------------------------------------
# counters: any positive delta, trip_after=1, CRITICAL
# ---------------------------------------------------------------------------


def test_counter_delta_trips_critical_immediately():
    eng = IncidentEngine(signals=[SignalSpec("requests_failed",
                                             kind="counter")])
    for _ in range(10):
        eng.observe({"requests_failed": 0.0})
    inc = eng.observe({"requests_failed": 3.0})
    assert inc is not None
    assert inc.severity == CRITICAL
    assert inc.detect_latency_steps == 1
    assert inc.signals["requests_failed"]["deviation"] == 3.0
    # Flat counter for clear_after samples closes it.
    for _ in range(SignalSpec("x").clear_after):
        eng.observe({"requests_failed": 3.0})
    assert eng.n_open == 0


def test_counter_joining_open_incident_escalates_severity():
    specs = [SignalSpec("lat", direction=1, min_samples=16),
             SignalSpec("requests_failed", kind="counter")]
    eng = IncidentEngine(signals=specs)
    for i in range(40):
        eng.observe({"lat": 0.01 + 1e-5 * (i % 3), "requests_failed": 0.0})
    opened = []
    for i in range(6):
        inc = eng.observe({"lat": 0.1 + 1e-4 * i, "requests_failed": 0.0})
        if inc:
            opened.append(inc)
    assert len(opened) == 1 and opened[0].severity == WARN
    # Failures start while the WARN incident is open: it escalates in
    # place rather than opening a second incident.
    eng.observe({"lat": 0.1 + 0.01, "requests_failed": 2.0})
    assert eng.n_opened == 1
    assert opened[0].severity == CRITICAL
    assert "requests_failed" in opened[0].signals


# ---------------------------------------------------------------------------
# determinism: same trace, byte-identical incidents
# ---------------------------------------------------------------------------


def test_same_trace_byte_identical_dumps():
    def run():
        eng = IncidentEngine(signals=[
            SignalSpec("lat", direction=1, min_samples=16),
            SignalSpec("requests_failed", kind="counter"),
        ], replica=0)
        log = []
        eng.fault_log_source = lambda: log
        rng = random.Random(7)
        for i in range(200):
            noise = 1e-4 * rng.random()
            lat, failed = 0.01 + noise, 0.0
            if 80 <= i < 120:
                lat += 0.09
                if i >= 85:
                    failed = float(i - 84)
                    log.append(FaultEvent(site="engine.decode",
                                          call_index=i, kind="nan",
                                          spec_index=0, row=0))
            eng.observe({"lat": lat, "requests_failed": failed})
        return eng.dump()
    a, b = run(), run()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert a["opened"] >= 1
    assert a["incidents"][0]["suspects"][0]["site"] == "engine.decode"


# ---------------------------------------------------------------------------
# triage: evidence correlation, scoring, ranking
# ---------------------------------------------------------------------------


def _tripped_engine_with(**sources):
    """Baseline, attach sources, then drive a level shift so triage runs
    with the cursors snapshotted at the first anomalous sample."""
    eng, _ = _level_engine()
    for k, v in sources.items():
        setattr(eng, k, v)
    eng._cursors = eng._read_cursors()
    _feed(eng, "lat", [0.01 + 1e-5 * (i % 3) for i in range(40)])
    opened = _feed(eng, "lat", [0.1 + 1e-4 * i for i in range(6)])
    assert len(opened) == 1
    return eng, opened[0]


def test_triage_fault_site_outranks_responses():
    log = []
    actions = []
    eng, _ = _level_engine()
    eng.fault_log_source = lambda: log
    eng.controller_source = lambda: actions
    eng._cursors = eng._read_cursors()
    _feed(eng, "lat", [0.01 + 1e-5 * (i % 3) for i in range(40)])
    # Evidence arrives DURING the excursion: a delay fault (kind agrees
    # with the latency symptom) and a controller knob move (a response).
    log.extend(FaultEvent(site="comm.allgather", call_index=i,
                          kind="delay", spec_index=0) for i in range(4))
    actions.append({"knob": "n_slots", "delta": -1})
    opened = _feed(eng, "lat", [0.1 + 1e-4 * i for i in range(6)])
    suspects = opened[0].suspects
    assert suspects[0]["site"] == "comm.allgather"
    assert suspects[0]["kind"] == "fault:delay"
    assert suspects[0]["evidence"]["fires"] == 4
    # 8.0 base + 0.4 fires + 2.0 latency-kind agreement
    assert suspects[0]["score"] == pytest.approx(10.4)
    ctrl = [s for s in suspects if s["site"] == "controller.n_slots"]
    assert ctrl and ctrl[0]["score"] < suspects[0]["score"]
    assert "comm.allgather fault:delay -> lat -> WARN" == \
        suspects[0]["chain"]


def test_triage_cursor_excludes_stale_evidence():
    # Faults fired long BEFORE the excursion must not be blamed for it.
    log = [FaultEvent(site="engine.prefill", call_index=i, kind="error",
                      spec_index=0) for i in range(10)]
    eng, inc = _tripped_engine_with(fault_log_source=lambda: log)
    assert not any(s["site"] == "engine.prefill" for s in inc.suspects)


def test_triage_blackbox_and_comm_sources():
    events = [{"seq": 5, "kind": "quarantine"}, {"seq": 6, "kind": "quarantine"}]
    comm = {"allreduce": {"achieved_over_est": 4.0},
            "allgather": {"achieved_over_est": 1.1}}
    eng, inc = _tripped_engine_with(
        blackbox_source=lambda: (5, events),
        comm_source=lambda: comm)
    sites = {s["site"]: s for s in inc.suspects}
    assert "engine.quarantine" in sites
    assert sites["engine.quarantine"]["evidence"]["events"] == 2
    assert "comm.allreduce" in sites          # only the worst site
    assert "comm.allgather" not in sites
    assert sites["comm.allreduce"]["evidence"]["achieved_over_est"] == 4.0


def test_retriage_at_close_picks_up_late_evidence():
    log = []
    eng, _ = _level_engine()
    eng.fault_log_source = lambda: log
    eng._cursors = eng._read_cursors()
    _feed(eng, "lat", [0.01 + 1e-5 * (i % 3) for i in range(40)])
    opened = _feed(eng, "lat", [0.1 + 1e-4 * i for i in range(6)])
    assert opened[0].suspects == []
    # The fault log lands while the incident is open (late attribution).
    log.append(FaultEvent(site="engine.decode", call_index=0, kind="delay",
                          spec_index=0))
    _feed(eng, "lat", [0.01 + 1e-5 * (i % 5) for i in range(20)])
    assert not opened[0].open
    assert opened[0].suspects[0]["site"] == "engine.decode"


# ---------------------------------------------------------------------------
# SLO-breach integration
# ---------------------------------------------------------------------------


def test_slo_breach_opens_critical_with_forensic_summary():
    eng = IncidentEngine(signals=[SignalSpec("lat", min_samples=16)])
    for i in range(5):
        eng.observe({"lat": 0.01 + 1e-5 * i})
    inc = eng.on_slo_breach(
        "tbt",
        detail={"p99": {"value": 0.5, "threshold": 0.1}},
        forensic={"queue_depth": 7, "in_flight": {"a": 1, "b": 2},
                  "requests": {"failed": 3},
                  "blackbox": {"events": [{"kind": "quarantine"},
                                          {"kind": "quarantine"},
                                          {"kind": "preempt"}]},
                  "slo": {"states": {"tbt": "BREACH"}}})
    assert inc.kind == "slo-breach"
    assert inc.severity == CRITICAL
    assert inc.detect_latency_steps == 1
    sig = inc.signals["slo:tbt"]
    assert sig["detail"] == {"p99": 0.5}
    assert inc.forensic == {
        "queue_depth": 7, "in_flight": 2, "requests": {"failed": 3},
        "blackbox_kinds": {"quarantine": 2, "preempt": 1},
        "slo_states": {"tbt": "BREACH"},
    }
    assert eng.stats()["severity_level"] == 2


# ---------------------------------------------------------------------------
# bounded memory: the ring evicts, counters keep the truth
# ---------------------------------------------------------------------------


def test_incident_ring_bounded_with_eviction_count():
    eng = IncidentEngine(signals=[SignalSpec("c", kind="counter")],
                         max_incidents=4)
    total = 0.0
    eng.observe({"c": total})              # first sample sets the baseline
    for _ in range(7):
        total += 1.0
        eng.observe({"c": total})          # trip
        for _ in range(10):
            eng.observe({"c": total})      # clear
    assert eng.n_opened == 7
    assert len(eng.incidents) == 4
    assert eng.n_evicted == 3
    st = eng.stats()
    assert st["total"] == 7 and st["evicted"] == 3 and st["open"] == 0
    assert len(st["ring"]) <= 8


def test_stats_dump_and_perfdb_shapes():
    eng = IncidentEngine(replica=3)
    st = eng.stats()
    assert set(st) == {"open", "total", "closed", "evicted", "steps",
                       "severity_level", "detect_latency_steps", "ring",
                       "annotations"}
    d = eng.dump()
    assert d["replica"] == 3
    assert set(d) == {"replica", "steps", "opened", "closed", "evicted",
                      "incidents", "annotations"}
    from triton_distributed_tpu.obs.perfdb import metric_direction
    s = eng.perfdb_sample()
    assert set(s) == {"incidents_open", "incidents_total",
                      "detect_latency_steps"}
    for k in s:
        assert metric_direction(k) == -1, f"{k} must gate lower-better"


def test_signal_spec_validation():
    with pytest.raises(ValueError):
        SignalSpec("x", direction=0)
    with pytest.raises(ValueError):
        SignalSpec("x", kind="gauge")
    assert len(default_signals()) == 10


# ---------------------------------------------------------------------------
# cross-replica merge
# ---------------------------------------------------------------------------


def _row(first, open_, closed, severity=WARN, signals=None, suspects=None):
    return {
        "id": 0, "kind": "anomaly", "severity": severity,
        "state": "closed" if closed is not None else "open",
        "step_first_anomaly": first, "step_open": open_,
        "step_closed": closed,
        "detect_latency_steps": open_ - first + 1, "replica": None,
        "signals": signals or {}, "suspects": suspects or [],
    }


def test_merge_overlapping_incidents_collapse():
    sus = [{"site": "engine.decode", "kind": "fault:nan", "score": 10.0,
            "evidence": {"fires": 3}, "chain": "x"}]
    dumps = {
        0: {"replica": 0, "opened": 2, "incidents": [
            _row(10, 12, 20, signals={"lat": {"kind": "level"}},
                 suspects=[dict(sus[0], evidence={"fires": 3})]),
            _row(100, 102, 110),
        ]},
        1: {"replica": 1, "opened": 1, "incidents": [
            _row(15, 17, 25, severity=CRITICAL,
                 signals={"requests_failed": {"kind": "counter"}},
                 suspects=[dict(sus[0], evidence={"fires": 2})]),
        ]},
    }
    m = IncidentEngine.merge(dumps)
    assert m["total"] == 2                  # [10..20]+[15..25] merge; [100..110] alone
    assert m["open"] == 0
    assert m["replica_incidents"] == 3
    g = m["ring"][0]
    assert g["replicas"] == [0, 1]
    assert g["step_first_anomaly"] == 10
    assert g["step_closed"] == 25
    assert g["severity"] == CRITICAL        # max across members
    assert set(g["signals"]) == {"r0:lat", "r1:requests_failed"}
    assert g["suspects"][0]["site"] == "engine.decode"
    assert g["suspects"][0]["score"] == 20.0
    assert g["suspects"][0]["evidence"]["fires"] == 5
    lone = m["ring"][1]
    assert lone["replicas"] == [0] and lone["step_closed"] == 110


def test_merge_disjoint_incidents_stay_separate():
    dumps = {
        0: {"replica": 0, "opened": 1, "incidents": [_row(10, 12, 20)]},
        1: {"replica": 1, "opened": 1, "incidents": [_row(50, 52, 60)]},
    }
    m = IncidentEngine.merge(dumps)
    assert m["total"] == 2 and m["open"] == 0
    assert [g["replicas"] for g in m["ring"]] == [[0], [1]]


def test_merge_deterministic_and_empty():
    assert IncidentEngine.merge({}) == {
        "open": 0, "total": 0, "replica_incidents": 0,
        "detect_latency_steps": 0, "severity_level": 0, "ring": []}
    dumps = {
        0: {"replica": 0, "opened": 1, "incidents": [_row(10, 12, 20)]},
        -1: {"replica": None, "opened": 1,
             "incidents": [_row(11, 13, None,
                                signals={"dead": {"kind": "counter"}})]},
    }
    a = IncidentEngine.merge(dumps)
    b = IncidentEngine.merge(dumps)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    # Negative index = the fleet-level engine; its signals prefix "fleet:".
    assert "fleet:dead" in a["ring"][0]["signals"]
