"""Tier-1 wiring for scripts/serve_smoke.py: a few seconds of synthetic
Poisson load through the serving subsystem, failing on pool leaks, lost
requests, or any step retrace beyond the first compile.

An arm whose contract is the host's runs on the plain path here
(``serve_smoke._host_arm_attn``: the gather oracle wherever the fused kernel
would be interpreted); ``test_serve_smoke_short`` and ``test_serve_smoke_kvq``
stay on the interpreted fused kernel. What only an arm's test shows is that
the arm RAISES on a violation of its contract and returns (and records) the
keys asserted here; the unit test that holds each contract's long form is
named in the arm's test."""

import importlib.util
import json
import pathlib

import pytest

_SCRIPT = pathlib.Path(__file__).parent.parent / "scripts" / "serve_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("serve_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_smoke_short():
    m = _load().main(3.0, rate_hz=6.0, seed=0)
    assert m["requests_submitted"] > 0
    assert m["requests_completed"] == m["requests_submitted"]
    assert m["trace_count_decode"] == 1
    assert m["trace_count_prefill"] == 1
    assert m["ttft_s_count"] == m["requests_submitted"]

    # Observability wiring (obs/): latency histograms populated and
    # self-consistent — every generated token is either a request's first
    # (TTFT) or a successor within a residency (TBT); preemption resets the
    # TBT chain, so re-admission first-tokens fall in neither bucket.
    assert m["tbt_s_count"] > 0
    assert (m["ttft_s_count"] + m["tbt_s_count"]
            <= m["tokens_generated"])
    assert m["tbt_s_p50"] >= 0.0 and m["ttft_s_p50"] > 0.0

    # Comm-ledger byte accounting: recorded == analytical wire bytes for
    # all-gather and reduce-scatter (executed on a TPU backend, replayed
    # analytically where Pallas collectives cannot lower — either way the
    # accounting path must agree with perf_model).
    sc = m["ledger_selfcheck"]
    assert sc["consistent"]
    assert sc["ag_bytes"] == sc["ag_expected"] > 0
    assert sc["rs_bytes"] == sc["rs_expected"] > 0
    assert sc["entries"]          # the checked series are present
    for key, entry in sc["entries"].items():
        # Executed (wall-timed) series come joined with one aggregate,
        # which is not a series.
        if key == "roofline_summary":
            continue
        assert entry["bytes_total"] > 0
        assert entry["calls"] + entry["traced_calls"] >= 1


def test_serve_smoke_slo_and_stats_feed(tmp_path):
    """--slo attaches the stock objective set (generous thresholds: a
    healthy short run must end all-OK with zero breaches) and
    --stats-jsonl streams the serve_top feed; both ride the same run."""
    feed = tmp_path / "stats.jsonl"
    m = _load().main(3.0, rate_hz=6.0, seed=0, slo=True,
                     stats_jsonl=str(feed))
    assert m["requests_completed"] == m["requests_submitted"] > 0
    assert m["slo_verdicts"] == {"ttft_p99": "OK", "tbt_p99": "OK",
                                 "error_rate": "OK"}
    assert m["slo_breaches"] == 0
    lines = feed.read_text().strip().splitlines()
    assert lines, "stats stream wrote nothing"
    import json

    from tools import serve_top

    snap = json.loads(lines[-1])
    assert "windows" in snap and "counters" in snap
    frame = serve_top.render(snap)
    assert "slo" in frame and "telemetry" in frame


def test_serve_smoke_fleet_chaos(tmp_path):
    """The --replicas N --chaos contract (ISSUE 11): the seeded replica
    kill quarantines AT LEAST one replica, EVERY survivor request still
    completes (requeue-by-recompute re-serves the drained ones, so
    failed == 0), and no replica retraces. main_fleet raises on any
    violation; the stats feed renders the serve_top fleet table.

    Only here: ``main_fleet``'s own raises, its result's keys and the fleet
    table of the stats feed. The long form of the kill, the drain and the
    requeue, bit for bit against goldens, is
    ``tests/test_fleet.py::test_fleet_kill_survivors_bit_identical`` and
    ``::test_fleet_chaos_same_seed_same_schedule``."""
    feed = tmp_path / "fleet_stats.jsonl"
    m = _load().main_fleet(3.0, rate_hz=6.0, n_replicas=3, seed=0,
                           chaos=True, stats_jsonl=str(feed))
    assert m["requests_submitted"] > 0
    assert m["requests_failed"] == 0
    assert m["requests_completed"] == m["requests_submitted"]
    assert m["quarantines"] >= 1
    assert m["replicas_dead"] >= 1
    assert m["requeues"] >= 0 and m["requeue_exhausted"] == 0
    assert m["faults_injected"] >= 1
    # The state log witnesses the full teardown of the killed replica.
    path = [e["to"] for e in m["state_log"]]
    assert "QUARANTINED" in path and "DRAINING" in path and "DEAD" in path

    import json

    from tools import serve_top

    lines = feed.read_text().strip().splitlines()
    assert lines, "fleet stats stream wrote nothing"
    snap = json.loads(lines[-1])
    assert "fleet" in snap and len(snap["fleet"]["replicas"]) == 3
    frame = serve_top.render(snap)
    assert "fleet" in frame and "routable" in frame


def test_serve_smoke_restore(tmp_path):
    """The --restore contract (ISSUE 18): journaled Poisson load,
    mid-flight checkpoint, simulated power cut, Fleet.restore onto fresh
    replicas — zero requests lost, at least one finishes AFTER the
    restore, and nothing retraces. main_restore raises on any violation
    and records a perfdb sample when asked.

    Only here: ``main_restore``'s raises, the journal's simulated power cut
    under Poisson load and the perfdb record. The long form (every request
    bit-identical to the never-crashed run, at every cut point) is
    ``tests/test_checkpoint.py::test_fleet_restore_bit_identical`` and
    ``::test_kill_point_sweep``."""
    db = tmp_path / "perf.jsonl"
    m = _load().main_restore(1.5, rate_hz=8.0, seed=0,
                             perfdb_path=str(db))
    assert m["requests_submitted"] > 0
    assert m["requests_lost"] == 0 and m["requests_failed"] == 0
    assert m["requests_completed"] == m["requests_submitted"]
    assert m["finished_after_restore"] >= 1
    assert m["restored_requests"] >= 1
    assert m["recovery_s"] >= 0.0
    rec = json.loads(db.read_text().strip().splitlines()[-1])
    assert rec["suite"] == "serve_smoke_restore"
    assert rec["metrics"]["requests_submitted"] == m["requests_submitted"]


def test_serve_smoke_adaptive(tmp_path):
    """The --adaptive contract (ISSUE 12): the overload burst drives the
    self-calibrated TTFT objective to WARN, the attached Controller
    actuates under pressure (level >= 1 moves), recovery walks the SLO
    back to OK with ZERO breaches, and the knob sweep never retraces
    either compiled step (main_adaptive raises on any violation — this
    test runs that contract under tier 1)."""
    feed = tmp_path / "adaptive_stats.jsonl"
    m = _load().main_adaptive(seed=0, stats_jsonl=str(feed))
    assert m["requests_completed"] == m["requests_submitted"] > 0
    assert m["warn_transitions"] >= 1
    assert m["slo_breaches"] == 0
    assert m["slo_verdicts"] == {"ttft_q50": "OK"}
    assert m["pressured_actions"] >= 1
    assert m["controller"]["actions"] >= m["pressured_actions"]
    assert m["trace_count_decode"] == 1
    assert m["trace_count_prefill"] == 1
    # Journey attribution sees the overload (ISSUE 13): the burst queues
    # many waves deep, so the mean queue-wait fraction is nonzero and
    # every bucket mean stays a valid fraction.
    assert m["journey_mean_fracs"]["queue"] > 0.0
    assert all(0.0 <= v <= 1.0 for v in m["journey_mean_fracs"].values())

    # The stats feed carries the controller block; serve_top renders it
    # as the ctl pane.
    import json

    from tools import serve_top

    lines = feed.read_text().strip().splitlines()
    assert lines, "adaptive stats stream wrote nothing"
    snap = json.loads(lines[-1])
    assert "controller" in snap and "knobs" in snap["controller"]
    # ... and the journey block, rendered as the slowest-journeys pane.
    assert "journey" in snap and "mean_fracs" in snap["journey"]
    frame = serve_top.render(snap)
    assert "ctl" in frame and "knobs" in frame
    assert "journeys" in frame


def test_serve_smoke_spec(tmp_path):
    """The --spec contract (ISSUE 16): the same deterministic workload
    through a speculative and a plain engine must produce byte-identical
    outputs with a NONZERO number of accepted draft tokens and zero
    retraces on either engine (main_spec raises on any violation); the
    stats feed carries the spec block serve_top renders as its pane.

    Only here: ``main_spec``'s raises (divergence from the plain engine, no
    proposal, no accept), its result's keys and the feed's spec block: host
    logic, on the plain path. The long form through the fused verify rows,
    with preemption and a 66-token request, is ``tests/test_speculative.py
    ::test_spec_ngram_bit_identical_with_preemption``."""
    feed = tmp_path / "spec_stats.jsonl"
    m = _load().main_spec(seed=0, n_requests=8, gen=16,
                          stats_jsonl=str(feed))
    assert m["requests_completed"] == m["requests_submitted"] > 0
    assert m["divergent_requests"] == 0
    assert m["spec_accepted_tokens"] > 0
    assert m["spec_proposed_tokens"] >= m["spec_accepted_tokens"]
    assert m["spec"]["drafter"] == "ngram"
    assert m["trace_count_decode"] <= 1
    assert m["trace_count_prefill"] == 1

    import json

    lines = feed.read_text().strip().splitlines()
    assert lines, "spec stats stream wrote nothing"
    snap = json.loads(lines[-1])
    assert "spec" in snap and "accept_rate" in snap["spec"]


def test_serve_smoke_kvq(tmp_path):
    """The --kvq contract (ISSUE 20): a quantized (int8) engine on a
    preemption-tight pool serves a shared-prefix workload cold then warm
    on the SAME engine; the warm outputs — produced from CoW-adopted
    quantized cached blocks — must be byte-identical to cold, with nonzero
    prefix hits, actual preemption churn, and trace_counts {1,1} (main_kvq
    raises on any violation — this test runs that contract under tier 1
    and pins the perfdb keys).

    Only here: ``main_kvq``'s raises, that cold and warm are ONE engine,
    and the perfdb keys it writes. ``gen`` is the arm's own parameter and
    no property of int8 (the arm's default stays 64, and its pool follows
    ``gen``): 8 tokens a request are the fewest whose decode still preempts
    (2) and whose warm pass still hits (5), some 45 steps of the interpreted
    fused kernel where 64 were 330 and 290 s of the 300 a test may take.
    The long form, 64 decode steps of warm == cold in the quantized domain
    against a cache-less engine, is ``tests/test_prefix_cache.py
    ::test_warm_cache_bit_identical_with_churn[fused-int8]``, and after
    rollback ``tests/test_speculative.py
    ::test_spec_rollback_then_prefix_cache_warm_equals_cold[int8]``."""
    db = tmp_path / "perf.jsonl"
    m = _load().main_kvq(seed=0, gen=8, perfdb_path=str(db))
    assert m["kv_dtype"] == "int8"
    assert m["kv_fingerprint"] == "int8:rowmax:v1:paired"
    assert m["warm_bit_identical"] is True
    assert m["gen"] == 8
    assert m["requests_completed"] == m["requests_submitted"] > 0
    assert m["prefix_hits_warm"] > 0
    assert m["preemptions"] >= 1
    assert m["trace_count_decode"] == 1
    assert m["trace_count_prefill"] == 1
    rec = json.loads(db.read_text().strip().splitlines()[-1])
    assert rec["suite"] == "serve_smoke_kvq"
    assert rec["meta"]["kv_dtype"] == "int8"
    assert rec["metrics"]["kvq_prefix_hits"] > 0
    assert rec["metrics"]["kvq_preemptions"] >= 1


def test_serve_smoke_chaos():
    """The --chaos mode's graceful-degradation contract: the engine rides
    out injected transient errors and NaN-poisoned rows, finishing with
    at least one quarantined AND at least one successful request, full
    accounting, a drained pool, and zero retraces (main() raises on any
    violation — this test exists to run that contract under tier 1).

    Only here: ``main()``'s chaos raises under Poisson load. The long form
    (survivors bit-identical to a fault-free run, retries invisible) is
    ``tests/test_resilience.py
    ::test_quarantined_request_leaves_survivors_bit_identical`` and
    ``::test_chaos_plan_run_completes_and_accounts``."""
    m = _load().main(3.0, rate_hz=6.0, seed=0, chaos=True)
    assert m["requests_submitted"] > 0
    assert m["requests_failed"] >= 1
    assert m["requests_completed"] >= 1
    assert (m["requests_completed"] + m["requests_failed"]
            == m["requests_submitted"])
    assert m["trace_count_decode"] == 1
    assert m["trace_count_prefill"] == 1
    # the fault plane actually exercised the retry path
    assert m.get("step_retries", 0) + m.get("alloc_retries", 0) > 0


def test_serve_smoke_whatif(tmp_path):
    """The --whatif contract (ISSUE 19): a short discretized-Poisson run
    is recorded by the always-on ServeTrace, the baseline replay through
    ReplayHarness is bit-identical (zero lost, zero retraces), and the
    planted full-prefill counterfactual produces a ranked report with a
    strictly positive goodput delta (main_whatif raises on any violation
    — this test runs that contract under tier 1 and pins the perfdb
    keys)."""
    db = tmp_path / "perf.jsonl"
    m = _load().main_whatif(seed=0, n_requests=6, perfdb_path=str(db))
    assert m["requests_completed"] == m["requests_submitted"] == 6
    assert m["requests_failed"] == 0
    assert m["whatif_baseline_bit_identical"] is True
    assert m["whatif_lost_requests"] == 0
    assert m["whatif_retraces"] == 0
    assert m["whatif_goodput_delta"] > 0.0
    assert (m["whatif_winner_goodput"]
            == pytest.approx(m["whatif_baseline_goodput"]
                             + m["whatif_goodput_delta"], abs=2e-6))
    assert m["cost_model_source"] in ("stock", "calibrated")
    assert m["trace_count_decode"] == 1
    assert m["trace_count_prefill"] == 1
    rec = json.loads(db.read_text().strip().splitlines()[-1])
    assert rec["suite"] == "serve_smoke_whatif"
    assert rec["metrics"]["whatif_lost_requests"] == 0
    assert rec["metrics"]["whatif_goodput_delta"] > 0.0


def test_serve_smoke_incidents(tmp_path):
    """The --incidents mode's detection contract end-to-end: a clean
    closed-loop phase opens ZERO incidents (precision), the seeded NaN
    chaos phase opens at least one CRITICAL incident whose top-ranked
    suspect is the injected fault site with near-immediate detection
    (recall + triage), and the always-on observer never retraces the
    compiled steps (main_incidents() raises on any violation — this test
    runs that contract under tier 1 and pins the perfdb keys)."""
    db = tmp_path / "perf.jsonl"
    m = _load().main_incidents(seed=0, perfdb_path=str(db))
    assert m["requests_failed"] >= 1
    assert m["faults_injected"] >= 1
    assert m["incidents_opened"] >= 1
    assert m["incident_severity"] == "CRITICAL"
    assert m["detect_latency_steps"] <= 4
    assert m["top_suspect"]["site"] == "engine.decode"
    assert m["top_suspect"]["kind"] == "fault:nan"
    assert "requests_failed" in m["top_suspect"]["chain"]
    assert m["trace_count_decode"] == 1
    assert m["trace_count_prefill"] == 1
    rows = [json.loads(line) for line in db.read_text().splitlines()]
    assert rows and rows[-1]["suite"] == "serve_smoke_incidents"
    metrics = rows[-1]["metrics"]
    assert metrics["incidents_total"] >= 1
    assert metrics["detect_latency_steps"] <= 4
