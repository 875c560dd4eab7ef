"""Unit tests for the unified observability layer (triton_distributed_tpu/obs):
span tracer (nesting, timing monotonicity, Chrome trace-event schema),
metrics registry (labels, flat-schema collisions, delta snapshots,
Prometheus round-trip), and the comm ledger (byte accounting vs the perf
model's analytical counts, disabled-path no-ops, traced-vs-timed regimes).
"""

import json
import threading

import jax
import jax.numpy as jnp
import pytest

from triton_distributed_tpu.obs import comm_ledger
from triton_distributed_tpu.obs import metrics as metrics_mod
from triton_distributed_tpu.obs import trace
from triton_distributed_tpu.obs.metrics import (
    Histogram,
    Metrics,
    parse_prometheus,
)
from triton_distributed_tpu.obs.window import (
    DEFAULT_BOUNDS,
    WindowRing,
)
from triton_distributed_tpu.runtime import perf_model as pm


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------


@pytest.fixture
def tracer():
    t = trace.Tracer()
    t.enable()
    yield t
    t.disable()
    t.reset()


def test_span_nesting_and_monotonic_timing(tracer):
    with tracer.span("outer"):
        with tracer.span("mid"):
            with tracer.span("inner"):
                pass
    recs = {r.name: r for r in tracer.records}
    assert set(recs) == {"outer", "mid", "inner"}
    assert recs["outer"].depth == 0
    assert recs["mid"].depth == 1
    assert recs["inner"].depth == 2
    for r in tracer.records:
        assert r.t_end >= r.t_start
    # Inner spans close first (stack discipline) and nest inside outer.
    assert recs["inner"].t_start >= recs["mid"].t_start
    assert recs["mid"].t_start >= recs["outer"].t_start
    assert recs["inner"].t_end <= recs["outer"].t_end


def test_span_disabled_is_noop_and_shared_context():
    t = trace.Tracer()
    assert t.span("a") is t.span("b")       # shared nullcontext: no allocs
    with t.span("a"):
        pass
    assert len(t) == 0


def test_span_records_attrs_and_exceptions(tracer):
    with pytest.raises(RuntimeError):
        with tracer.span("failing", tag="x"):
            raise RuntimeError("boom")
    (r,) = tracer.records
    assert r.name == "failing" and r.attrs == {"tag": "x"}
    assert r.t_end >= r.t_start


def test_instant_and_async_events(tracer):
    tracer.instant("tick", n=1)
    tracer.async_begin("request", "r1", prompt_len=4)
    tracer.async_end("request", "r1", tokens=2)
    phases = [r.phase for r in tracer.records]
    assert phases == ["i", "b", "e"]
    b, e = tracer.records[1], tracer.records[2]
    assert b.async_id == e.async_id == "r1"
    assert e.t_start >= b.t_start


def test_chrome_trace_schema(tracer, tmp_path):
    with tracer.span("work", k=1):
        tracer.instant("mark")
    tracer.async_begin("request", 7)
    tracer.async_end("request", 7)
    path = tracer.export_chrome_trace(str(tmp_path / "td"))
    payload = json.loads(open(path).read())
    events = payload["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    data = [e for e in events if e["ph"] != "M"]
    assert len(data) == 4
    # Metadata events label the merged rows: one process_name per pid plus
    # a thread_name per host thread seen in the buffer.
    pid = payload["metadata"]["process_index"]
    pnames = [e for e in meta if e["name"] == "process_name"]
    assert [e["args"]["name"] for e in pnames] == [f"rank {pid}"]
    assert any(e["name"] == "thread_name" for e in meta)
    by_phase = {e["ph"]: e for e in data}
    assert set(by_phase) == {"X", "i", "b", "e"}
    x = by_phase["X"]
    assert x["name"] == "work" and x["dur"] >= 0 and x["args"] == {"k": 1}
    for e in data:
        assert isinstance(e["ts"], float) and "pid" in e and "tid" in e
    assert by_phase["b"]["id"] == by_phase["e"]["id"] == "7"
    assert by_phase["i"]["s"] == "t"
    # Per-rank file naming + mergeability.
    assert path.endswith(f"trace.p{pid}.json")
    merged = trace.merge_chrome_traces(str(tmp_path / "td"))
    merged_events = json.loads(open(merged).read())["traceEvents"]
    assert len([e for e in merged_events if e["ph"] != "M"]) == 4


def test_ring_buffer_bounded():
    t = trace.Tracer(capacity=8)
    t.enable()
    for i in range(50):
        t.instant(f"e{i}")
    assert len(t) == 8
    assert t.records[0].name == "e42"      # oldest evicted
    # Evictions are COUNTED, never silent, and reset() clears the counter.
    assert t.dropped == 42
    t.reset()
    assert t.dropped == 0 and len(t) == 0


def test_dropped_spans_surface_in_chrome_export(tmp_path):
    t = trace.Tracer(capacity=4)
    t.enable()
    for i in range(10):
        t.instant(f"e{i}")
    path = t.export_chrome_trace(str(tmp_path))
    meta = json.loads(open(path).read())["metadata"]
    # A truncated trace announces itself: the export metadata carries both
    # how much survived and how much the ring wrap evicted.
    assert meta["recorded_spans"] == 4
    assert meta["dropped_spans"] == 6


def test_module_level_dropped_spans_counter():
    # The serving gauge reads the module-level counter; don't resize the
    # process-global ring (other tests share it) — the default 64k ring
    # simply shouldn't wrap here, so the counter stays 0 and resets clean.
    trace.reset()
    assert trace.dropped_spans() == 0
    with trace.tracing():
        trace.instant("d0")
    assert trace.dropped_spans() == 0
    assert trace.get_tracer().dropped == trace.dropped_spans()
    trace.reset()


def test_module_level_tracing_context_restores_state():
    assert not trace.enabled()
    with trace.tracing():
        assert trace.enabled()
        with trace.span("s"):
            pass
    assert not trace.enabled()
    assert any(r.name == "s" for r in trace.get_tracer().records)
    trace.reset()


def test_group_profile_nested_reentry_is_noop(tmp_path):
    # jax.profiler.start_trace raises on double entry; the obs version
    # guards it (and pre-creates the directory). CPU jax still runs the
    # profiler machinery, so this exercises the real path.
    with trace.group_profile("outer", dir=str(tmp_path)):
        with trace.group_profile("inner", dir=str(tmp_path)):
            jnp.square(jnp.arange(8.0)).block_until_ready()
    assert (tmp_path / "outer").is_dir()
    assert not (tmp_path / "inner").exists()    # inner was a guarded no-op


def test_group_profile_disabled_runs_nothing(tmp_path):
    with trace.group_profile("off", enabled=False, dir=str(tmp_path)):
        pass
    assert not (tmp_path / "off").exists()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_histogram_stats():
    h = Histogram()
    for v in [1.0, 2.0, 3.0, 4.0]:
        h.observe(v)
    assert h.count == 4 and h.mean == 2.5 and h.sum == 10.0
    assert h.percentile(50) == 2.0
    assert h.percentile(100) == 4.0
    assert Histogram().percentile(50) == 0.0


def test_metrics_flat_schema_and_labels():
    m = Metrics()
    m.inc("req", 2.0)
    m.set_gauge("depth", 3.0)
    m.observe("lat_s", 0.1, labels={"axis": "tp"})
    m.observe("lat_s", 0.3, labels={"axis": "tp"})
    d = m.as_dict()
    assert d["req"] == 2.0 and d["depth"] == 3.0
    assert d["lat_s{axis=tp}_count"] == 2.0
    assert d["lat_s{axis=tp}_p50"] == 0.1
    # Label order never makes a second series.
    m.observe("x", 1.0, labels={"b": "2", "a": "1"})
    m.observe("x", 2.0, labels={"a": "1", "b": "2"})
    assert m.as_dict()["x{a=1,b=2}_count"] == 2.0


def test_as_dict_collision_raises():
    m = Metrics()
    m.observe("ttft_s", 0.5)
    m.inc("ttft_s_count")          # collides with the histogram's flat key
    with pytest.raises(ValueError, match="collision.*ttft_s_count"):
        m.as_dict()


def test_metrics_delta_snapshot():
    m = Metrics()
    m.inc("tok", 5)
    m.observe("lat", 1.0)
    snap = m.snapshot()
    d0 = m.delta(snap)
    assert d0 == {}                # nothing changed since the snapshot
    m.inc("tok", 3)
    m.observe("lat", 9.0)
    d = m.delta(snap)
    assert d["tok"] == 3.0
    assert d["lat_count"] == 1.0 and d["lat_p50"] == 9.0   # new obs only
    assert m.delta(None)["tok"] == 8.0                     # since creation


def test_prometheus_roundtrip():
    m = Metrics()
    m.inc("requests", 4, labels={"kind": "prefill"})
    m.set_gauge("queue_depth", 2.0)
    m.observe("ttft_s", 0.25)
    m.observe("ttft_s", 0.75)
    text = m.to_prometheus()
    assert "# TYPE requests_total counter" in text
    assert "# TYPE ttft_s histogram" in text
    parsed = parse_prometheus(text)
    assert parsed["requests_total{kind=prefill}"] == 4.0
    assert parsed["queue_depth"] == 2.0
    assert parsed["ttft_s_count"] == 2.0
    assert parsed["ttft_s_sum"] == 1.0
    assert parsed["ttft_s{quantile=0.5}"] == 0.25
    # Real-histogram exposition: cumulative _bucket{le=...} series over the
    # fixed bounds, closed by the +Inf bucket == total count.
    assert parsed["ttft_s_bucket{le=+Inf}"] == 2.0
    bucket_vals = [v for k, v in parsed.items()
                   if k.startswith("ttft_s_bucket{")]
    assert len(bucket_vals) == len(DEFAULT_BOUNDS) + 1
    assert bucket_vals == sorted(bucket_vals)        # cumulative
    # 0.25 and 0.75 both land below 1.0: the le=1 bucket already sees both.
    assert parsed["ttft_s_bucket{le=1}"] == 2.0


def test_prometheus_bucket_counts_match_histogram():
    m = Metrics()
    vals = [0.0005, 0.003, 0.003, 0.02, 0.9, 50.0, 1e4]   # incl. overflow
    for v in vals:
        m.observe("lat_s", v)
    parsed = parse_prometheus(m.to_prometheus())
    h = m.histograms["lat_s"]
    # Every finite cumulative bucket agrees with the histogram's own
    # cumulative_buckets(); +Inf is the total (overflow included).
    for le, cum in h.cumulative_buckets():
        assert parsed[f"lat_s_bucket{{le={le:g}}}"] == float(cum)
    assert parsed["lat_s_bucket{le=+Inf}"] == float(len(vals))
    assert parsed["lat_s_sum"] == pytest.approx(sum(vals))


def test_histogram_bounded_reservoir_and_exact_accumulators():
    h = Histogram(max_samples=64)
    n = 10_000
    for i in range(n):
        h.observe(float(i))
    # The reservoir is bounded at max_samples (most recent kept)...
    assert len(h.samples) == 64
    assert list(h.samples)[0] == float(n - 64)
    # ...while count/sum/mean/min/max stay EXACT via running accumulators.
    assert h.count == n
    assert h.sum == pytest.approx(n * (n - 1) / 2.0)
    assert h.mean == pytest.approx((n - 1) / 2.0)
    assert h.min == 0.0 and h.max == float(n - 1)
    # Percentiles read the trailing reservoir only.
    assert h.percentile(0) == float(n - 64)
    assert h.tail(3) == [float(n - 3), float(n - 2), float(n - 1)]


def test_to_prometheus_cost_independent_of_observation_count():
    """Scrape cost regression: exposition reads running accumulators and
    fixed bucket arrays, so a registry that has absorbed 100k observations
    must scrape in roughly the same time as one that absorbed 100 — a
    linear full-list scan per scrape would blow this bound immediately."""
    import timeit

    small, big = Metrics(), Metrics()
    for i in range(100):
        small.observe("lat_s", i * 1e-3)
    for i in range(100_000):
        big.observe("lat_s", i * 1e-3)
    k = 20
    t_small = timeit.timeit(small.to_prometheus, number=k)
    t_big = timeit.timeit(big.to_prometheus, number=k)
    # Bounded-reservoir sorts differ (100 vs 8192 retained samples) but the
    # cost must not scale with the 1000x observation-count gap. Generous
    # slack for shared-CI noise.
    assert t_big <= 10.0 * t_small + 0.2, (t_small, t_big)


# ---------------------------------------------------------------------------
# comm ledger
# ---------------------------------------------------------------------------


@pytest.fixture
def led():
    led = comm_ledger.CommLedger()
    led.enable()
    return led


def test_ledger_disabled_records_nothing():
    led = comm_ledger.CommLedger()
    led.record("all_gather", axis="tp", world=8, nbytes=1024)
    out = led.timed(lambda: jnp.ones((4,)), "all_gather", axis="tp",
                    world=8, nbytes=1024)
    assert out.shape == (4,)
    assert len(led) == 0 and led.snapshot() == {}


def test_ledger_series_aggregation(led):
    for _ in range(3):
        led.record("all_gather", axis="tp", world=8, nbytes=100.0,
                   method="ring_1d", est_s=1e-4)
    led.record("all_gather", axis="tp", world=8, nbytes=7.0, method="ll")
    ag = {e.method: e for e in led.get("all_gather")}
    assert ag["ring_1d"].calls == 3 and ag["ring_1d"].bytes_total == 300.0
    assert ag["ring_1d"].est_s_total == pytest.approx(3e-4)
    assert ag["ll"].bytes_total == 7.0
    assert led.bytes_for("all_gather") == 307.0
    snap = led.snapshot()
    assert "all_gather[ring_1d,axis=tp,world=8]" in snap


def test_ledger_timed_records_wall_clock(led):
    out = led.timed(lambda: jnp.arange(8.0) * 2, "all_reduce", axis="tp",
                    world=4, nbytes=64, method="one_shot", est_s=1e-6)
    assert float(out[1]) == 2.0
    (e,) = led.get("all_reduce")
    assert e.calls == 1 and e.wall_samples == 1 and e.wall_s_total > 0
    assert "achieved_over_est" in e.as_dict()


def test_ledger_timed_under_trace_falls_back_to_traced(led):
    @jax.jit
    def f(x):
        return led.timed(lambda: x * 2, "all_gather", axis="tp", world=8,
                         nbytes=512)

    f(jnp.ones((4,)))
    (e,) = led.get("all_gather")
    # Trace-time wall clocks measure compilation: must record as traced.
    assert e.traced_calls == 1 and e.calls == 0 and e.wall_samples == 0
    assert e.bytes_total == 512.0


def test_ledger_bytes_match_analytical_wire_bytes(led, mesh8):
    """The acceptance invariant: ledger bytes == perf_model analytical
    bytes for AG and RS, via the exact wire_bytes_* helpers the kernel
    wrappers call."""
    world = mesh8.shape["tp"]
    x = jnp.ones((world, 4, 128), jnp.float32)
    shard = x.nbytes // world
    led.record("all_gather", axis="tp", world=world,
               nbytes=pm.wire_bytes_all_gather(shard, world))
    assert led.bytes_for("all_gather") == (world - 1) * shard

    per_dev = world * 4 * 128 * 4
    led.record("reduce_scatter", axis="tp", world=world,
               nbytes=pm.wire_bytes_reduce_scatter(per_dev, world))
    assert led.bytes_for("reduce_scatter") == (world - 1) * per_dev // world


def test_wire_bytes_formulas():
    # All-gather: each device receives world-1 shards.
    assert pm.wire_bytes_all_gather(100, 8) == 700
    assert pm.wire_bytes_all_gather(100, 1) == 0
    # Reduce-scatter: each device sends world-1 chunks of nbytes/world.
    assert pm.wire_bytes_reduce_scatter(800, 8) == 700
    # All-reduce: one-shot gathers everything; two-shot is RS + AG.
    assert pm.wire_bytes_all_reduce(800, 8, "one_shot") == 7 * 800
    assert pm.wire_bytes_all_reduce(800, 8, "two_shot") == 2 * 700
    # All-to-all: world-1 of world chunks leave each device.
    assert pm.wire_bytes_all_to_all(800, 8) == 700


def test_ledger_selfcheck_consistent(mesh8):
    sc = comm_ledger.selfcheck(mesh=mesh8, axis="tp")
    assert sc["consistent"]
    assert sc["ag_bytes"] == sc["ag_expected"] > 0
    assert sc["rs_bytes"] == sc["rs_expected"] > 0
    assert sc["world"] == mesh8.shape["tp"]
    assert sc["ag_mode"] in ("executed", "analytical")
    # The check leaves the process-global ledger exactly as it found it.
    assert comm_ledger.snapshot() == {}
    assert not comm_ledger.enabled()


def test_ledger_selfcheck_covers_all_reduce_and_all_to_all(mesh8):
    """The selfcheck invariant extends to the reducing and permuting
    families: recorded bytes must equal the analytical wire bytes for AR
    (at whatever method the wrapper's own dispatch picks) and EP a2a."""
    sc = comm_ledger.selfcheck(mesh=mesh8, axis="tp")
    for fam in ("ar", "a2a"):
        assert sc[f"{fam}_bytes"] == sc[f"{fam}_expected"] > 0
        assert sc[f"{fam}_mode"] in ("executed", "analytical")
    assert sc["consistent"]


def test_ledger_selfcheck_executes_every_family(mesh8):
    """On the 8-device CPU mesh all four collectives RUN under the
    interpreter: selfcheck's arrays stay under the interpreter's buffer
    ceiling (tests/conftest.py), so none falls back to the analytical
    replay and none deadlocks."""
    sc = comm_ledger.selfcheck(mesh=mesh8, axis="tp")
    for fam in ("ag", "rs", "ar", "a2a"):
        assert sc[f"{fam}_mode"] == "executed", fam
    assert sc["consistent"]


def test_instrumented_all_gather_records_when_enabled(mesh8):
    """End-to-end through the real kernel wrapper: enabling the ledger and
    calling ``all_gather`` must produce a ledger entry whose bytes match
    the analytical count — whether the Pallas kernel executes (TPU) or
    dies in lowering (CPU hosts without interpreter support), the wrapper's
    accounting math is the thing under test, so a lowering failure falls
    back to replaying the record with the same formula."""
    from triton_distributed_tpu.kernels.allgather import all_gather

    world = mesh8.shape["tp"]
    x = jnp.ones((world, 4, 128), jnp.float32)
    expected = pm.wire_bytes_all_gather(x.nbytes // world, world)
    with comm_ledger.ledger(reset_first=True):
        try:
            jax.block_until_ready(all_gather(x, mesh=mesh8, axis="tp"))
        except Exception:  # noqa: BLE001 — no Pallas lowering on this host
            comm_ledger.record("all_gather", axis="tp", world=world,
                               nbytes=expected, method="analytical")
        assert comm_ledger.get_ledger().bytes_for("all_gather") == expected
    comm_ledger.reset()


def test_disabled_ledger_kernel_path_stays_empty(mesh8):
    """With the ledger disabled the instrumented wrapper must not record
    (the near-zero-overhead default path)."""
    from triton_distributed_tpu.kernels.allgather import all_gather

    assert not comm_ledger.enabled()
    world = mesh8.shape["tp"]
    x = jnp.ones((world, 4, 128), jnp.float32)
    try:
        all_gather(x, mesh=mesh8, axis="tp")
    except Exception:  # noqa: BLE001
        pass
    assert comm_ledger.snapshot() == {}


def test_ledger_thread_safety(led):
    def worker():
        for _ in range(200):
            led.record("all_gather", axis="tp", world=8, nbytes=1.0)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    (e,) = led.get("all_gather")
    assert e.calls == 800 and e.bytes_total == 800.0


# ---------------------------------------------------------------------------
# roofline attribution
# ---------------------------------------------------------------------------


from triton_distributed_tpu.obs import roofline  # noqa: E402


V5E = pm.match_hardware("tpu v5 lite")
# Synthetic chip with an absurdly fat interconnect: forces the HBM branch
# for wired (world > 1) collectives, which no real TPU row exercises.
FAT_ICI = pm.Hardware("fat-ici", 1e15, 1e9, 1e12, 6, 1e-6, 25e9, 10e-6)


def test_collective_bound_world1_rides_hbm():
    # Loopback / degenerate axis: no wire, the DMA rides HBM.
    bound, bound_s = roofline.collective_bound(
        "all_gather", nbytes=1e6, world=1, hw=V5E)
    assert bound == "hbm"
    assert bound_s == pytest.approx(2.0 * 1e6 / V5E.hbm_bw)


def test_collective_bound_wired_world_is_ici_on_real_hw():
    # On every real TPU row the aggregate ICI egress is the slower pipe.
    bound, bound_s = roofline.collective_bound(
        "all_gather", nbytes=1e6, world=8, hw=V5E)
    assert bound == "ici"
    assert bound_s == pytest.approx(
        1e6 / (V5E.ici_link_bw * V5E.ici_links))
    # Reducing collectives carry the 3x HBM touch but stay ICI-bound here.
    bound_rs, _ = roofline.collective_bound(
        "reduce_scatter", nbytes=1e6, world=8, hw=V5E)
    assert bound_rs == "ici"


def test_collective_bound_hbm_branch_when_ici_is_free():
    bound, bound_s = roofline.collective_bound(
        "reduce_scatter", nbytes=1e6, world=8, hw=FAT_ICI)
    assert bound == "hbm"
    assert bound_s == pytest.approx(3.0 * 1e6 / FAT_ICI.hbm_bw)


def test_classify_step_compute_vs_hbm():
    big_flops = roofline.classify_step(flops=1e12, hbm_bytes=1e3,
                                       wall_s=1e-2, hw=V5E)
    assert big_flops.bound == "compute"
    assert big_flops.achieved_over_bound == pytest.approx(
        1e-2 / (1e12 / V5E.peak_bf16_flops))
    big_bytes = roofline.classify_step(flops=1e3, hbm_bytes=1e9,
                                       wall_s=None, hw=V5E)
    assert big_bytes.bound == "hbm"
    assert big_bytes.achieved_over_bound is None     # never timed


def test_attribute_joins_ledger_snapshot(led):
    led.record("all_gather", axis="tp", world=8, nbytes=1e6,
               method="ring_1d", wall_s=1e-3)
    led.record("ep_all_to_all", axis="ep", world=8, nbytes=2e6,
               method="stacked")                      # bytes only, no wall
    recs = roofline.attribute(led.snapshot(roofline=False), hw=V5E)
    ag = recs["all_gather[ring_1d,axis=tp,world=8]"]
    assert ag.bound == "ici" and ag.calls == 1
    assert ag.bytes_per_call == 1e6
    assert ag.achieved_s == pytest.approx(1e-3)
    # achieved >= bound: the efficiency fraction is >= 1 by construction.
    assert ag.achieved_over_bound == pytest.approx(1e-3 / ag.bound_s)
    assert ag.achieved_over_bound > 1.0
    a2a = recs["ep_all_to_all[stacked,axis=ep,world=8]"]
    assert a2a.achieved_s is None and a2a.achieved_over_bound is None
    assert a2a.bound in ("ici", "hbm")

    summ = roofline.summary(recs)
    assert summ["sites"] == 2 and summ["timed_sites"] == 1
    assert summ["worst_site"] == ag.site
    assert summ["worst_achieved_over_bound"] == pytest.approx(
        ag.achieved_over_bound, rel=1e-3)
    assert roofline.summary({}) == {}


def test_snapshot_joins_roofline_when_timed(led):
    led.record("all_gather", axis="tp", world=8, nbytes=1e6,
               method="ring_1d", wall_s=1e-3)
    snap = led.snapshot()
    e = snap["all_gather[ring_1d,axis=tp,world=8]"]
    assert e["roofline_bound"] in ("ici", "hbm")
    assert e["achieved_over_bound"] > 0
    assert snap["roofline_summary"]["sites"] == 1
    # JSON-ready end to end.
    json.dumps(snap)


def test_snapshot_skips_roofline_when_nothing_timed(led):
    led.record("all_gather", axis="tp", world=8, nbytes=1e6)
    snap = led.snapshot()
    assert "roofline_summary" not in snap
    assert "roofline_bound" not in snap["all_gather[auto,axis=tp,world=8]"]


# ---------------------------------------------------------------------------
# perf_model speeds-and-feeds single source of truth (bench.py delegates)
# ---------------------------------------------------------------------------


def test_peak_bf16_tflops_single_source():
    assert pm.peak_bf16_tflops("TPU v5 lite") == pytest.approx(197.0)
    # Marketing / short spellings resolve through the alias table.
    assert pm.peak_bf16_tflops("v5e") == pytest.approx(197.0)
    assert pm.peak_bf16_tflops("TPU v6e") == pytest.approx(918.0)
    # bench.py's plausibility slack scales the peak...
    assert pm.peak_bf16_tflops("TPU v4", tolerance=1.02) == pytest.approx(
        275.0 * 1.02)
    # ...and its unknown-device fallback returns the default UNSCALED.
    assert pm.peak_bf16_tflops("quantum abacus", tolerance=1.02,
                               default=1000.0) == 1000.0
    assert pm.peak_bf16_tflops("quantum abacus") == pytest.approx(197.0)


def test_hbm_gbps_from_table():
    assert pm.hbm_gbps(V5E) == pytest.approx(819.0)
    assert pm.hbm_gbps() > 0          # detect_hardware fallback path


def test_prometheus_hostile_label_values_roundtrip():
    """Structural characters in label VALUES — quotes, backslashes,
    newlines, commas, braces, equals — must survive exposition and parse
    back to the exact internal series key. Both sides escape: the
    exposition writes 0.0.4 quoted values, the internal flat key
    backslash-escapes its own structural set; a mismatch on either side
    makes the round-trip key unsplittable or ambiguous."""
    hostile = [
        'a,b=c',                 # internal structural chars
        'quo"te',                # exposition structural char
        'back\\slash',
        'new\nline',
        'brace}close{open',
        '\\,=}"\n\\\\',          # everything at once, incl. trailing run
        '',                      # empty value
    ]
    m = Metrics()
    for i, v in enumerate(hostile):
        m.set_gauge("g", float(i), labels={"path": v, "idx": str(i)})
        m.inc("hits", i + 1.0, labels={"path": v})
    parsed = parse_prometheus(m.to_prometheus())
    for i, v in enumerate(hostile):
        gkey = metrics_mod._series_key("g", {"path": v, "idx": str(i)})
        assert parsed[gkey] == float(i), f"gauge lost for {v!r}"
        ckey = metrics_mod._series_key("hits_total", {"path": v})
        assert parsed[ckey] == i + 1.0, f"counter lost for {v!r}"
        # ...and the flat key itself splits back to the raw value.
        name, labels = metrics_mod._split_series(gkey, quoted=False)
        assert name == "g" and labels["path"] == v
    # Distinct hostile values never collide into one series.
    assert len([k for k in parsed if k.startswith("g{")]) == len(hostile)


def test_prometheus_hostile_label_names_and_metric_names():
    # Label/metric NAMES are sanitized (exposition forbids escapes there);
    # values survive verbatim alongside.
    m = Metrics()
    m.set_gauge("lat.p99-s", 7.0, labels={"the key": 'v"al'})
    text = m.to_prometheus()
    assert "lat_p99_s" in text
    parsed = parse_prometheus(text)
    assert parsed[metrics_mod._series_key("lat_p99_s",
                                          {"the_key": 'v"al'})] == 7.0


def test_merge_chrome_traces_dedupes_metadata(tmp_path):
    """Multi-source merge schema: ph:"M" process/thread metadata repeated
    across per-rank files (one rank contributes host + device + journey
    rows, each re-stating its track names) collapses to first-occurrence;
    data events pass through untouched, in file order."""
    meta_p0 = [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": "rank 0"}},
        {"ph": "M", "name": "thread_name", "pid": 0, "tid": 1,
         "args": {"name": "host"}},
    ]
    ev = {"ph": "X", "name": "work", "pid": 0, "tid": 1, "ts": 1.0,
          "dur": 2.0, "args": {}}
    (tmp_path / "trace.p0.json").write_text(json.dumps(
        {"traceEvents": meta_p0 + [ev] + meta_p0}))      # dup in-file
    (tmp_path / "trace.p1.json").write_text(json.dumps(
        {"traceEvents": [
            meta_p0[0],                                  # dup cross-file
            {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
             "args": {"name": "rank 0 DIFFERENT"}},      # same ids, new args
            {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
             "args": {"name": "rank 1"}},
            dict(ev, pid=1, ts=5.0),
        ]}))
    merged = json.loads(open(trace.merge_chrome_traces(str(tmp_path)))
                        .read())
    assert set(merged) == {"traceEvents", "displayTimeUnit"}
    events = merged["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    data = [e for e in events if e["ph"] != "M"]
    # Exact-duplicate metadata collapsed; differing args kept (they are a
    # different declaration, not a repeat).
    keys = [(e["name"], e["pid"], e["tid"],
             json.dumps(e["args"], sort_keys=True)) for e in meta]
    assert len(keys) == len(set(keys)) == 4
    assert [e["ts"] for e in data] == [1.0, 5.0]         # file order
    # Merging the merged file's directory again is stable (idempotent on
    # the metadata set).
    again = json.loads(open(trace.merge_chrome_traces(
        str(tmp_path), out_name="trace.merged2.json")).read())
    assert [e for e in again["traceEvents"] if e["ph"] == "M"] == meta


# ---------------------------------------------------------------------------
# window quantiles: edge cases vs numpy ground truth
# ---------------------------------------------------------------------------


def _ring(values, clock=lambda: 100.0):
    r = WindowRing(bucket_s=1.0, n_buckets=64, clock=clock)
    for v in values:
        r.observe(v, now=100.0)
    return r


def test_window_quantile_empty_and_single():
    r = WindowRing(bucket_s=1.0, n_buckets=8, clock=lambda: 0.0)
    st = r.query(8.0)
    assert st.count == 0 and st.quantile(50) == 0.0 and st.mean == 0.0
    assert st.frac_gt(0.0) == 0.0
    r.observe(0.037)
    st = r.query(8.0)
    # One sample: every quantile is that sample (min==max clamps the
    # in-bucket interpolation to the observed point).
    for p in (0, 1, 50, 99, 100):
        assert st.quantile(p) == 0.037
    assert st.min == st.max == 0.037 and st.count == 1


def test_window_quantile_identical_values_and_extremes():
    st = _ring([0.02] * 1000).query(60.0)
    for p in (0, 50, 90, 99, 100):
        assert st.quantile(p) == 0.02
    # p=0 / p=100 never extrapolate past observed min/max.
    st = _ring([0.001, 0.01, 0.1]).query(60.0)
    assert st.quantile(0) == 0.001
    assert st.quantile(100) == 0.1


def test_window_quantile_vs_numpy_within_bucket_error():
    import numpy as np

    rng = np.random.RandomState(0)
    # Log-uniform over the bucket range: exercises many buckets.
    vals = list(10.0 ** rng.uniform(-3.5, 1.5, size=2000))
    st = _ring(vals).query(60.0)
    assert st.count == 2000
    assert st.sum == pytest.approx(float(np.sum(vals)))
    assert st.mean == pytest.approx(float(np.mean(vals)))
    for p in (50, 90, 99):
        exact = float(np.percentile(vals, p))
        got = st.quantile(p)
        # The documented accuracy contract: the interpolated quantile lands
        # within the containing bucket, so worst-case relative error is the
        # log-bucket ratio 10^(1/8) ~ 1.334.
        ratio = 10.0 ** (1.0 / 8.0)
        assert exact / ratio <= got <= exact * ratio, (p, got, exact)
    # frac_gt agrees with the exact empirical fraction to bucket error:
    # bracket the threshold one bucket either side.
    for thr in (0.01, 0.1, 1.0):
        exact = float(np.mean(np.asarray(vals) > thr))
        lo = float(np.mean(np.asarray(vals) > thr * ratio))
        hi = float(np.mean(np.asarray(vals) > thr / ratio))
        assert lo - 1e-9 <= st.frac_gt(thr) <= hi + 1e-9, (thr, exact)


def test_window_counter_ring_expiry():
    # Counter mode (bounds=None): sum()/mean() over the trailing window
    # only, with lazy O(1) expiry as the fake clock advances.
    now = [10.0]
    r = WindowRing(bucket_s=1.0, n_buckets=4, bounds=None,
                   clock=lambda: now[0])
    r.observe(3.0)
    now[0] = 11.0
    r.observe(5.0)
    assert r.sum(4.0) == 8.0
    assert r.query(4.0).counts is None       # no histogram arrays
    assert r.mean(4.0) == 4.0
    assert r.rate(4.0) == pytest.approx(8.0 / 4.0)
    # Advance past the first bucket: 3.0 expires, 5.0 survives.
    now[0] = 13.5
    assert r.sum(3.0) == 5.0
    # Advance past the ring: everything expires; the slot is reset on
    # touch, not by a timer.
    now[0] = 30.0
    assert r.sum(4.0) == 0.0 and r.query(4.0).count == 0
    # Windows longer than the ring clamp to the ring.
    assert r.max_window_s == 4.0
    assert r.sum(1e9) == 0.0
