"""Always-on serving observability tests (obs.window / obs.slo /
obs.blackbox / TailSampler and their BatchEngine wiring).

The load-bearing guarantees:
  1. bounded memory — every always-on structure (windowed rings, histogram
     reservoirs, blackbox ring, sampler pending/kept sets, tracer ring) is
     constant-size under unbounded observation streams, and every eviction
     is COUNTED;
  2. deterministic SLO state machine — under a sustained latency fault the
     multi-window burn-rate evaluation walks OK -> WARN -> BREACH exactly
     (fast window trips first), driven either by a fake clock or by the
     seeded resilience ``FaultPlan`` through the real engine;
  3. forensic breach bundle — a transition into BREACH fires
     ``Watchdog.snapshot`` and the dump contains the blackbox event ring,
     the windowed percentiles, and at least one sampled trace of an
     offending (slow-kept) request.
"""

import json
import time

import jax
import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.obs.blackbox import Blackbox
from triton_distributed_tpu.obs.metrics import (
    DEFAULT_MAX_SAMPLES,
    Metrics,
)
from triton_distributed_tpu.obs.slo import (
    BREACH,
    OK,
    WARN,
    Objective,
    SLOEngine,
    default_serving_slo,
)
from triton_distributed_tpu.obs.trace import TailSampler, Tracer
from triton_distributed_tpu.obs.window import WindowRing, WindowStats


class FakeClock:
    """Deterministic injectable clock for window/SLO tests."""

    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


# ---------------------------------------------------------------------------
# window ring
# ---------------------------------------------------------------------------


def test_window_ring_quantiles_and_frac_gt():
    clock = FakeClock()
    ring = WindowRing(bucket_s=1.0, n_buckets=60, clock=clock)
    for i in range(100):
        ring.observe(0.001 + i * 0.0001)       # 0.1 .. 10.9 ms
        clock.advance(0.1)
    st = ring.query(60.0)
    assert st.count == 100
    assert st.min == pytest.approx(0.001)
    assert st.max == pytest.approx(0.0109)
    assert st.mean == pytest.approx(0.00595, rel=1e-3)
    # Interpolated quantiles: exact at the extremes, within a value-bucket
    # ratio (~33%) in the middle.
    assert st.quantile(0) >= st.min
    assert st.quantile(100) == pytest.approx(st.max)
    assert st.quantile(50) == pytest.approx(0.0060, rel=0.35)
    # frac_gt is the SLO violation fraction: ~half the points sit above
    # the median value.
    assert st.frac_gt(st.max) == 0.0
    assert st.frac_gt(0.0) == 1.0
    assert st.frac_gt(0.006) == pytest.approx(0.5, abs=0.2)
    d = st.as_dict()
    assert {"count", "mean", "min", "max", "p50", "p90", "p99"} <= set(d)


def test_window_ring_lazy_expiry():
    clock = FakeClock()
    ring = WindowRing(bucket_s=1.0, n_buckets=10, clock=clock)
    ring.observe(1.0)
    assert ring.query(10.0).count == 1
    # Trailing-window semantics: out of a 2 s window after 3 s...
    clock.advance(3.0)
    assert ring.query(2.0).count == 0
    assert ring.query(10.0).count == 1
    # ...and fully expired once the ring wraps past its slot.
    clock.advance(20.0)
    assert ring.query(10.0).count == 0
    # Queries clamp to the ring's maximum coverage.
    assert ring.max_window_s == 10.0
    ring.observe(2.0)
    assert ring.query(1e9).count == 1


def test_window_ring_counter_mode_and_rate():
    clock = FakeClock()
    ring = WindowRing(bucket_s=1.0, n_buckets=30, bounds=None, clock=clock)
    for i in range(10):
        if i:
            clock.advance(1.0)
        ring.observe(2.0)
    st = ring.query(10.0)
    assert st.count == 10 and st.sum == 20.0
    assert st.counts is None                   # no value buckets to carry
    assert "sum" in st.as_dict() and "p50" not in st.as_dict()
    assert ring.rate(10.0) == pytest.approx(2.0)


def test_window_ring_rejects_degenerate_config():
    with pytest.raises(ValueError):
        WindowRing(bucket_s=0.0)
    with pytest.raises(ValueError):
        WindowRing(n_buckets=1)


def test_window_stats_empty_is_zero():
    st = WindowStats()
    assert st.count == 0 and st.mean == 0.0
    assert st.quantile(99) == 0.0 and st.frac_gt(0.5) == 0.0


# ---------------------------------------------------------------------------
# windowed metrics registry
# ---------------------------------------------------------------------------


def test_metrics_windowed_queries():
    clock = FakeClock()
    m = Metrics(windowed=True, window_bucket_s=0.25, clock=clock)
    for _ in range(8):
        m.observe("ttft_s", 0.05)
        m.inc("requests_completed")
        clock.advance(0.25)
    st = m.window_stats("ttft_s", 10.0)
    assert st is not None and st.count == 8
    assert m.window_counter("requests_completed", 10.0) == 8.0
    w = m.window("ttft_s", 10.0)
    assert w["count"] == 8.0 and "p99" in w
    wc = m.window("requests_completed", 10.0)
    assert wc["sum"] == 8.0 and wc["rate_per_s"] == pytest.approx(0.8)
    # Lifetime stats are untouched by windowing.
    assert m.histograms["ttft_s"].count == 8
    # Expiry: advance past the ring coverage, window empties, lifetime
    # totals stay.
    clock.advance(m._hist_windows["ttft_s"].max_window_s + 1.0)
    assert m.window_stats("ttft_s", 10.0).count == 0
    assert m.histograms["ttft_s"].count == 8


def test_metrics_unwindowed_window_queries_are_empty():
    m = Metrics()                  # windowed=False: hot path is untouched
    m.observe("ttft_s", 0.1)
    m.inc("requests_completed")
    assert m.window_stats("ttft_s", 10.0) is None
    assert m.window_counter("requests_completed", 10.0) == 0.0
    assert m.window("ttft_s", 10.0) == {}


# ---------------------------------------------------------------------------
# blackbox recorder
# ---------------------------------------------------------------------------


def test_blackbox_ring_counts_evictions():
    clock = FakeClock()
    bb = Blackbox(capacity=4, clock=clock)
    for i in range(10):
        bb.record("admit" if i % 2 == 0 else "finish", req=i)
        clock.advance(0.1)
    assert len(bb) == 4
    assert bb.n_recorded == 10 and bb.n_dropped == 6
    evs = bb.events()
    assert [e["req"] for e in evs] == [6, 7, 8, 9]     # oldest evicted
    assert all({"t", "wall", "kind"} <= set(e) for e in evs)
    assert [e["req"] for e in bb.events(kind="admit")] == [6, 8]
    assert [e["req"] for e in bb.events(last=2)] == [8, 9]
    dump = bb.dump(last=3)
    assert dump["capacity"] == 4 and dump["dropped"] == 6
    assert len(dump["events"]) == 3
    json.dumps(dump)
    bb.clear()
    assert len(bb) == 0 and bb.n_recorded == 0 and bb.n_dropped == 0


def test_blackbox_rejects_zero_capacity():
    with pytest.raises(ValueError):
        Blackbox(capacity=0)


def test_blackbox_seq_survives_wraparound_and_dump_json(tmp_path):
    """The ``seq`` satellite (ISSUE 13): a CONSTANT clock puts every
    event on the same tick, so after the ring wraps only the monotonic
    ``seq`` counter keeps a total order — ``events()`` must sort on it,
    and ``dump_json`` must round-trip the whole bundle byte-exactly."""
    bb = Blackbox(capacity=4, clock=FakeClock(5.0))
    for i in range(11):
        bb.record("ev", i=i)
    assert bb.n_recorded == 11 and bb.n_dropped == 7
    evs = bb.events()
    assert [e["i"] for e in evs] == [7, 8, 9, 10]
    assert [e["seq"] for e in evs] == [7, 8, 9, 10]
    assert all(e["t"] == 5.0 for e in evs)      # clock alone can't order

    path = bb.dump_json(str(tmp_path / "sub" / "bb.json"))
    with open(path, encoding="utf-8") as f:
        loaded = json.load(f)
    assert loaded["capacity"] == 4
    assert loaded["recorded"] == 11 and loaded["dropped"] == 7
    assert loaded["events"] == evs              # JSON-able as-is


def test_tail_sampler_keep_drop_determinism_under_fixed_seed():
    """The keep/drop verdict SEQUENCE is a pure function of (seed, submit
    order): two same-seed samplers agree on every one of 200 verdicts; a
    different seed picks a different head sample."""
    def verdicts(seed):
        s = TailSampler(head_frac=0.3, slow_s=None, seed=seed)
        out = []
        for i in range(200):
            s.begin(f"r{i}")
            out.append(s.finish(f"r{i}", latency_s=1e-4))
        return out

    a = verdicts(3)
    assert a == verdicts(3)
    assert any(a) and not all(a)                # a real 0<frac<1 sample
    assert a != verdicts(4)


# ---------------------------------------------------------------------------
# tail sampler
# ---------------------------------------------------------------------------


def test_tail_sampler_head_sampling_is_seed_deterministic():
    def run(seed):
        s = TailSampler(head_frac=0.25, slow_s=None, seed=seed)
        kept = []
        for i in range(200):
            s.begin(i)
            kept.append(s.finish(i, latency_s=0.001))
        return kept, s

    kept_a, sa = run(seed=7)
    kept_b, _ = run(seed=7)
    kept_c, _ = run(seed=8)
    assert kept_a == kept_b                     # same seed, same decisions
    assert kept_a != kept_c                     # a different head sample
    assert sa.n_kept_head == sum(kept_a)
    assert 0 < sa.n_kept_head < 200             # ~25%, neither none nor all
    assert sa.n_dropped == 200 - sa.n_kept_head


def test_tail_sampler_keeps_slow_and_errored():
    s = TailSampler(head_frac=0.0, slow_s=0.1, seed=0)
    s.begin("fast")
    assert not s.finish("fast", latency_s=0.01)
    s.begin("slow")
    assert s.finish("slow", latency_s=0.5)
    s.begin("bad")
    assert s.finish("bad", error="nan-quarantine")
    reasons = {rt.req_id: rt.kept_reason for rt in s.kept}
    assert reasons == {"slow": "slow", "bad": "error"}
    assert s.kept[-1].attrs["error"] == "nan-quarantine"
    st = s.stats()
    assert st["kept_tail"] == 2 and st["dropped"] == 1 and st["pending"] == 0


def test_tail_sampler_mark_slow_keeps_in_flight_request():
    s = TailSampler(head_frac=0.0, slow_s=0.05, seed=0)
    s.begin("straggler", prompt_len=9)
    s.event("straggler", "admit", slot=2)
    # One token gap blew the budget: the trace must be kept NOW, while the
    # request is still in flight, so a breach snapshot contains it.
    s.mark_slow("straggler", slow_gap_s=0.2)
    assert len(s.kept) == 1 and s.kept[0].kept_reason == "slow"
    assert s.n_pending == 1
    # finish() is idempotent on the keep decision (no double count).
    s.finish("straggler", latency_s=1.0)
    assert s.n_kept_tail == 1 and len(s.kept) == 1 and s.n_pending == 0
    d = s.kept[0].as_dict()
    assert d["kept_reason"] == "slow"
    assert [e["name"] for e in d["events"]] == ["admit"]
    json.dumps(d)


def test_tail_sampler_bounds_pending_events_and_kept():
    s = TailSampler(head_frac=0.0, slow_s=0.0, keep=4, max_events=2,
                    max_pending=8, seed=0)
    # Pending cap: begins past the cap are refused and counted.
    for i in range(12):
        s.begin(i)
    assert s.n_pending == 8 and s.n_overflow == 4
    # Per-request event cap.
    for _ in range(5):
        s.event(0, "tok")
    for i in range(8):
        assert s.finish(i, latency_s=1.0)       # slow_s=0 keeps everything
    # Kept ring bounded: only the last ``keep`` survive.
    assert len(s.kept) == 4
    assert [rt.req_id for rt in s.kept] == [4, 5, 6, 7]
    assert s.stats()["retained"] == 4
    # finish of an unknown (never-begun / cap-refused) request is a no-op.
    assert not s.finish("never-begun", latency_s=9.9)


def test_tail_sampler_event_drops_counted():
    s = TailSampler(head_frac=1.0, slow_s=None, max_events=2, seed=0)
    s.begin("r")
    for i in range(5):
        s.event("r", f"e{i}")
    assert s.finish("r", latency_s=0.001)
    (rt,) = s.kept
    assert len(rt.events) == 2 and rt.n_event_drops == 3
    assert rt.as_dict()["event_drops"] == 3


def test_tail_sampler_rejects_bad_head_frac():
    with pytest.raises(ValueError):
        TailSampler(head_frac=1.5)


# ---------------------------------------------------------------------------
# SLO engine (fake clock)
# ---------------------------------------------------------------------------


def _slo_rig(objective, clock):
    m = Metrics(windowed=True, window_bucket_s=0.05, window_buckets=400,
                clock=clock)
    transitions = []
    eng = SLOEngine([objective], m, clock=clock,
                    on_transition=lambda o, old, new, detail:
                    transitions.append((old, new)))
    return m, eng, transitions


def test_slo_engine_requires_windowed_metrics():
    with pytest.raises(ValueError, match="windowed"):
        SLOEngine(default_serving_slo(), Metrics())


def test_slo_latency_ladder_ok_warn_breach_and_recovery():
    clock = FakeClock()
    obj = Objective.latency("tbt_p99", "tbt_s", 0.02, fast_window_s=0.4,
                            slow_window_s=1.6, min_count=3)
    m, eng, transitions = _slo_rig(obj, clock)
    # Healthy phase: fill both windows with good observations.
    for _ in range(40):
        m.observe("tbt_s", 0.005)
        eng.evaluate()
        clock.advance(0.05)
    assert eng.verdicts() == {"tbt_p99": OK} and transitions == []
    # Sustained fault: every token gap violates the threshold. The fast
    # window saturates with violations first (WARN), then the slow window
    # accumulates 6x-budget burn too (BREACH) — exactly one ladder.
    for _ in range(40):
        m.observe("tbt_s", 0.1)
        eng.evaluate()
        clock.advance(0.05)
        if eng.verdicts()["tbt_p99"] == BREACH:
            break
    assert transitions == [(OK, WARN), (WARN, BREACH)]
    assert eng.n_breaches == 1
    # Recovery: healthy traffic flushes the windows and the machine walks
    # back down to OK (fast window clears first).
    for _ in range(80):
        m.observe("tbt_s", 0.005)
        eng.evaluate()
        clock.advance(0.05)
    assert eng.verdicts() == {"tbt_p99": OK}
    assert transitions[-1][1] == OK
    summ = eng.summary()
    assert summ["worst"] == OK and summ["breaches"] == 1
    assert summ["evaluations"] == eng.n_evaluations
    json.dumps(summ)


def test_slo_cold_window_reads_healthy():
    clock = FakeClock()
    obj = Objective.latency("ttft_p99", "ttft_s", 0.01, fast_window_s=0.4,
                            slow_window_s=1.6, min_count=8)
    m, eng, transitions = _slo_rig(obj, clock)
    # Fewer than min_count observations — even all-violating ones — must
    # not trip (cold start is not an incident).
    for _ in range(5):
        m.observe("ttft_s", 9.9)
        eng.evaluate()
        clock.advance(0.05)
    assert eng.verdicts()["ttft_p99"] == OK and transitions == []


def test_slo_ratio_ceiling_and_floor():
    clock = FakeClock()
    obj = Objective.ratio_ceiling(
        "error_rate", "requests_failed",
        ("requests_completed", "requests_failed"), 0.05,
        fast_window_s=0.4, slow_window_s=1.6, min_count=4)
    m, eng, transitions = _slo_rig(obj, clock)
    for _ in range(30):
        m.inc("requests_completed")
        eng.evaluate()
        clock.advance(0.05)
    assert eng.verdicts()["error_rate"] == OK
    for _ in range(30):
        m.inc("requests_failed")
        eng.evaluate()
        clock.advance(0.05)
        if eng.verdicts()["error_rate"] == BREACH:
            break
    assert transitions == [(OK, WARN), (WARN, BREACH)]
    # Floors invert the direction: a healthy hit rate above the floor.
    clock2 = FakeClock()
    floor = Objective.ratio_floor("hit_rate", "prefix_hits",
                                  "prefix_lookups", 0.4, fast_window_s=0.4,
                                  slow_window_s=1.6, min_count=4)
    m2, eng2, tr2 = _slo_rig(floor, clock2)
    for _ in range(20):
        m2.inc("prefix_lookups")
        m2.inc("prefix_hits")
        eng2.evaluate()
        clock2.advance(0.05)
    assert eng2.verdicts()["hit_rate"] == OK and tr2 == []


def test_objective_validation():
    with pytest.raises(ValueError, match="kind"):
        Objective(name="x", kind="nope", metric="m", threshold=1.0)
    with pytest.raises(ValueError, match="direction"):
        Objective(name="x", kind="rate", metric="m", threshold=1.0,
                  direction="gt")
    with pytest.raises(ValueError, match="denominator"):
        Objective(name="x", kind="ratio", metric="m", threshold=1.0)
    with pytest.raises(ValueError, match="fast window"):
        Objective.latency("x", "m", 1.0, fast_window_s=60.0,
                          slow_window_s=10.0)
    with pytest.raises(ValueError, match="duplicate"):
        SLOEngine([Objective.latency("x", "m", 1.0),
                   Objective.latency("x", "m", 2.0)],
                  Metrics(windowed=True))
    objs = default_serving_slo(prefix_hit_floor=0.4)
    assert [o.name for o in objs] == ["ttft_p99", "tbt_p99", "error_rate",
                                      "prefix_hit_rate"]


# ---------------------------------------------------------------------------
# bounded-memory soak
# ---------------------------------------------------------------------------


def test_bounded_memory_soak():
    """>= 1e5 observations through every always-on structure: retained
    state stays at its configured bound and every eviction is counted."""
    n = 100_000
    clock = FakeClock()
    m = Metrics(windowed=True, window_bucket_s=0.05, window_buckets=100,
                clock=clock)
    for i in range(n):
        m.observe("tbt_s", (i % 500) * 1e-4)
        if i % 7 == 0:
            m.inc("requests_completed")
        clock.advance(0.001)
    h = m.histograms["tbt_s"]
    assert h.count == n                          # exact accumulators...
    assert len(h.samples) <= DEFAULT_MAX_SAMPLES  # ...bounded reservoir
    ring = m._hist_windows["tbt_s"]
    assert len(ring._ring) == 100                # ring never grows
    assert m.window_stats("tbt_s", 5.0).count <= 5.0 / 0.05 * 50 + 50

    bb = Blackbox(capacity=512, clock=clock)
    for i in range(n // 10):
        bb.record("finish", req=i)
    assert len(bb) == 512
    assert bb.n_dropped == bb.n_recorded - 512

    s = TailSampler(head_frac=0.01, slow_s=None, keep=64, seed=0)
    for i in range(n // 10):
        s.begin(i)
        s.finish(i, latency_s=0.001)
    st = s.stats()
    assert st["pending"] == 0 and st["retained"] <= 64
    assert st["begun"] == st["kept_head"] + st["dropped"]

    t = Tracer(capacity=256)
    t.enable()
    for i in range(n // 10):
        t.instant("e")
    assert len(t) == 256 and t.dropped == n // 10 - 256


# ---------------------------------------------------------------------------
# serve_top rendering (pure snapshot -> str)
# ---------------------------------------------------------------------------


def test_serve_top_render_and_feed(tmp_path):
    from tools import serve_top

    snap = serve_top._demo_snapshot(25)          # the "slow" demo phase
    frame = serve_top.render(snap)
    assert "serve_top" in frame and "slots" in frame and "pool" in frame
    assert "last 10s" in frame and "last 5m" in frame
    assert "BRCH" in frame                       # demo breach is rendered
    assert "telemetry" in frame
    # Feed tailing: last parseable JSON line wins; garbage is skipped.
    feed = tmp_path / "stats.jsonl"
    feed.write_text(json.dumps(serve_top._demo_snapshot(1)) + "\n"
                    + json.dumps(snap) + "\nnot json\n")
    got = serve_top._last_snapshot(str(feed))
    assert got == snap
    assert serve_top._last_snapshot(str(tmp_path / "missing")) is None
    # --once over the feed exits 0.
    assert serve_top.main(["--stats-jsonl", str(feed), "--once"]) == 0


# ---------------------------------------------------------------------------
# engine wiring: always-on defaults, snapshotting, and the breach ladder
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1],
                     set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


def _prompts(config, n=6, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    return [list(map(int, rng.integers(1, config.vocab_size - 1, size=6)))
            for _ in range(n)]


def test_engine_defaults_on_bit_identical_and_snapshot(setup):
    from triton_distributed_tpu.serving import BatchEngine

    _, config, engine = setup
    prompts = _prompts(config)

    # The plain path (its steps take milliseconds here, the fused kernel's
    # under the interpreter 0.3 s and more on a busy machine): the snapshot
    # below must find every request inside the trailing 10 s window.
    be = BatchEngine(engine, n_slots=4, block_size=4, prefill_chunk=8,
                     **PLAIN_PATH)
    assert be.metrics.windowed and be.blackbox is not None \
        and be.sampler is not None
    for i, p in enumerate(prompts):
        be.submit(p, 5, req_id=f"r{i}")
    out_on = be.run()
    assert be.trace_counts == {"decode": 1, "prefill": 1}

    snap = be.stats_snapshot()
    assert {"slots", "pool", "counters", "windows", "blackbox",
            "sampler"} <= set(snap)
    assert snap["windows"]["10s"]["ttft_s"]["count"] >= len(prompts)
    assert snap["blackbox"]["recorded"] > 0
    json.dumps(snap, default=str)
    # The blackbox saw the full lifecycle, scheduler decisions included.
    kinds = {e["kind"] for e in be.blackbox.events()}
    assert {"admit", "finish", "schedule_admit"} <= kinds

    be_off = BatchEngine(engine, n_slots=4, block_size=4, prefill_chunk=8,
                         windowed_metrics=False, blackbox=False,
                         tail_sampling=False, **PLAIN_PATH)
    assert be_off.blackbox is None and be_off.sampler is None
    for i, p in enumerate(prompts):
        be_off.submit(p, 5, req_id=f"r{i}")
    assert be_off.run() == out_on          # telemetry never touches tokens
    assert be_off.trace_counts == {"decode": 1, "prefill": 1}


def test_engine_attach_slo_requires_windowed(setup):
    from triton_distributed_tpu.serving import BatchEngine

    _, _, engine = setup
    be = BatchEngine(engine, n_slots=2, block_size=4,
                     windowed_metrics=False)
    with pytest.raises(ValueError, match="windowed"):
        be.attach_slo()


def test_engine_stream_stats_jsonl(setup, tmp_path):
    from triton_distributed_tpu.serving import BatchEngine

    _, config, engine = setup
    path = tmp_path / "stats.jsonl"
    be = BatchEngine(engine, n_slots=4, block_size=4, prefill_chunk=8,
                     **PLAIN_PATH)
    be.stream_stats(str(path), interval_s=0.0)    # emit every step
    for i, p in enumerate(_prompts(config, 4)):
        be.submit(p, 4, req_id=f"r{i}")
    be.run()
    lines = path.read_text().strip().splitlines()
    assert lines
    for line in lines:
        snap = json.loads(line)
        assert "windows" in snap and "counters" in snap


def test_engine_slo_fault_ladder_breach_bundle(setup):
    """The acceptance scenario: a seeded FaultPlan latency fault drives the
    attached SLO deterministically OK -> WARN -> BREACH, and the breach
    fires a watchdog snapshot bundling the blackbox ring, the windowed
    percentiles, and a sampled trace of an offending (slow) request."""
    from triton_distributed_tpu.resilience import Watchdog
    from triton_distributed_tpu.resilience import faults as _faults
    from triton_distributed_tpu.resilience.faults import FaultPlan, FaultSpec
    from triton_distributed_tpu.serving import BatchEngine

    _, config, engine = setup
    prompts = _prompts(config)
    # The plain path: the ladder's windows (0.4 s / 1.6 s) and
    # the +100 ms fault are sized for steps of milliseconds, and the fused
    # kernel under the interpreter steps in ~0.3 s (one step's jitter then
    # fills the fast window).
    be = BatchEngine(engine, n_slots=4, block_size=4, prefill_chunk=8,
                     tail_sampling=TailSampler(head_frac=0.0, slow_s=0.05,
                                               seed=0), **PLAIN_PATH)
    ri = 0

    def feed(n):
        nonlocal ri
        for _ in range(n):
            be.submit(prompts[ri % len(prompts)], 16, req_id=f"s{ri}")
            ri += 1

    # 1. compile warmup, entirely off the SLO clock.
    feed(4)
    be.run()
    # 2. healthy flush, longer than the slow window: compile-time
    #    stragglers expire out of both windows before the SLO attaches.
    t0 = time.monotonic()
    while time.monotonic() - t0 < 2.0:
        if not be.step():
            feed(2)
    # 3. attach watchdog + SLO over clean windows. The threshold is the
    #    healthy token gap just measured plus half the injected delay: a
    #    healthy window trips only if 6% of its steps run 50 ms late, a
    #    faulted one always, however loaded this CPU is.
    threshold_s = be.metrics.window("tbt_s", 1.6)["p50"] + 0.05
    wd = Watchdog()
    be.attach_watchdog(wd)
    slo = be.attach_slo(
        [Objective.latency("tbt_p99", "tbt_s", threshold_s,
                           fast_window_s=0.4,
                           slow_window_s=1.6, min_count=3)],
        eval_interval_s=0.05)
    # 4. short healthy confirmation.
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.3:
        if not be.step():
            feed(2)
    assert slo.verdicts()["tbt_p99"] == OK, slo.verdicts()
    # 5. sustained seeded latency fault: every decode step +100 ms.
    plan = FaultPlan([FaultSpec(site="engine.decode", kind="delay", p=1.0,
                                delay_s=0.1)])
    t0 = time.monotonic()
    with _faults.plan(plan):
        while time.monotonic() - t0 < 20.0:
            if not be.step():
                feed(2)
            if slo.verdicts()["tbt_p99"] == BREACH:
                break
    seq = [(t["old"], t["new"]) for t in slo.transitions]
    assert seq == [(OK, WARN), (WARN, BREACH)], seq
    assert slo.n_breaches == 1
    assert be.metrics.counters.get("slo_breaches") == 1.0

    snap = wd.last_snapshot
    assert snap is not None and snap["reason"].startswith("slo-breach:")
    assert snap["blackbox"]["events"], "breach dump missing blackbox ring"
    assert "tbt_s" in snap["windows"]["10s"], "breach dump missing windows"
    assert "slo_detail" in snap
    assert any(t["kept_reason"] == "slow" for t in snap["sampled_traces"]), \
        "breach dump missing the offending sampled trace"
    json.dumps(snap, default=str)
    # The blackbox recorded the SLO transitions themselves.
    slo_events = be.blackbox.events(kind="slo")
    assert [(e["old"], e["new"]) for e in slo_events] == seq
