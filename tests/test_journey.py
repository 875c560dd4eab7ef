"""Request-journey tracing tests (obs/journey.py and its wiring).

The load-bearing guarantees (ISSUE 13):
  1. exact attribution — every instant between submit and finish is in
     exactly ONE phase bucket, so the per-request fractions sum to
     1.0 +/- 1e-6 by construction, online and post-hoc alike;
  2. stitch == live — ``Journey.stitch`` over a dumped event bag
     reproduces the live recorder's summary exactly (same ``_Accum``
     state machine), and is order-independent given the ``(t, seq)`` key;
  3. zero intrusion — journey recording never changes the greedy output,
     never retraces a compiled step (``trace_counts`` stays {1,1}), and
     is bounded (event caps, pending cap, summary deques — drops
     counted);
  4. fleet-wide causality — a cross-replica requeue stays ONE journey:
     the hop chain reads submit -> route -> drain -> requeue -> route ->
     finish with hop ids monotonically numbered across replicas, and
     the forensic ``tools/explain_request.py`` report over the dumped
     journal is deterministic.

Journeys are recorded by the host: the engines here take the plain path
(``conftest.PLAIN_PATH``); no case stays on "fused".
"""

import json

import jax
import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.obs import trace
from triton_distributed_tpu.obs.journey import (
    BUCKETS,
    Journey,
    JourneyContext,
    JourneyRecorder,
)
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import BatchEngine
from triton_distributed_tpu.serving.router import Router


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1],
                     set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


class TickClock:
    """Deterministic virtual clock: advances a fixed tick per read."""

    def __init__(self, tick: float = 1.0):
        self.n = 0
        self.tick = tick

    def __call__(self) -> float:
        self.n += 1
        return self.n * self.tick


def _frac_sum(summary: dict) -> float:
    return sum(summary["fracs"][b] for b in BUCKETS)


# -- 1. context + phase machine ---------------------------------------------

def test_context_hop_numbering_is_monotonic():
    ctx = JourneyContext(req_id="r")
    assert ctx.next_hop("submit") == 0
    assert ctx.next_hop("route", where=2, t=1.5) == 1
    assert ctx.next_hop("drain") == 2
    assert [h["hop"] for h in ctx.hops] == [0, 1, 2]
    assert ctx.hops[1] == {"hop": 1, "kind": "route", "where": 2,
                           "t": 1.5}


def test_recorder_exact_attribution_with_virtual_clock():
    """Each clock read advances 1s, so bucket seconds are countable by
    hand: the phase machine must land them in the right buckets and the
    fractions must sum to exactly 1."""
    rec = JourneyRecorder(clock=TickClock())
    rec.begin("r1", phase="route")            # t=1, route opens
    rec.hop("r1", "route", where=0)           # t=2: route 1s -> queue
    rec.event("r1", "adopt")                  # t=3: queue continues
    rec.event("r1", "admit", cached=4)        # t=4: queue 2s -> prefill
    rec.event("r1", "prefill_chunk", tokens=8, budget=8)   # t=5
    rec.event("r1", "decode_start")           # t=6: prefill 2s -> decode
    rec.hop("r1", "preempt")                  # t=7: decode 1s -> preempted
    rec.event("r1", "admit")                  # t=8: preempted 1s -> prefill
    rec.event("r1", "decode_start")           # t=9: prefill 1s -> decode
    j = rec.finish("r1", keep=True)           # t=10: decode 1s
    assert j is not None
    s = j.summary
    assert s["attribution_s"] == {"route": 1.0, "queue": 2.0,
                                  "prefill": 3.0, "decode": 2.0,
                                  "preempted": 1.0, "requeue": 0.0,
                                  "restore": 0.0}
    assert s["total_s"] == 9.0
    assert _frac_sum(s) == pytest.approx(1.0, abs=1e-9)
    assert s["dominant"] == "prefill"
    assert s["cached_tokens"] == 4 and s["prefill_tokens"] == 8
    assert s["n_admits"] == 2 and s["n_preempts"] == 1
    assert s["budget_split"] == {"8": {"chunks": 1, "tokens": 8}}
    # Segments tile [t0, t1] with no gaps or overlap.
    segs = j.segments
    assert segs[0][1] == j.t0 and segs[-1][2] == j.t1
    for (_, _, e0), (_, s1, _) in zip(segs, segs[1:]):
        assert e0 == s1


def test_stitch_matches_live_and_is_order_independent():
    rec = JourneyRecorder(clock=TickClock())
    rec.begin("r", phase="queue", prompt_len=8)
    rec.event("r", "admit")
    rec.event("r", "prefill_chunk", tokens=8, budget=32)
    rec.event("r", "decode_start")
    live = rec.finish("r", keep=True)
    evs = list(live.events)
    restitched = Journey.stitch(evs, req_id="r", hops=live.hops)
    assert restitched.summary["fracs"] == live.summary["fracs"]
    assert restitched.summary["attribution_s"] == \
        live.summary["attribution_s"]
    assert restitched.summary["total_s"] == live.summary["total_s"]
    assert restitched.status == live.status == "ok"
    # Shuffled input: the (t, seq) sort key restores the causal order.
    shuffled = [evs[i] for i in (3, 0, 4, 1, 2)]
    again = Journey.stitch(shuffled, req_id="r")
    assert again.summary["attribution_s"] == \
        live.summary["attribution_s"]
    with pytest.raises(ValueError):
        Journey.stitch([])


def test_recorder_bounded_memory_and_counted_drops():
    rec = JourneyRecorder(clock=TickClock(), keep=2, summary_cap=4,
                          max_events=3, max_pending=2, slowest_k=2)
    assert rec.begin("a") is not None
    assert rec.begin("b") is not None
    assert rec.begin("c") is None             # pending cap: counted
    assert rec.n_pending_drops == 1
    for _ in range(10):
        rec.event("a", "prefill_chunk", tokens=1, budget=8)
    assert rec.n_event_drops > 0
    rec.event("a", "admit")                   # accum unaffected by cap
    rec.finish("a", keep=True)
    rec.finish("b", keep=True)
    for i in range(6):
        rec.begin(f"x{i}")
        rec.finish(f"x{i}", keep=True)
    assert len(rec.kept) == 2                 # keep deque bounded
    assert len(rec.summaries) == 4            # summary deque bounded
    assert len(rec.slowest()) == 2            # top-k bounded
    # events() for unknown ids are ignored, not errors
    rec.event("never-begun", "admit")
    st = rec.stats()
    assert st["event_drops"] == rec.n_event_drops
    assert st["pending_drops"] == 1


def test_perfdb_sample_keys_and_ranges():
    rec = JourneyRecorder(clock=TickClock())
    rec.begin("r")
    rec.event("r", "admit")
    rec.finish("r")
    s = rec.perfdb_sample()
    assert s["journey_finished"] == 1.0
    for b in BUCKETS:
        assert 0.0 <= s[f"journey_{b}_frac_p99"] <= 1.0


# -- 2. route-decision breakdown (satellite) --------------------------------

def test_route_breakdown_components_sum_to_score():
    r = Router(w_cache=2.0, w_headroom=0.5, w_queue=1.0)
    cands = [(0, {"match_frac": 0.5, "headroom": 0.25, "load": 1.0,
                  "slo_level": 1}),
             (1, {"match_frac": 0.0, "headroom": 1.0, "load": 0.0,
                  "slo_level": 0})]
    d = r.route([1, 2, 3], cands)
    assert set(d.breakdown) == {0, 1}
    for idx, comps in d.breakdown.items():
        assert set(comps) == {"cache", "headroom", "queue", "slo"}
        assert sum(comps.values()) == pytest.approx(d.scores[idx])
    # Candidate 0: 2*0.5 + 0.5*0.25 - 1*1.0 - 0.75 = -0.625; candidate 1
    # wins on headroom with no penalties.
    assert d.scores[0] == pytest.approx(-0.625)
    assert d.scores[1] == pytest.approx(0.5)
    assert d.replica == 1


# -- 3. engine integration: zero intrusion ----------------------------------

def test_engine_journey_bit_identical_zero_retrace(setup):
    _, config, engine = setup
    rng = np.random.default_rng(0)
    kw = dict(n_slots=4, n_blocks=32, block_size=4, prefill_chunk=8,
              **PLAIN_PATH)
    be_on = BatchEngine(engine, **kw)         # journey on by default
    be_off = BatchEngine(engine, **kw, journey=False)
    assert be_on.journey is not None and be_off.journey is None
    prompts = [rng.integers(0, config.vocab_size,
                            size=int(rng.integers(4, 16))).tolist()
               for _ in range(6)]
    outs = []
    for be in (be_on, be_off):
        rids = [be.submit(p, max_new_tokens=6) for p in prompts]
        done = be.run(max_steps=500)
        outs.append([done[r] for r in rids])
        assert be.trace_counts == {"decode": 1, "prefill": 1}
        be.pool.check_invariants()
    assert outs[0] == outs[1]                 # bit-identical greedy output
    rec = be_on.journey
    assert rec.n_finished == 6 and not rec._pending
    for s in rec.summaries:
        assert _frac_sum(s) == pytest.approx(1.0, abs=1e-6)
        assert s["status"] == "ok"
    snap = be_on.stats_snapshot()
    assert "journey" in snap
    json.dumps(snap, default=str)             # feed stays JSON-able
    assert snap["journey"]["finished"] == 6
    pd = be_on.perfdb_sample()
    assert pd["journey_finished"] == 6.0


def test_engine_preemption_lands_in_preempted_bucket(setup):
    """Oversubscribed pool (the preemption-golden config): the evicted
    request's journey must carry the preempt hop, a nonzero ``preempted``
    bucket, and still sum to 1 — and displaced journeys are always kept
    regardless of the sampler verdict."""
    _, config, engine = setup
    rng = np.random.default_rng(1)
    be = BatchEngine(engine, n_slots=3, n_blocks=6, block_size=4,
                     prefill_chunk=8, tail_sampling=False, **PLAIN_PATH)
    prompts = [rng.integers(0, config.vocab_size, size=7).tolist()
               for _ in range(4)]
    rids = [be.submit(p, max_new_tokens=8) for p in prompts]
    out = be.run(max_steps=500)
    assert len(out) == 4
    assert be.metrics.as_dict()["preemptions"] > 0
    rec = be.journey
    preempted = [j for j in rec.kept if j.summary["n_preempts"] > 0]
    assert preempted, "no journey recorded the forced preemption"
    for j in preempted:
        assert j.summary["attribution_s"]["preempted"] > 0.0
        assert _frac_sum(j.summary) == pytest.approx(1.0, abs=1e-6)
        assert any(h["kind"] == "preempt" for h in j.hops)
    assert rids[0] is not None
    be.pool.check_invariants()


# -- 4. fleet-wide causality: requeue stays one journey ---------------------

def test_fleet_chaos_requeue_hop_chain_and_explain(setup, tmp_path):
    """Replica 0 wedges mid-run: a displaced request's single journey
    must read route -> drain -> requeue -> route(new replica) -> finish
    with monotonic hop ids, the fleet perfdb sample must not N-x count
    the shared recorder, and ``tools/explain_request.py`` over the dumped
    journal must render a deterministic report that shows the chain."""
    from triton_distributed_tpu.resilience import faults
    from triton_distributed_tpu.resilience.faults import (
        default_fleet_chaos_plan,
    )
    from triton_distributed_tpu.serving.fleet import Fleet

    _, config, engine = setup
    fleet = Fleet.build(engine, n_replicas=2, fail_threshold=2,
                        n_slots=4, n_blocks=24, block_size=4,
                        prefill_chunk=8, **PLAIN_PATH)
    assert all(rep.engine.journey is fleet.journey
               for rep in fleet.replicas)     # ONE shared recorder
    fleet.journey.clock = TickClock(1e-3)     # deterministic report
    rng = np.random.default_rng(0)
    for _ in range(8):
        n = int(rng.integers(4, 20))
        fleet.submit(rng.integers(1, config.vocab_size, size=n).tolist(),
                     6)
    plan = default_fleet_chaos_plan(0, kill_replica=0, kill_after=3)
    with faults.plan(plan):
        out = fleet.run(max_steps=500)
    fleet.check_invariants()
    assert len(out) == 8

    requeued = sorted((r for r in fleet._requeues if r in out), key=str)
    assert requeued, "chaos kill displaced nothing"
    j = fleet.journey.lookup(requeued[0])
    assert j is not None                      # displaced => always kept
    kinds = [h["kind"] for h in j.hops]
    assert kinds[0] == "submit"
    assert "drain" in kinds
    routes = [h for h in j.hops if h["kind"] == "route"]
    assert len(routes) >= 2                   # placed, displaced, replaced
    assert routes[0]["where"] == 0 and routes[-1]["where"] == 1
    assert [h["hop"] for h in j.hops] == list(range(len(j.hops)))
    assert _frac_sum(j.summary) == pytest.approx(1.0, abs=1e-6)
    assert j.summary["attribution_s"]["requeue"] > 0.0
    ekinds = [e["kind"] for e in j.events]
    last_route = len(ekinds) - 1 - ekinds[::-1].index("route")
    assert ekinds.index("drain") < ekinds.index("requeue") < last_route
    assert ekinds[-1] == "finish"

    # Shared-recorder accounting: the fleet sample carries the journey
    # totals ONCE, not once per replica.
    pd = fleet.perfdb_sample()
    assert pd["journey_finished"] == float(fleet.journey.n_finished)
    assert "journey" in fleet.stats_snapshot()

    # explain_request over the dumped journal: exit 0, shows the chain,
    # and renders byte-identically for the same journal.
    from tools import explain_request

    journal = str(tmp_path / "journal.json")
    fleet.journey.dump_json(journal)
    j1 = explain_request.explain_from_journal(journal,
                                              req_id=str(requeued[0]),
                                              slowest=False)
    r1, r2 = explain_request.render(j1), explain_request.render(
        explain_request.explain_from_journal(journal,
                                             req_id=str(requeued[0]),
                                             slowest=False))
    assert r1 == r2
    assert "requeue" in r1 and "## Route decisions" in r1
    # the sum of seven fractions, each rounded to nine places
    fsum = float(r1.split("fraction sum = ")[1].split()[0])
    assert fsum == pytest.approx(1.0, abs=1e-8)
    assert explain_request.main(["--journal", journal, "--req",
                                 str(requeued[0]), "--out",
                                 str(tmp_path / "rep.md")]) == 0
    assert explain_request.main(["--journal", journal, "--req",
                                 "missing"]) == 1
    assert explain_request.main(["--journal",
                                 str(tmp_path / "nope.json"),
                                 "--slowest"]) == 2


# -- 5. chrome export rides the merge ---------------------------------------

def test_chrome_merge_carries_journey_rows_next_to_host_rows(tmp_path):
    td = str(tmp_path / "traces")
    tracer = trace.Tracer()
    tracer.enable()
    try:
        with tracer.span("host_work"):
            pass
        tracer.export_chrome_trace(td)
    finally:
        tracer.disable()
        tracer.reset()

    rec = JourneyRecorder(clock=TickClock())
    rec.begin("r")
    rec.event("r", "admit")
    rec.event("r", "decode_start")
    rec.finish("r", keep=True)
    jpath = rec.export_chrome_trace(td)
    assert jpath.endswith(".journey.json")

    merged = json.loads(open(trace.merge_chrome_traces(td)).read())
    evs = merged["traceEvents"]
    pnames = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert "journeys" in pnames               # the journey process row...
    assert any(n.startswith("rank") for n in pnames)   # ...beside host's
    jx = [e for e in evs if e.get("cat") == "journey" and e["ph"] == "X"]
    assert {e["name"] for e in jx} == {"queue", "prefill", "decode"}
    hx = [e for e in evs if e.get("name") == "host_work"]
    assert hx, "host span rows lost in the merge"
    jpids = {e["pid"] for e in jx}
    assert jpids.isdisjoint({e["pid"] for e in hx})    # no pid collision
    for e in jx:
        assert e["ts"] >= 0 and e["dur"] >= 0
