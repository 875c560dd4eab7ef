"""Fleet + router tests (serving/fleet.py, serving/router.py).

The load-bearing guarantees (docs/serving.md, "Fleet & router"):
  1. router determinism — scoring is a pure weighted sum over the signal
     bundle (cache affinity wins, WARN sheds softly, BREACH is priced out
     unless everyone breaches, ties break least-recently-routed then by
     index), and the ``router.route`` fault site defers placement;
  2. kill survival — a seeded mid-decode replica kill quarantines/drains
     exactly that replica, the requeued requests finish BIT-IDENTICAL to
     their single-sequence golden runs on the survivors, nothing is lost
     or double-owned (``check_invariants`` every step), and no replica
     ever retraces (``trace_counts`` == {1,1} per replica);
  3. bounded requeue — a ``RetryPolicy(retries=0)`` budget turns the
     drain into a terminal failure carrying the full displacement chain;
  4. health machine — transient failure degrades, ``recovery_steps``
     clean steps recover (DEGRADED -> RECOVERED -> HEALTHY), a stale
     heartbeat on a busy replica quarantines;
  5. chaos determinism — same seed, same fleet => bit-identical fault
     log and state-transition schedule.
"""

import time

import jax
import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.resilience import (
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    TransientFault,
    Watchdog,
    default_fleet_chaos_plan,
    faults,
)
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import (
    DEAD,
    DEGRADED,
    DRAINING,
    HEALTHY,
    QUARANTINED,
    RECOVERED,
    ROUTABLE,
    Fleet,
    Router,
)


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


def _golden(engine, prompt, gen_len):
    out = engine.serve(np.asarray([prompt], np.int32), gen_len=gen_len)
    return np.asarray(out)[0]


def _build(engine, **kw):
    # Routing, requeue, health and schedules are host logic: every fleet of
    # this file takes the plain path, and no case stays on "fused".
    kw = {**PLAIN_PATH, **kw}
    kw.setdefault("n_replicas", 3)
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_blocks", 16)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("fail_threshold", 2)
    return Fleet.build(engine, **kw)


def _specs(config, n, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, config.vocab_size,
                          size=int(rng.integers(4, 9))).tolist(),
             int(rng.integers(3, 7))) for _ in range(n)]


# -- 1. router scoring ------------------------------------------------------

def test_router_prefers_cache_affinity():
    r = Router()
    cands = [(0, {"match_frac": 0.9, "headroom": 0.5, "load": 0.5,
                  "slo_level": 0}),
             (1, {"match_frac": 0.0, "headroom": 1.0, "load": 0.0,
                  "slo_level": 0})]
    d = r.route([1, 2, 3], cands)
    # 2.0*0.9 + 0.5*0.5 - 0.5 = 1.55 beats 0.5*1.0 = 0.5: the warm cache
    # outweighs the emptier replica.
    assert d.replica == 0
    assert d.scores[0] == pytest.approx(1.55)
    assert d.scores[1] == pytest.approx(0.5)
    # The decision carries the reproducibility witness.
    assert d.signals[0]["match_frac"] == 0.9


def test_router_sheds_slo_warn_and_breach():
    r = Router()
    base = {"match_frac": 0.0, "headroom": 1.0, "load": 0.0}
    # WARN sheds softly: an otherwise-equal OK replica wins...
    d = r.route([1], [(0, {**base, "slo_level": 1}),
                      (1, {**base, "slo_level": 0})])
    assert d.replica == 1
    # ...but a strong-enough cache hit still beats the WARN penalty.
    d = r.route([1], [(0, {**base, "match_frac": 0.9, "slo_level": 1}),
                      (1, {**base, "slo_level": 0})])
    assert d.replica == 0
    # BREACH is priced above any achievable signal sum...
    d = r.route([1], [(0, {**base, "match_frac": 1.0, "slo_level": 2}),
                      (1, {**base, "slo_level": 0})])
    assert d.replica == 1
    # ...yet a fleet entirely in BREACH still places (liveness).
    d = r.route([1], [(0, {**base, "slo_level": 2}),
                      (1, {**base, "slo_level": 2})])
    assert d is not None


def test_router_ties_break_round_robin_then_index():
    r = Router()
    sig = {"match_frac": 0.0, "headroom": 1.0, "load": 0.0, "slo_level": 0}
    cands = [(0, dict(sig)), (1, dict(sig)), (2, dict(sig))]
    picks = [r.route([1], cands).replica for _ in range(6)]
    # First pick is the lowest index; after that, least-recently-routed
    # cycles deterministically.
    assert picks == [0, 1, 2, 0, 1, 2]
    assert r.route([1], []) is None


def test_router_route_is_a_fault_site():
    r = Router()
    plan = FaultPlan([FaultSpec(site="router.route", kind="error", p=1.0)],
                     seed=0)
    sig = {"match_frac": 0.0, "headroom": 1.0, "load": 0.0, "slo_level": 0}
    with faults.plan(plan):
        with pytest.raises(TransientFault):
            r.route([1], [(0, sig)])
    assert plan.n_fired == 1
    # No half-made decision: the clock never advanced.
    assert r.n_routed == 0


# -- 2. seeded kill mid-decode ---------------------------------------------

def test_fleet_kill_survivors_bit_identical(setup):
    """Replica 0 wedges permanently mid-decode; the fleet must quarantine
    and drain it, requeue its in-flight work onto the survivors, and every
    request must still finish with the exact single-sequence greedy
    tokens — all without a single retrace on any replica."""
    _, config, engine = setup
    fleet = _build(engine)
    specs = _specs(config, 9)
    rids = [fleet.submit(p, max_new_tokens=g) for p, g in specs]
    plan = default_fleet_chaos_plan(seed=0, kill_replica=0, kill_after=4)
    with faults.plan(plan):
        while fleet.step() or fleet.pending:
            fleet.check_invariants()
            assert fleet.n_steps < 2000
    fleet.check_invariants()

    assert not fleet.failed, f"unexpected failures: {fleet.failed}"
    out = {rid: list(req.output) for rid, req in fleet.finished.items()}
    assert sorted(out) == sorted(rids)
    for rid, (p, g) in zip(rids, specs):
        np.testing.assert_array_equal(
            np.asarray(out[rid], np.int32), _golden(engine, p, g),
            err_msg=f"request {rid} diverged after requeue")

    # Exactly the killed replica died; the survivors stayed routable.
    states = [rep.state for rep in fleet.replicas]
    assert states[0] == DEAD
    assert all(s in ROUTABLE for s in states[1:])
    fm = fleet.metrics.as_dict()
    assert fm["replica_quarantines"] == 1
    assert fm["requeues"] >= 1
    assert any(fleet.requeue_chain(r) for r in rids)
    # The one-compile-per-step-shape guarantee holds PER REPLICA through
    # the kill, drain, and requeues.
    for rep in fleet.replicas:
        for kind, n in rep.engine.trace_counts.items():
            assert n <= 1, f"replica {rep.idx} retraced {kind}"


def test_fleet_requeue_budget_exhausts_with_reason_chain(setup):
    """retries=0: the first displacement is terminal — the request fails
    carrying the quarantine reason plus the exhaustion marker, and the
    untouched requests still complete."""
    _, config, engine = setup
    fleet = _build(engine, requeue=RetryPolicy(retries=0))
    specs = _specs(config, 6, seed=3)
    rids = [fleet.submit(p, max_new_tokens=g) for p, g in specs]
    plan = default_fleet_chaos_plan(seed=0, kill_replica=0, kill_after=3)
    with faults.plan(plan):
        out = fleet.run(max_steps=2000)
    fleet.check_invariants()

    failed = fleet.failed
    assert failed, "the kill should displace at least one in-flight request"
    assert len(out) + len(failed) == len(rids)
    for rid, req in failed.items():
        assert "requeue budget exhausted (0 allowed)" in req.error
        assert "quarantined" in req.error      # the displacement reason
        chain = fleet.requeue_chain(rid)
        assert chain and "quarantined" in chain[0]
    fm = fleet.metrics.as_dict()
    assert fm["requeue_exhausted"] == len(failed)
    # Survivor requests still match golden.
    for rid, (p, g) in zip(rids, specs):
        if rid in out:
            np.testing.assert_array_equal(np.asarray(out[rid], np.int32),
                                          _golden(engine, p, g))


def test_fleet_dead_fleet_fails_pending(setup):
    """Every replica dead => queued work fails loudly with the terminal
    reason instead of spinning."""
    _, config, engine = setup
    fleet = _build(engine, n_replicas=2, fail_threshold=1)
    specs = _specs(config, 4, seed=5)
    rids = [fleet.submit(p, max_new_tokens=g) for p, g in specs]
    plan = FaultPlan([
        FaultSpec(site="replica.*", kind="error", p=1.0, start_after=0),
    ], seed=0)
    with faults.plan(plan):
        fleet.run(max_steps=200)
    assert all(rep.state == DEAD for rep in fleet.replicas)
    assert sorted(fleet.failed) == sorted(rids)
    assert any("no routable replicas (fleet dead)" in req.error
               for req in fleet.failed.values())
    fleet.check_invariants()


# -- 3. health machine ------------------------------------------------------

def test_health_degrade_then_recover(setup):
    """One transient step failure: HEALTHY -> DEGRADED, then
    ``recovery_steps`` clean steps -> RECOVERED, one more -> HEALTHY."""
    _, _, engine = setup
    fleet = _build(engine, fail_threshold=3, recovery_steps=2)
    rep = fleet.replicas[0]
    plan = FaultPlan([FaultSpec(site="replica.0.step", kind="error",
                                p=1.0, max_fires=1)], seed=0)
    with faults.plan(plan):
        fleet.step()
    assert rep.state == DEGRADED and rep.consecutive_failures == 1
    fleet.step()                      # clean step: failure streak closes
    assert rep.consecutive_failures == 0
    fleet.step()
    fleet.step()
    assert rep.state == RECOVERED
    fleet.step()
    assert rep.state == HEALTHY
    path = [(e["from"], e["to"]) for e in fleet.state_log
            if e["replica"] == 0]
    assert path == [(HEALTHY, DEGRADED), (DEGRADED, RECOVERED),
                    (RECOVERED, HEALTHY)]
    fm = fleet.metrics.as_dict()
    assert fm["replica_recoveries"] == 1
    assert "replica_quarantines" not in fm


def test_health_heartbeat_stale_quarantines_busy_replica(setup):
    """A stale heartbeat on a replica WITH active slots quarantines it
    (idle staleness is ignored — an idle engine legitimately stops
    beating); the drained request finishes on a survivor."""
    _, config, engine = setup
    fleet = _build(engine)
    rep0 = fleet.replicas[0]
    rep0.engine.attach_watchdog(Watchdog(), heartbeat_interval_s=30.0)
    hb = rep0.engine.heartbeat

    # Idle + stale: NOT a wedge.
    hb._last = time.monotonic() - 999.0
    fleet.step()
    assert rep0.state == HEALTHY

    rid = fleet.submit([1, 2, 3, 4], max_new_tokens=4)
    fleet.step()                       # routes to replica 0 and prefill
    assert rep0.active_slots == 1      # (stepping beat the heartbeat)
    hb._last = time.monotonic() - 999.0
    fleet.step()                       # busy + stale => quarantine
    assert rep0.state in (QUARANTINED, DRAINING)
    assert "heartbeat stale" in rep0.quarantine_reason
    out = fleet.run(max_steps=500)
    assert rid in out
    assert rep0.state == DEAD
    np.testing.assert_array_equal(np.asarray(out[rid], np.int32),
                                  _golden(engine, [1, 2, 3, 4], 4))
    fleet.check_invariants()


# -- 4. chaos determinism ---------------------------------------------------

def test_fleet_chaos_same_seed_same_schedule(setup):
    """Same seed + same fleet => bit-identical fault log AND state
    transition schedule (the replay witness chaos triage depends on)."""
    _, config, engine = setup

    def run(seed):
        fleet = _build(engine)
        for p, g in _specs(config, 6, seed=1):
            fleet.submit(p, max_new_tokens=g)
        plan = default_fleet_chaos_plan(seed=seed, kill_replica=1,
                                        kill_after=3)
        with faults.plan(plan):
            out = fleet.run(max_steps=2000)
        flog = [(e.site, e.kind, e.call_index) for e in plan.log]
        slog = [(e["step"], e["replica"], e["from"], e["to"])
                for e in fleet.state_log]
        return out, flog, slog

    out_a, flog_a, slog_a = run(7)
    out_b, flog_b, slog_b = run(7)
    assert flog_a == flog_b
    assert slog_a == slog_b
    assert out_a == out_b
