"""Resilience layer tests (resilience/ + its serving-path threading).

The load-bearing guarantees (docs/resilience.md):
  1. determinism — the same ``FaultPlan`` seed against the same call
     sequence fires the bit-identical fault sequence (``plan.log``);
  2. graceful degradation — a quarantined request leaves the SURVIVORS'
     greedy output bit-identical to a fault-free run, and a chaos run
     completes with every request accounted for (ok or failed) without a
     single retrace;
  3. watchdog — deadline breach raises ``WatchdogTimeout`` AND dumps a
     snapshot containing the in-flight request table;
  4. anti-starvation — a request preempted ``preemption_cap`` times ages
     out of the victim pool and gets to finish;
  5. allocator honesty — releasing an unknown/already-released seq_id
     raises instead of silently no-opping.

Faults, retries, quarantine, the watchdog and admission are host logic: every
engine of this file takes the plain path (``conftest.PLAIN_PATH``); no case
stays on "fused".
"""

import time

import jax
import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.obs import comm_ledger
from triton_distributed_tpu.resilience import (
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    TransientFault,
    Watchdog,
    WatchdogTimeout,
    default_chaos_plan,
    faults,
    install_hooks,
    uninstall_hooks,
)
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import BatchEngine, KVPool, Request, \
    Scheduler


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    faults.uninstall()
    comm_ledger.set_resilience_hooks(pre_call=None, deadline=None)


def _golden(engine, prompt, gen_len):
    out = engine.serve(np.asarray([prompt], np.int32), gen_len=gen_len)
    return np.asarray(out)[0]


# -- 1. fault plan ----------------------------------------------------------

def _drive(plan, n=200):
    events = []
    for i in range(n):
        site = ("engine.decode", "pool.ensure", "comm.all_gather")[i % 3]
        try:
            d = plan.fire(site)
        except TransientFault:
            d = "error"
        events.append(d)
    return events


def test_fault_plan_seed_determinism():
    specs = [FaultSpec(site="engine.decode", kind="error", p=0.3),
             FaultSpec(site="pool.ensure", kind="error", p=0.2,
                       start_after=3),
             FaultSpec(site="comm.*", kind="error", p=0.25),
             FaultSpec(site="engine.decode", kind="nan", p=0.2, row=2)]
    a, b = FaultPlan(specs, seed=7), FaultPlan(specs, seed=7)
    ea, eb = _drive(a), _drive(b)
    assert ea == eb
    assert a.log == b.log               # the bit-identical witness
    assert a.n_fired > 0                # the plan actually did something
    c = FaultPlan(specs, seed=8)
    _drive(c)
    assert c.log != a.log               # seed moves the sequence


def test_fault_spec_validation_and_matching():
    with pytest.raises(ValueError):
        FaultSpec(site="x", kind="bogus")
    with pytest.raises(ValueError):
        FaultSpec(site="x", kind="error", p=1.5)
    assert FaultSpec(site="comm.*", kind="error").matches("comm.all_gather")
    assert not FaultSpec(site="comm.*", kind="error").matches("pool.ensure")


def test_fault_plan_start_after_and_max_fires():
    plan = FaultPlan([FaultSpec(site="s", kind="error", p=1.0,
                                start_after=2, max_fires=2)])
    fired = []
    for _ in range(6):
        try:
            plan.fire("s")
            fired.append(False)
        except TransientFault:
            fired.append(True)
    assert fired == [False, False, True, True, False, False]


def test_pool_ensure_is_a_fault_site(setup):
    _, config, _ = setup
    pool = KVPool(config, n_blocks=4, block_size=4, max_seq_len=16)
    with faults.plan(FaultPlan([FaultSpec(site="pool.ensure", kind="error",
                                          p=1.0)])):
        with pytest.raises(TransientFault):
            pool.ensure("a", 4)
    # the fault fired BEFORE any mutation
    assert pool.n_free == 4 and pool.owned("a") == 0
    pool.check_invariants()
    assert pool.ensure("a", 4)          # uninstalled: clean path


def test_nan_directive():
    plan = FaultPlan([FaultSpec(site="engine.decode", kind="nan", p=1.0,
                                row=3)])
    assert plan.fire("engine.decode") == ("nan", 3)


# -- 2. retry policy --------------------------------------------------------

def test_retry_policy_recovers_and_reports_latency():
    calls, sleeps, recovered = [], [], []
    pol = RetryPolicy(retries=3, base_delay_s=0.01, max_delay_s=0.02)

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientFault("flake")
        return "ok"

    out = pol.run(flaky, on_recovery=recovered.append,
                  sleep=sleeps.append)
    assert out == "ok" and len(calls) == 3
    assert sleeps == [0.01, 0.02]       # doubling, capped at max_delay_s
    assert len(recovered) == 1 and recovered[0] >= 0.0


def test_retry_policy_exhausts_and_ignores_non_retryable():
    pol = RetryPolicy(retries=2)
    with pytest.raises(TransientFault):
        pol.run(lambda: (_ for _ in ()).throw(TransientFault("x")),
                sleep=lambda _: None)
    with pytest.raises(ValueError):     # not retryable: propagates at once
        pol.run(lambda: (_ for _ in ()).throw(ValueError("x")),
                sleep=lambda _: None)


# -- 3. pool release honesty ------------------------------------------------

def test_pool_release_unknown_and_double_release_raise(setup):
    _, config, _ = setup
    pool = KVPool(config, n_blocks=4, block_size=4, max_seq_len=16)
    with pytest.raises(KeyError):
        pool.release("never-allocated")
    assert pool.ensure("a", 4)
    pool.release("a")
    with pytest.raises(KeyError):       # double release
        pool.release("a")
    pool.check_invariants()
    # check_invariants itself flags a stale empty table
    pool._tables["ghost"] = []
    with pytest.raises(AssertionError):
        pool.check_invariants()
    del pool._tables["ghost"]


# -- 4. scheduler aging (anti-starvation) -----------------------------------

def test_select_victim_skips_aged_requests():
    young = Request(req_id="y", prompt=[1], max_new_tokens=1, priority=0)
    old = Request(req_id="o", prompt=[1], max_new_tokens=1, priority=0,
                  n_preemptions=4)
    hi = Request(req_id="h", prompt=[1], max_new_tokens=1, priority=5)
    running = [(0, old, 0), (1, young, 1), (2, hi, 2)]
    # uncapped: old (priority 0, latest? no — young is later). LIFO picks
    # the LATEST-admitted among lowest priority: that's young either way.
    assert Scheduler.select_victim(running) == 1
    # with young also aged, the cap excludes both zeros -> hi is the only
    # candidate left
    young.n_preemptions = 4
    assert Scheduler.select_victim(running, preemption_cap=4) == 2
    old.n_preemptions = young.n_preemptions = hi.n_preemptions = 4
    assert Scheduler.select_victim(running, preemption_cap=4) is None
    assert Scheduler.select_victim(running) == 1  # cap-free fallback


def test_starvation_cap_lets_low_priority_finish(setup):
    """Regression: a low-priority request under sustained high-priority
    pressure used to livelock (evict -> re-prefill -> evict). The aging
    cap bounds its preemptions and it completes."""
    _, config, engine = setup
    be = BatchEngine(engine, n_slots=2, n_blocks=6, block_size=4,
                     prefill_chunk=8, max_seq_len=24, **PLAIN_PATH)
    cap = be.scheduler.preemption_cap
    assert cap is not None
    lo = be.submit([5, 6, 7], max_new_tokens=8, priority=0, req_id="lo")
    for i in range(6):
        be.submit([10 + i] * 4, max_new_tokens=6, priority=5,
                  req_id=f"hi{i}")
    out = be.run(max_steps=500)
    assert set(out) == {"lo"} | {f"hi{i}" for i in range(6)}
    assert len(out["lo"]) == 8
    assert be.finished["lo"].n_preemptions <= cap
    assert be.finished["lo"].status == "ok"
    be.pool.check_invariants()


# -- 5. quarantine: graceful degradation ------------------------------------

def test_quarantined_request_leaves_survivors_bit_identical(setup):
    """A NaN-poisoned slot is quarantined with an error status; every
    surviving request's greedy output is bit-identical to the single-
    sequence reference — the fault handling touched masks, not math."""
    _, config, engine = setup
    be = BatchEngine(engine, n_slots=4, n_blocks=16, block_size=4,
                     prefill_chunk=8, **PLAIN_PATH)
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5], [3, 5, 8, 9, 7, 9]]
    for i, p in enumerate(prompts):
        be.submit(p, max_new_tokens=6, req_id=f"r{i}")
    # slot 0 holds r0 (first admitted); poison its logits on the second
    # decode step, exactly once
    plan = FaultPlan([FaultSpec(site="engine.decode", kind="nan", p=1.0,
                                row=0, start_after=1, max_fires=1)])
    with faults.plan(plan):
        out = be.run(max_steps=200)
    assert plan.n_fired == 1
    assert set(be.failed) == {"r0"}
    r0 = be.failed["r0"]
    assert r0.status == "failed" and "non-finite" in r0.error
    assert "r0" not in out
    # survivors: bit-identical to the fault-free single-sequence runs
    for i in (1, 2):
        assert out[f"r{i}"] == _golden(engine, prompts[i], 6).tolist()
        assert be.finished[f"r{i}"].status == "ok"
    # failure handling never re-specialized the compiled steps
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    be.pool.check_invariants()
    # drained: every block is free or prefix-cached with zero references
    assert be.pool.n_free + be.pool.n_reclaimable == be.pool.n_blocks


def test_transient_step_faults_are_invisible_after_retry(setup):
    """Errors within the retry budget change NOTHING about the output —
    the attempt fails before the compiled step consumes its donated
    buffers, so the re-run starts from intact state."""
    _, config, engine = setup
    be = BatchEngine(engine, n_slots=2, n_blocks=8, block_size=4,
                     prefill_chunk=8, **PLAIN_PATH)
    prompt = [7, 3, 2, 6]
    be.submit(prompt, max_new_tokens=5, req_id="r")
    plan = FaultPlan([FaultSpec(site="engine.decode", kind="error", p=1.0,
                                start_after=1, max_fires=2),
                      FaultSpec(site="engine.prefill", kind="error", p=1.0,
                                start_after=0, max_fires=1)])
    with faults.plan(plan):
        out = be.run(max_steps=100)
    assert plan.n_fired == 3
    assert out["r"] == _golden(engine, prompt, 5).tolist()
    assert not be.failed
    m = be.metrics.as_dict()
    assert m["step_retries"] >= 3 and m["step_recoveries"] >= 2
    assert m["recovery_s_count"] >= 2
    assert be.trace_counts == {"decode": 1, "prefill": 1}


def test_chaos_plan_run_completes_and_accounts(setup):
    _, config, engine = setup
    be = BatchEngine(engine, n_slots=4, n_blocks=12, block_size=4,
                     prefill_chunk=8, retry=RetryPolicy(retries=6),
                     **PLAIN_PATH)
    n = 8
    rng = np.random.default_rng(0)
    for i in range(n):
        be.submit(rng.integers(1, config.vocab_size,
                               size=int(rng.integers(3, 10))).tolist(),
                  max_new_tokens=int(rng.integers(2, 7)), req_id=f"q{i}")
    chaos = default_chaos_plan(seed=3, error_p=0.15, nan_p=0.15)
    with faults.plan(chaos):
        out = be.run(max_steps=2000)
    assert chaos.n_fired > 0
    assert len(out) + len(be.failed) == n
    for req in be.failed.values():
        assert req.status == "failed" and req.error
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    be.pool.check_invariants()
    # drained: every block is free or prefix-cached with zero references
    assert be.pool.n_free + be.pool.n_reclaimable == be.pool.n_blocks


def test_faulted_cache_lookup_degrades_to_cold_prefill(setup):
    """Satellite: a faulted ``cache.lookup`` must read as a cache MISS —
    the request re-prefills cold, emits bit-identical output, scores zero
    hits, and leaves every refcount exactly as it was (the fault site
    fires before the cache touches any state)."""
    _, config, engine = setup
    be = BatchEngine(engine, n_slots=2, n_blocks=16, block_size=4,
                     prefill_chunk=8, **PLAIN_PATH)
    prompt = [5, 3, 5, 3, 5, 3, 5, 3, 2]
    golden = _golden(engine, prompt, 4).tolist()
    be.submit(prompt, max_new_tokens=4, req_id="warm")
    out = be.run()
    assert out["warm"] == golden
    assert be.pool.n_cached > 0           # the tree is populated
    cached_before = sorted(be.pool._cached.items())
    # now EVERY lookup faults: the identical prompt would have hit
    plan = FaultPlan([FaultSpec(site="cache.lookup", kind="error", p=1.0)])
    install_hooks(plan=plan)
    try:
        be.submit(prompt, max_new_tokens=4, req_id="again")
        out = be.run()
    finally:
        uninstall_hooks()
    assert plan.n_fired > 0               # the site actually bit
    assert out["again"] == golden         # cold prefill, correct output
    m = be.metrics.as_dict()
    assert m.get("prefix_hits", 0) == 0   # degraded, not served from cache
    assert m["prefix_lookup_faults"] > 0
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    be.pool.check_invariants()
    # refcounts untouched by the faulted lookups: same resident set, all
    # references back to zero after the drain
    assert sorted(be.pool._cached.items()) == cached_before
    assert be.pool.n_free + be.pool.n_reclaimable == be.pool.n_blocks
    # control: with the plan gone the same prompt DOES hit
    be.submit(prompt, max_new_tokens=4, req_id="hit")
    out = be.run()
    assert out["hit"] == golden
    assert be.metrics.as_dict()["prefix_hits"] >= 1


def test_disabled_plan_is_bit_identical(setup):
    """No plan installed: the resilience threading must be invisible —
    same tokens as the single-sequence reference, statuses 'ok'."""
    _, config, engine = setup
    be = BatchEngine(engine, n_slots=2, n_blocks=8, block_size=4,
                     prefill_chunk=8, **PLAIN_PATH)
    prompt = [2, 7, 1, 8, 2, 8]
    be.submit(prompt, max_new_tokens=4, req_id="r")
    out = be.run()
    assert out["r"] == _golden(engine, prompt, 4).tolist()
    assert be.finished["r"].status == "ok" and not be.failed


# -- 6. backpressure --------------------------------------------------------

def test_admission_backpressure(setup):
    _, config, engine = setup
    be = BatchEngine(engine, n_slots=2, n_blocks=8, block_size=4,
                     prefill_chunk=8, max_seq_len=24,
                     admission_pressure=0.9, **PLAIN_PATH)
    be.submit([1, 2, 3, 4], max_new_tokens=4, req_id="a")
    be.step()                           # 'a' resident: pool 75% free < 90%
    be.submit([5, 6, 7, 8], max_new_tokens=4, req_id="b")
    be.step()
    assert be.metrics.as_dict()["admission_backpressure"] > 0
    assert be.finished == {}            # 'b' deferred, nothing lost
    out = be.run(max_steps=300)
    # both finish: backpressure defers, never deadlocks — once 'a' drains
    # the engine goes idle and idle admission is never blocked
    assert set(out) == {"a", "b"}


# -- 7. watchdog ------------------------------------------------------------

def test_watchdog_deadline_breach_raises_and_snapshots(tmp_path):
    snap_file = tmp_path / "snap.json"
    wd = Watchdog(snapshot_provider=lambda: {"in_flight": [{"slot": 0}]},
                  snapshot_path=str(snap_file))
    with wd.deadline("fast", seconds=5.0):
        pass                            # well under deadline: no breach
    assert not wd.breaches
    with pytest.raises(WatchdogTimeout):
        with wd.deadline("slow", seconds=0.05):
            time.sleep(0.3)
    assert wd.breaches and "slow" in wd.breaches[-1]
    assert wd.last_snapshot["in_flight"] == [{"slot": 0}]
    assert "comm_ledger" in wd.last_snapshot
    assert snap_file.exists()


def test_watchdog_snapshot_contains_in_flight_table(setup):
    """The engine-attached watchdog's snapshot carries the live request
    table — the thing an operator needs when a step wedges."""
    _, config, engine = setup
    be = BatchEngine(engine, n_slots=2, n_blocks=8, block_size=4,
                     prefill_chunk=8, **PLAIN_PATH)
    wd = be.attach_watchdog(Watchdog(), step_deadline_s=300.0)
    be.submit([1, 2, 3], max_new_tokens=6, req_id="w0")
    be.submit([4, 5, 6, 7], max_new_tokens=6, req_id="w1")
    be.run(max_steps=2)                 # leave both requests in flight
    snap = wd.snapshot("manual-probe")
    rows = {r["req_id"]: r for r in snap["in_flight"]}
    assert set(rows) == {"w0", "w1"}
    for r in rows.values():
        assert {"slot", "phase", "offset", "ctx_len", "generated",
                "priority", "n_preemptions"} <= set(r)
    assert snap["pool"]["n_blocks"] == 8
    assert "metrics" in snap and "comm_ledger" in snap
    be.run()                            # drain


def test_heartbeat_staleness():
    wd = Watchdog()
    hb = wd.heartbeat("loop", interval_s=0.05)
    hb.beat()
    time.sleep(0.12)
    with pytest.raises(WatchdogTimeout):
        hb.beat()
    hb.beat()                           # breach consumed; loop may resume
    time.sleep(0.12)
    with pytest.raises(WatchdogTimeout):
        hb.check()


def test_heartbeat_stale_poll_registers_nothing():
    """``stale()``/``age()`` are PURE polls for an external health machine
    (the fleet's): they flag staleness without registering a breach or
    dumping a snapshot — the breach-raising beat()/check() path is
    untouched."""
    wd = Watchdog()
    hb = wd.heartbeat("loop", interval_s=0.05)
    hb.beat()
    assert not hb.stale()
    assert 0.0 <= hb.age() < 0.05
    time.sleep(0.12)
    assert hb.stale() and hb.age() > 0.05
    assert not wd.breaches and not hb._breached
    with pytest.raises(WatchdogTimeout):    # beat() still escalates
        hb.beat()


def test_heartbeat_stop_monitor_idempotent_and_restartable():
    """A fleet teardown may stop a heartbeat that never had a monitor, or
    stop one twice; and a start/stop/start cycle must hand the new thread
    a FRESH stop flag (not the already-set one)."""
    wd = Watchdog()
    hb = wd.heartbeat("loop", interval_s=30.0)
    hb.stop_monitor()                   # no monitor: a no-op
    hb.start_monitor()
    t1 = hb._thread
    assert t1 is not None and t1.is_alive()
    hb.start_monitor()                  # already running: same thread
    assert hb._thread is t1
    hb.stop_monitor()
    assert hb._thread is None and not t1.is_alive()
    hb.stop_monitor()                   # double stop: still a no-op
    hb.start_monitor()
    t2 = hb._thread
    assert t2 is not t1 and t2.is_alive()
    hb.stop_monitor(join_timeout_s=1.0)
    assert not t2.is_alive()


# -- 8. comm-ledger hooks ---------------------------------------------------

def test_comm_hooks_fire_without_ledger_enabled(setup):
    """install_hooks makes every host collective wrapper a fault site even
    with ledger recording OFF (the active() gate)."""
    mesh, _, _ = setup
    from triton_distributed_tpu.kernels.allgather import all_gather

    assert not comm_ledger.enabled()
    x = np.ones((1, 4, 128), np.float32)
    install_hooks(plan=FaultPlan([FaultSpec(site="comm.*", kind="error",
                                            p=1.0)]))
    try:
        assert comm_ledger.active()
        with pytest.raises(TransientFault):
            all_gather(x, mesh=mesh, axis="tp")
    finally:
        uninstall_hooks()
    assert not comm_ledger.active()
    jax.block_until_ready(all_gather(x, mesh=mesh, axis="tp"))  # clean


def test_comm_deadline_hook(setup):
    mesh, _, _ = setup
    from triton_distributed_tpu.kernels.allgather import all_gather

    wd = Watchdog()
    install_hooks(watchdog=wd, collective_deadline_s=300.0)
    try:
        assert comm_ledger.active()
        jax.block_until_ready(all_gather(np.ones((1, 4, 128), np.float32),
                                         mesh=mesh, axis="tp"))
        assert not wd.breaches          # generous deadline: no breach
    finally:
        uninstall_hooks()
