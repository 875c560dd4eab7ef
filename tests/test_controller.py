"""Adaptive control plane (serving/controller.py) — ISSUE 12 contracts.

Unit layer: the Knob rate limits (step cap, per-knob cooldown), the
relax hysteresis (tighten immediately, relax only after a clean OK
streak), reversal counting, and the control law's determinism — two
controllers fed the identical synthetic observation stream must produce
bit-identical action logs.

Fault layer: the ``controller.act`` site's do-nothing fallback — a
faulted tick discards every proposed move, leaves the knobs untouched,
and logs the skip.

Integration layer: a real ``BatchEngine`` under chaos with the
controller attached still traces each compiled step exactly once (knob
moves are data, never shape), and a fleet kill + cooldown-gated
``revive()`` replays bit-identically (fault log, state log, action log,
and generated tokens) across two runs with the same seed. Knobs, kills and
revives are host logic: the plants take the plain path
(``conftest.PLAIN_PATH``); no case stays on "fused".
"""

import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.resilience import (
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    default_fleet_chaos_plan,
    faults,
)
from triton_distributed_tpu.serving import Controller, Knob
from triton_distributed_tpu.serving.controller import default_engine_knobs


def _obs(*, level=0, queue=0, decode=0, prefill=0, backlog=0,
         free=1.0, step=0, dead=()):
    return {"level": level, "queue": queue, "decode_rows": decode,
            "prefill_rows": prefill, "backlog_tokens": backlog,
            "free_frac": free, "step": step, "dead": dead}


# ---------------------------------------------------------------------------
# Control-law units (plant-less controller, synthetic observations)
# ---------------------------------------------------------------------------


def test_tighten_is_rate_limited_to_knob_step():
    ctl = Controller()
    # WARN with decode rows: budget heads for lo=8, but only step=16/tick.
    ctl.tick(_obs(level=1, decode=2))
    assert ctl.knobs["prefill_budget"].value == 48.0
    ctl.tick(_obs(level=1, decode=2))
    assert ctl.knobs["prefill_budget"].value == 32.0


def test_knob_cooldown_blocks_consecutive_moves():
    knobs = default_engine_knobs(64, 0.0)
    knobs["prefill_budget"].cooldown = 3
    ctl = Controller(knobs=knobs)
    ctl.tick(_obs(level=1, decode=1))
    assert ctl.knobs["prefill_budget"].value == 48.0
    for _ in range(2):            # inside the cooldown: no move
        ctl.tick(_obs(level=1, decode=1))
        assert ctl.knobs["prefill_budget"].value == 48.0
    ctl.tick(_obs(level=1, decode=1))
    assert ctl.knobs["prefill_budget"].value == 32.0


def test_relax_needs_consecutive_ok_streak():
    ctl = Controller(relax_after=3)
    for _ in range(4):            # drive budget to lo under pressure
        ctl.tick(_obs(level=1, decode=1))
    assert ctl.knobs["prefill_budget"].value == 8.0
    # One OK tick, then WARN again: the streak resets, nothing relaxed.
    ctl.tick(_obs(level=0))
    assert ctl.knobs["prefill_budget"].value == 8.0
    ctl.tick(_obs(level=1, decode=1))
    ctl.tick(_obs(level=0))
    ctl.tick(_obs(level=0))
    assert ctl.knobs["prefill_budget"].value == 8.0   # streak still < 3
    ctl.tick(_obs(level=0))                           # third clean OK
    assert ctl.knobs["prefill_budget"].value == 24.0
    assert any(a["reason"] == "healthy: relax budget"
               for a in ctl.action_log)


def test_pure_prefill_widens_despite_pressure_history():
    """The hysteresis exemption: widening with zero decode rows cannot
    hurt TBT, so it skips the OK-streak gate (still rate-limited)."""
    ctl = Controller(relax_after=10 ** 6)
    ctl.tick(_obs(level=1, decode=1))
    assert ctl.knobs["prefill_budget"].value == 48.0
    ctl.tick(_obs(level=0, prefill=3, backlog=300))
    assert ctl.knobs["prefill_budget"].value == 64.0


def test_oscillation_counting():
    ctl = Controller(relax_after=1)
    ctl.tick(_obs(level=1, decode=1))          # down
    ctl.tick(_obs(level=0))                    # up (relax_after=1)
    ctl.tick(_obs(level=1, decode=1))          # down again
    assert ctl.knobs["prefill_budget"].reversals == 2
    assert ctl.oscillations >= 2


def test_knob_clamp_and_integer():
    k = Knob("x", value=5.0, lo=2.0, hi=9.0, step=4.0, relax_to=9.0,
             integer=True)
    assert k.clamp(100.0) == 9.0
    assert k.clamp(-3.0) == 2.0
    assert k.clamp(4.4) == 4.0


def test_determinism_same_obs_stream_identical_action_log():
    rng = np.random.default_rng(7)
    stream = [
        _obs(level=int(rng.integers(0, 3)),
             decode=int(rng.integers(0, 4)),
             prefill=int(rng.integers(0, 3)),
             backlog=int(rng.integers(0, 200)),
             free=float(rng.uniform(0.05, 1.0)),
             step=i)
        for i in range(60)
    ]
    logs = []
    for _ in range(2):
        ctl = Controller(relax_after=2)
        for obs in stream:
            ctl.tick(dict(obs))
        logs.append(ctl.action_log)
    assert logs[0] == logs[1]
    assert logs[0], "the stream produced no actions at all"


def test_stats_and_perfdb_sample_shapes():
    ctl = Controller()
    ctl.tick(_obs(level=1, decode=1))
    st = ctl.stats()
    assert set(st["knobs"]) == {"prefill_budget", "admission_pressure",
                                "reclaim_headroom"}
    assert st["actions"] >= 1 and st["last_action"]["knob"]
    sample = ctl.perfdb_sample()
    assert sample["controller_actions"] >= 1.0
    assert sample["controller_act_faults"] == 0.0


# ---------------------------------------------------------------------------
# controller.act fault site: the do-nothing fallback
# ---------------------------------------------------------------------------


def test_act_fault_discards_moves_and_logs_skip():
    ctl = Controller()
    plan = FaultPlan([FaultSpec(site="controller.act", kind="error",
                                p=1.0)], seed=0)
    with faults.plan(plan):
        applied = ctl.tick(_obs(level=1, decode=1))
    assert applied == []
    assert ctl.n_act_faults == 1
    # No knob moved: state stays coherent with the (unmutated) plant.
    assert ctl.knobs["prefill_budget"].value == 64.0
    assert ctl.knobs["admission_pressure"].value == 0.0
    [entry] = [a for a in ctl.action_log if a["knob"] == "__fault__"]
    assert "skipped" in entry["reason"]
    # The plant recovers on the next (unfaulted) tick.
    applied = ctl.tick(_obs(level=1, decode=1))
    assert applied and ctl.knobs["prefill_budget"].value == 48.0


# ---------------------------------------------------------------------------
# Integration: real plants
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1],
                     set_default=False)
    config = ModelConfig.from_name("tiny")
    return Engine(config, mesh=mesh, mode="xla", block_n=8)


def test_engine_control_sweep_zero_retraces_under_chaos(tiny_engine):
    """The tentpole guarantee: a full knob sweep (budget, pressure,
    reclaim all moving) with transient chaos on still compiles each step
    kind exactly once — adaptation is data, not shape."""
    from triton_distributed_tpu.serving import BatchEngine

    config = tiny_engine.config
    be = BatchEngine(tiny_engine, n_slots=4, n_blocks=24, block_size=4,
                     prefill_chunk=8,
                     retry=RetryPolicy(retries=6, base_delay_s=0.001),
                     **PLAIN_PATH)
    ctl = be.attach_controller(interval_steps=1, relax_after=2)
    rng = np.random.default_rng(0)
    plan = FaultPlan([
        FaultSpec(site="engine.decode", kind="error", p=0.05,
                  start_after=1),
        FaultSpec(site="pool.ensure", kind="error", p=0.03, start_after=2),
        FaultSpec(site="controller.act", kind="error", p=0.1,
                  start_after=1),
    ], seed=3)
    n = 24
    with faults.plan(plan):
        for i in range(n):
            be.submit(rng.integers(0, config.vocab_size,
                                   size=int(rng.integers(4, 14))).tolist(),
                      max_new_tokens=int(rng.integers(2, 8)))
            if i % 3 == 0:
                be.step()
        be.run()
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    done = len(be.finished) + len(be.failed)
    assert done == n
    assert len(be.failed) == 0      # all injected faults were retryable
    assert ctl.n_actions >= 1       # the sweep actually moved knobs
    be.pool.check_invariants()


def _fleet_adaptive_run(tiny_engine, seed: int):
    """One seeded fleet run with a transient kill + controller revive;
    returns every determinism witness the replay test compares."""
    from triton_distributed_tpu.serving import ROUTABLE, Fleet

    config = tiny_engine.config
    fleet = Fleet.build(tiny_engine, n_replicas=2, n_slots=2, n_blocks=16,
                        block_size=4, prefill_chunk=8, fail_threshold=2,
                        revive_cooldown_steps=4, **PLAIN_PATH)
    ctl = fleet.attach_controller(interval_steps=1, relax_after=2)
    plan = default_fleet_chaos_plan(seed, kill_replica=0, kill_after=3,
                                    kill_fires=2)
    rng = np.random.default_rng(0)      # workload fixed; seed moves faults
    work = [(rng.integers(0, config.vocab_size,
                          size=int(rng.integers(3, 8))).tolist(),
             int(rng.integers(2, 6))) for _ in range(16)]
    nxt = 0
    with faults.plan(plan):
        for step in range(400):
            while nxt < len(work) and nxt <= step // 2:
                prompt, gen = work[nxt]
                fleet.submit(prompt, max_new_tokens=gen,
                             req_id=f"r{nxt}")
                nxt += 1
            busy = fleet.step()
            fleet.check_invariants()
            if nxt >= len(work) and not busy and not fleet.pending:
                break
    assert not fleet.failed
    assert len(fleet.finished) == len(work)
    assert sum(rep.revives for rep in fleet.replicas) >= 1, \
        "the transient kill never exercised revive()"
    assert all(rep.state in ROUTABLE for rep in fleet.replicas)
    for rep in fleet.replicas:
        assert rep.engine.trace_counts == {"decode": 1, "prefill": 1}
    revive_log = [e for e in fleet.state_log
                  if e["to"] == "HEALTHY" and "revive" in e["reason"]]
    assert revive_log, "state log records no revival"
    return {
        "faults": [(ev.site, ev.call_index, ev.kind, ev.spec_index)
                   for ev in plan.log],
        "states": fleet.state_log,
        "actions": ctl.action_log,
        "outputs": {rid: list(req.output)
                    for rid, req in sorted(fleet.finished.items())},
        "revives": ctl.n_revives,
    }


def test_fleet_kill_revive_replays_bit_identically(tiny_engine):
    a = _fleet_adaptive_run(tiny_engine, seed=0)
    b = _fleet_adaptive_run(tiny_engine, seed=0)
    assert a["faults"] == b["faults"]
    assert a["states"] == b["states"]
    assert a["actions"] == b["actions"]
    assert a["outputs"] == b["outputs"]
    assert a["revives"] == b["revives"] >= 1


def test_revive_cooldown_and_state_gate(tiny_engine):
    """Fleet.revive is cooldown-gated (False until the death has aged
    ``revive_cooldown_steps`` fleet steps; ``force=True`` overrides) and
    refuses non-DEAD replicas outright."""
    from triton_distributed_tpu.serving import DEAD, Fleet

    fleet = Fleet.build(tiny_engine, n_replicas=2, n_slots=2, n_blocks=16,
                        block_size=4, prefill_chunk=8,
                        revive_cooldown_steps=5, **PLAIN_PATH)
    with pytest.raises(ValueError, match="not DEAD"):
        fleet.revive(0)
    rep = fleet.replicas[0]
    fleet._quarantine_replica(rep, "test kill")
    fleet._transition(rep, "DRAINING", "test")
    fleet._transition(rep, DEAD, "test")
    rep.died_at_step = fleet.n_steps
    assert fleet.revive(0) is False          # cooldown not yet served
    assert rep.state == DEAD and rep.revives == 0
    fleet.n_steps += 5
    assert fleet.revive(0) is True
    assert rep.state == "HEALTHY" and rep.revives == 1
    assert rep.died_at_step is None
    rep.engine.pool.check_invariants()
