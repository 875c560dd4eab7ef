#!/usr/bin/env python
"""Synthetic-load serving smoke: Poisson arrivals through BatchEngine.

Drives the continuous-batching engine (serving/batch_engine.py) with an
open-loop Poisson arrival process on the tiny model for ``--duration``
seconds (default 30), then drains, and FAILS (exit 1) if either compiled
step retraced beyond its first compile — the subsystem's core guarantee is
that slot churn (arrivals, completions, preemptions) is data, not shape.

Runs on CPU (``JAX_PLATFORMS=cpu scripts/serve_smoke.py``) or TPU alike.
``main()`` is importable; tests/test_serve_smoke.py runs it with a short
duration as a tier-1 test.

``--chaos`` additionally installs the stock fault plan
(``resilience.default_chaos_plan``: transient step/allocator errors plus
NaN-poisoned logit rows) with aggressive rates and asserts GRACEFUL
DEGRADATION instead of full completion: the engine must finish the run
(no crash, no retrace), every submitted request must end as either
completed or quarantined-with-error, at least one request of each kind
must exist, and the pool must still drain clean.

``--spec`` runs the speculative-decoding arm: the same deterministic
workload through a speculative engine (n-gram drafter + fused verify +
KV rollback) and a plain engine, asserting byte-identical outputs,
nonzero accepted draft tokens, and zero retraces on either engine.

``--incidents`` runs the incident-engine arm: a clean closed-loop phase
that must open ZERO incidents (flap-freedom/precision), then a seeded
NaN fault plan at ``engine.decode`` that must open >= 1 incident whose
TOP-ranked suspect names the injected site with near-immediate detection
latency (recall + attribution).

``--restore`` runs the crash-recovery arm: Poisson load through a
journaled fleet, a mid-flight checkpoint, a simulated power cut
(``journal.crash()`` — the un-fsynced tail is lost), then
``Fleet.restore`` onto fresh replicas sharing the dead fleet's compiled
steps. FAILS unless zero requests are lost, at least one request
finishes after the restore, and no replica retraces.

``--whatif`` runs the deterministic-replay arm (obs/replay.py): a short
discretized-Poisson fleet run is recorded by the always-on ``ServeTrace``,
the baseline replay through ``ReplayHarness`` must be bit-identical to
the live run (same outputs, zero lost, zero retraces, ``trace_counts``
{1,1}), and one counterfactual (full prefill budget vs the run's
throttled one) must produce a ranked what-if report.

``--kvq`` runs the quantized-KV-cache arm: one BatchEngine with
``kv_dtype`` (int8 by default, fp8 via ``--kv-dtype``) on a pool tight
enough to preempt, serving a shared-prefix workload cold then warm on
the same engine. FAILS unless the warm outputs — produced from
CoW-adopted quantized cached blocks — are byte-identical to cold over
64 decode steps, prefix hits are nonzero, preemption churn actually
occurred, and ``trace_counts`` stays {1,1}.

``--replicas N`` (N >= 2) switches to the FLEET path (serving/fleet.py):
N replicas behind the cache/SLO-aware router. Plain run: everything
completes, no replica leaves the ROUTABLE states, every replica's two
steps compiled at most once. With ``--chaos``, a seeded kill
(``resilience.default_fleet_chaos_plan``) wedges one replica and the run
asserts the fleet contract instead: >= 1 replica quarantined AND 100% of
the survivor-served requests complete, requeues stay within budget, the
ownership invariants hold, and per-replica ``trace_counts`` stays {1,1}.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _host_arm_attn() -> str:
    """``paged_attn`` for the arms whose contract is the HOST's: the fused
    kernel where it is compiled, its bit-identical gather oracle where it
    would be interpreted. Two reasons, one choice. (1) Verdicts made of
    wall-clock seconds sized for steps of milliseconds — the SLO windows and
    detectors of ``--adaptive`` and ``--incidents``, ``--slo``'s verdicts,
    ``--whatif``'s cost model, calibrated from the seconds its steps took.
    Interpreted, a kernel call costs ~0.3 s (and a mixed step makes two):
    ``main_adaptive``'s 2 s fast window never holds ``min_count`` TTFT
    samples and WARN cannot fire, a first token waits for seconds of
    compilation on a busy machine, and a prefilling step is priced by the
    interpreter, not by its work. (2) Contracts no step's attention
    arithmetic decides — ``--chaos`` (quarantine, retries, accounting),
    ``--replicas`` (kill, drain, requeue, ownership), ``--restore`` (journal,
    checkpoint, zero lost), ``--spec`` (acceptance, rollback, lossless
    against the plain engine), ``--efficiency`` (the ledger's fractions):
    interpreted they cost minutes of tier-1 for what
    ``tests/test_paged_attention.py`` holds once, that the two paths serve
    the same tokens. So on a CPU two arms cover the fused kernel: the plain
    run (Poisson churn through it) and ``--kvq`` (its dequantization of
    adopted blocks)."""
    from triton_distributed_tpu.runtime.platform import on_tpu

    return "fused" if on_tpu() else "gather"


def main_fleet(duration_s: float = 30.0, *, rate_hz: float = 4.0,
               n_replicas: int = 3, n_slots: int = 4,
               n_blocks: int | None = 12, seed: int = 0,
               chaos: bool = False, perfdb_path: str | None = None,
               stats_jsonl: str | None = None) -> dict:
    """The ``--replicas N`` arm: Poisson load through a ``Fleet`` of N
    replicas. Plain run asserts full completion with every replica still
    routable; ``chaos`` installs the seeded replica-kill plan and asserts
    the fleet contract (>= 1 replica quarantined, 100% of survivor
    requests ok, ownership invariants hold every step, per-replica
    ``trace_counts`` stays {1,1}). Raises RuntimeError on violation."""
    import contextlib

    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.resilience import (
        default_fleet_chaos_plan,
        faults,
    )
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving import DEAD, ROUTABLE, Fleet

    if n_replicas < 2:
        raise ValueError("--replicas needs >= 2 (use the single-engine "
                         "path otherwise)")
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    fleet = Fleet.build(engine, n_replicas=n_replicas, n_slots=n_slots,
                        n_blocks=n_blocks, block_size=4, prefill_chunk=8,
                        fail_threshold=2, paged_attn=_host_arm_attn())
    plan = None
    plan_ctx = contextlib.nullcontext()
    if chaos:
        plan = default_fleet_chaos_plan(seed,
                                        kill_replica=seed % n_replicas,
                                        kill_after=8)
        plan_ctx = faults.plan(plan)

    rng = np.random.default_rng(seed)
    start = time.monotonic()
    deadline = start + duration_s
    next_arrival = start
    next_stats = 0.0
    submitted = 0
    with plan_ctx:
        while True:
            now = time.monotonic()
            if now >= deadline and next_arrival >= deadline:
                break
            while next_arrival <= min(now, deadline):
                prompt = rng.integers(0, config.vocab_size,
                                      size=int(rng.integers(3, 12))).tolist()
                fleet.submit(prompt, max_new_tokens=int(rng.integers(2, 8)))
                submitted += 1
                next_arrival += float(rng.exponential(1.0 / rate_hz))
            busy = fleet.step()
            # The ownership audit runs EVERY step — a request owned by two
            # replicas or a leaked block must be caught at the step it
            # happens, not after the drain smoothed it over.
            fleet.check_invariants()
            if stats_jsonl and now >= next_stats:
                next_stats = now + 0.5
                with open(stats_jsonl, "a") as f:
                    f.write(json.dumps(fleet.stats_snapshot(),
                                       default=str) + "\n")
            if not busy:
                time.sleep(min(0.02,
                               max(0.0, next_arrival - time.monotonic())))
        fleet.run(max_steps=100000)      # drain in-flight + queued work
    fleet.check_invariants()

    fm = fleet.metrics.as_dict()
    quarantines = int(fm.get("replica_quarantines", 0.0))
    completed = len(fleet.finished)
    failed = len(fleet.failed)
    if completed + failed != submitted:
        raise RuntimeError(f"drain incomplete: {completed} ok + {failed} "
                           f"failed != {submitted} submitted")
    if chaos:
        if not quarantines:
            raise RuntimeError("fleet chaos run quarantined no replica — "
                               "the seeded kill never bit")
        if failed:
            raise RuntimeError(
                f"{failed} survivor requests failed under the fleet kill "
                f"(requeue must re-serve every drained request)")
    else:
        if failed or quarantines:
            raise RuntimeError(f"{failed} failed / {quarantines} "
                               f"quarantined without chaos")
        if any(rep.state not in ROUTABLE for rep in fleet.replicas):
            raise RuntimeError("replica left the routable states without "
                               "chaos")
    for rep in fleet.replicas:
        for kind, n in rep.engine.trace_counts.items():
            if n > 1:
                raise RuntimeError(
                    f"replica {rep.idx} {kind} step retraced {n} times — "
                    "fleet churn must be data, not shape")

    m = {
        "requests_submitted": submitted,
        "requests_completed": completed,
        "requests_failed": failed,
        "wall_s": round(time.monotonic() - start, 3),
        "fleet_steps": fleet.n_steps,
        "replica_states": [rep.state for rep in fleet.replicas],
        "replicas_dead": sum(rep.state == DEAD for rep in fleet.replicas),
        "quarantines": quarantines,
        "requeues": int(fm.get("requeues", 0.0)),
        "requeue_exhausted": int(fm.get("requeue_exhausted", 0.0)),
        "faults_injected": plan.n_fired if plan is not None else 0,
        "state_log": fleet.state_log,
    }
    if perfdb_path:
        from triton_distributed_tpu.obs.perfdb import PerfDB

        sample = fleet.perfdb_sample()
        sample["requests_submitted"] = float(submitted)
        rec = PerfDB(perfdb_path).append(
            suite="serve_smoke_fleet_chaos" if chaos
            else "serve_smoke_fleet",
            metrics=sample,
            meta={"duration_s": duration_s, "rate_hz": rate_hz,
                  "seed": seed, "n_replicas": n_replicas})
        m["perfdb_run_id"] = rec.run_id
    return m


def main_restore(duration_s: float = 6.0, *, rate_hz: float = 6.0,
                 n_replicas: int = 2, n_slots: int = 3,
                 n_blocks: int = 10, seed: int = 0,
                 perfdb_path: str | None = None) -> dict:
    """The ``--restore`` arm: checkpoint / crash / restore under Poisson
    load. Phase 1 submits open-loop arrivals through a journaled fleet,
    checkpoints mid-flight, takes a few more journal-only steps, and
    dies (``journal.crash()`` — the un-fsynced tail is lost exactly as a
    power cut would lose it). ``Fleet.restore`` then rebuilds onto fresh
    replicas (compiled steps shared from the dead fleet's engine — no
    retrace) and drains. FAILS unless ZERO submitted requests are lost
    (every one finishes, none failed), at least one request finishes
    AFTER the restore, and no replica ever retraces."""
    import os
    import shutil
    import tempfile

    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving import Fleet

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    kw = dict(n_replicas=n_replicas, n_slots=n_slots, n_blocks=n_blocks,
              block_size=4, prefill_chunk=8, fail_threshold=2,
              paged_attn=_host_arm_attn())
    fleet = Fleet.build(engine, **kw)
    workdir = tempfile.mkdtemp(prefix="tdt_smoke_restore_")
    try:
        jpath = os.path.join(workdir, "wal.jsonl")
        fleet.attach_journal(jpath)

        rng = np.random.default_rng(seed)
        start = time.monotonic()
        deadline = start + duration_s
        next_arrival = start
        submitted = 0
        while time.monotonic() < deadline or submitted == 0:
            now = time.monotonic()
            while next_arrival <= min(now, deadline) or submitted == 0:
                prompt = rng.integers(
                    0, config.vocab_size,
                    size=int(rng.integers(3, 12))).tolist()
                fleet.submit(prompt, max_new_tokens=int(rng.integers(4, 10)))
                submitted += 1
                next_arrival += float(rng.exponential(1.0 / rate_hz))
            fleet.step()
            fleet.check_invariants()
        # A final burst right before the checkpoint: guaranteed in-flight
        # work at the crash (an early Poisson lull could otherwise drain
        # the fleet completely, leaving nothing to recover).
        for _ in range(4):
            prompt = rng.integers(0, config.vocab_size,
                                  size=int(rng.integers(3, 12))).tolist()
            fleet.submit(prompt, max_new_tokens=8)
            submitted += 1
        ck = os.path.join(workdir, "ckpt")
        fleet.checkpoint(ck)
        for _ in range(3):               # journal-suffix territory
            fleet.step()
        fleet.journal.crash()            # power cut mid-flight
        donor = fleet.replicas[0].engine

        t0 = time.monotonic()
        restored = Fleet.restore(ck, engine, donor=donor, **kw)
        recovery_s = time.monotonic() - t0
        finished_at_restore = len(restored.finished)
        restored.run(max_steps=100000)
        restored.check_invariants()

        completed = len(restored.finished)
        failed = len(restored.failed)
        lost = submitted - completed - failed
        if lost or failed:
            raise RuntimeError(
                f"restore lost work: {submitted} submitted, {completed} "
                f"ok, {failed} failed, {lost} vanished — the journal "
                "contract is zero lost requests")
        post_restore = completed - finished_at_restore
        if post_restore < 1:
            raise RuntimeError(
                "no request finished after the restore — the recovered "
                "fleet never actually served")
        for rep in restored.replicas:
            for kind, n in rep.engine.trace_counts.items():
                if n > 1:
                    raise RuntimeError(
                        f"replica {rep.idx} {kind} step retraced {n} "
                        "times during recovery")

        m = {
            "requests_submitted": submitted,
            "requests_completed": completed,
            "requests_failed": failed,
            "requests_lost": lost,
            "finished_after_restore": post_restore,
            "restored_requests": int(restored.metrics.counters.get(
                "restored_requests", 0.0)),
            "recovery_s": round(recovery_s, 4),
            "wall_s": round(time.monotonic() - start, 3),
            "fleet_steps": restored.n_steps,
        }
        if perfdb_path:
            from triton_distributed_tpu.obs.perfdb import PerfDB

            sample = restored.perfdb_sample()
            sample["requests_submitted"] = float(submitted)
            sample["recovery_s"] = recovery_s
            rec = PerfDB(perfdb_path).append(
                suite="serve_smoke_restore", metrics=sample,
                meta={"duration_s": duration_s, "rate_hz": rate_hz,
                      "seed": seed, "n_replicas": n_replicas})
            m["perfdb_run_id"] = rec.run_id
        return m
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main_adaptive(*, seed: int = 0, warmup: int = 24, burst: int = 48,
                  tail: int = 8, perfdb_path: str | None = None,
                  stats_jsonl: str | None = None) -> dict:
    """The ``--adaptive`` arm: a closed-loop warmup, then an overload
    burst, then a light tail — with the SLO engine and the adaptive
    ``Controller`` both attached. Asserts the full control story on one
    run: the burst drives the TTFT objective to WARN, the controller
    actuates under pressure (level >= 1 moves in its action log), the
    drain walks the objective back to OK, BREACH never fires, and both
    compiled steps still traced exactly once. The TTFT threshold is
    self-calibrated from the warmup's own median (6x), so the arm passes
    on any machine speed — overload is structural (queue wait across
    many waves), not a wall-clock constant. Raises RuntimeError on any
    violation."""
    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.obs.slo import BREACH, WARN, Objective
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving import BatchEngine

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny", max_length=128)
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    be = BatchEngine(engine, n_slots=4, n_blocks=96, block_size=4,
                     prefill_chunk=8, paged_attn=_host_arm_attn())
    rng = np.random.default_rng(seed)
    start = time.monotonic()

    def one_request(gen: int = 8):
        prompt = rng.integers(0, config.vocab_size,
                              size=int(rng.integers(6, 12))).tolist()
        be.submit(prompt, max_new_tokens=gen)

    # Phase 1 — closed-loop warmup: establishes the healthy-TTFT baseline
    # (every sample lands in the slow window as a GOOD observation, which
    # is what structurally caps the slow burn rate below the breach line).
    for _ in range(warmup):
        one_request()
        be.run()
    base = be.metrics.window("ttft_s", 600.0).get("p50", 0.0)
    if not base:
        raise RuntimeError("warmup recorded no TTFT samples")
    threshold = max(6.0 * base, 0.02)

    # TTFT objective only, q50/burn 1.6: the fast window trips when >=80%
    # of its samples violate (mid-burst: all of them), while the slow
    # window holds the warmup's good samples too, so its fraction stays
    # below 0.8 by construction (burst/(burst+warmup) < 0.8) — WARN yes,
    # BREACH never, on any machine.
    slo_engine = be.attach_slo(
        [Objective.latency("ttft_q50", "ttft_s", threshold, quantile=0.5,
                           burn=1.6, fast_window_s=2.0,
                           slow_window_s=600.0, min_count=8)],
        eval_interval_s=0.1)
    ctl = be.attach_controller(interval_steps=1, relax_after=6)
    if stats_jsonl:
        be.stream_stats(stats_jsonl, interval_s=0.5)

    # Phase 2 — overload: one instantaneous burst, many waves deep. Late
    # waves queue behind ~burst/n_slots generations, so their TTFT is
    # hundreds of step times >> 6x the ~3-step warmup baseline. The
    # pre-burst quiesce ages the warmup's good samples out of the fast
    # window, and the paced drain keeps the overload IN the fast window
    # long enough that WARN fires while decode rows are still active —
    # which is when the controller's level>=1 tighten path can actually
    # bite (an idle plant has nothing to actuate on).
    time.sleep(2.2)
    for _ in range(burst):
        one_request(gen=48)
    while be.step():
        time.sleep(0.005)

    # Phase 3 — light tail, then idle past the fast window so the SLO
    # walks back to OK (idle steps still evaluate — _obs_tick runs even
    # when no slot is active).
    for _ in range(tail):
        one_request()
        be.run()
    settle_until = time.monotonic() + 2.6
    while time.monotonic() < settle_until:
        be.step()
        time.sleep(0.02)

    m = be.metrics.as_dict()
    submitted = warmup + burst + tail
    completed = int(m.get("requests_completed", 0))
    failed = int(m.get("requests_failed", 0))
    be.pool.check_invariants()
    if completed != submitted or failed:
        raise RuntimeError(f"adaptive run: {completed} ok + {failed} "
                           f"failed != {submitted} submitted")
    for kind, n in be.trace_counts.items():
        if n > 1:
            raise RuntimeError(
                f"{kind} step retraced {n} times under the control sweep "
                "— knob moves must be data, not shape")
    warned = [t for t in slo_engine.transitions if t["new"] == WARN]
    if not warned:
        raise RuntimeError("overload burst never drove the SLO to WARN")
    if slo_engine.n_breaches or any(t["new"] == BREACH
                                    for t in slo_engine.transitions):
        raise RuntimeError("adaptive run BREACHed — degradation was not "
                           "graceful")
    if slo_engine.worst_level() != 0:
        raise RuntimeError(f"SLO did not recover to OK: "
                           f"{slo_engine.verdicts()}")
    if not ctl.action_log:
        raise RuntimeError("controller took no actions under overload")
    pressured = [a for a in ctl.action_log if a.get("level", 0) >= 1]
    if not pressured:
        raise RuntimeError("controller never actuated at WARN — the SLO "
                           "signal did not reach the knobs")
    # Journey attribution must SEE the overload: the burst queues many
    # waves deep, so the mean queue-wait fraction across finished
    # journeys is structurally nonzero (machine-speed independent).
    journey_fracs = be.journey.mean_fracs()
    if not journey_fracs["queue"] > 0.0:
        raise RuntimeError("overload burst left zero journey queue-wait "
                           "attribution — the journey phase machine "
                           "missed the queue phase")

    result = {
        "requests_submitted": submitted,
        "requests_completed": completed,
        "wall_s": round(time.monotonic() - start, 3),
        "ttft_threshold_s": round(threshold, 5),
        "warn_transitions": len(warned),
        "slo_breaches": 0,
        "slo_verdicts": slo_engine.verdicts(),
        "controller": ctl.stats(),
        "pressured_actions": len(pressured),
        "trace_count_decode": be.trace_counts["decode"],
        "trace_count_prefill": be.trace_counts["prefill"],
        "journey_mean_fracs": journey_fracs,
        "journey_slowest": be.journey.slowest(4),
    }
    if perfdb_path:
        from triton_distributed_tpu.obs.perfdb import PerfDB

        sample = be.perfdb_sample()
        sample["warn_transitions"] = float(len(warned))
        sample["breach_steps"] = 0.0
        rec = PerfDB(perfdb_path).append(
            suite="serve_smoke_adaptive", metrics=sample,
            meta={"seed": seed, "warmup": warmup, "burst": burst})
        result["perfdb_run_id"] = rec.run_id
    return result


def main_spec(*, seed: int = 0, n_requests: int = 16, gen: int = 32,
              perfdb_path: str | None = None,
              stats_jsonl: str | None = None) -> dict:
    """The ``--spec`` arm: speculative decoding end to end, asserted
    LOSSLESS. The same deterministic workload (half repetitive prompts —
    n-gram fuel — half random) runs through a speculative engine and a
    plain engine sharing the model params; the run fails unless

      * every request's output is byte-identical across the two engines
        (the acceptance rule + KV rollback changed WHEN tokens were
        verified, never WHICH tokens were emitted);
      * the drafter actually landed accepted tokens (> 0) — the greedy
        cycles the tiny model falls into are the structural guarantee,
        so a zero here means the verify plumbing is broken, not the
        workload unlucky;
      * neither engine retraced either compiled step (draft width churn
        is ``seq_lens`` data, not shape).
    """
    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving import BatchEngine

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny", max_length=128)
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    start = time.monotonic()

    rng = np.random.default_rng(seed)
    prompts = []
    for i in range(n_requests):
        if i % 2:
            prompts.append([5, 6, 7, 5, 6, 7, 5, 6])
        else:
            prompts.append(rng.integers(
                0, config.vocab_size,
                size=int(rng.integers(4, 10))).tolist())

    def run(speculative):
        be = BatchEngine(engine, n_slots=4, n_blocks=96, block_size=4,
                         prefill_chunk=8, speculative=speculative,
                         paged_attn=_host_arm_attn())
        if speculative and stats_jsonl:
            be.stream_stats(stats_jsonl, interval_s=0.5)
        for i, p in enumerate(prompts):
            be.submit(p, max_new_tokens=gen, req_id=i)
        out = be.run(max_steps=20000)
        be.pool.check_invariants()
        for kind, n in be.trace_counts.items():
            if n > 1:
                raise RuntimeError(
                    f"{'spec' if speculative else 'plain'} {kind} step "
                    f"retraced {n} times — draft width must be data, "
                    "not shape")
        return be, out

    be_spec, out_spec = run(True)
    _, out_plain = run(False)

    diverged = [i for i in range(n_requests)
                if out_spec.get(i) != out_plain.get(i)]
    if diverged:
        raise RuntimeError(f"speculative outputs diverged from plain "
                           f"decode for requests {diverged} — speculation "
                           "must be lossless under greedy")
    m = be_spec.metrics.as_dict()
    accepted = int(m.get("spec_accepted_tokens", 0))
    proposed = int(m.get("spec_proposed_tokens", 0))
    if not proposed:
        raise RuntimeError("drafter proposed nothing — the n-gram fuel "
                           "prompts never produced a draft")
    if not accepted:
        raise RuntimeError("zero drafts accepted — verify/acceptance "
                           "plumbing is broken (the repetitive workload "
                           "structurally produces accepts)")

    result = {
        "requests_submitted": n_requests,
        "requests_completed": int(m.get("requests_completed", 0)),
        "tokens_generated": int(m.get("tokens_generated", 0)),
        "wall_s": round(time.monotonic() - start, 3),
        "spec_proposed_tokens": proposed,
        "spec_accepted_tokens": accepted,
        "spec_verify_rows": int(m.get("spec_verify_rows", 0)),
        "spec_rollback_tokens": int(m.get("spec_rollback_tokens", 0)),
        "divergent_requests": 0,
        "spec": be_spec.stats_snapshot()["spec"],
        "trace_count_decode": be_spec.trace_counts["decode"],
        "trace_count_prefill": be_spec.trace_counts["prefill"],
    }
    if perfdb_path:
        from triton_distributed_tpu.obs.perfdb import PerfDB

        sample = be_spec.perfdb_sample()
        if result["wall_s"]:
            sample["serve_tokens_per_s"] = round(
                result["tokens_generated"] / result["wall_s"], 2)
        rec = PerfDB(perfdb_path).append(
            suite="serve_smoke_spec", metrics=sample,
            meta={"seed": seed, "n_requests": n_requests, "gen": gen})
        result["perfdb_run_id"] = rec.run_id
    return result


def main_incidents(*, seed: int = 0, warmup: int = 32,
                   chaos_requests: int = 24,
                   perfdb_path: str | None = None,
                   stats_jsonl: str | None = None) -> dict:
    """The ``--incidents`` arm: precision AND recall of the always-on
    incident engine on one run. Phase 1 is a clean closed-loop workload —
    the engine must open ZERO incidents (the flap-freedom gate). Phase 2
    installs a seeded NaN fault plan at ``engine.decode``; the resulting
    quarantines drive the ``requests_failed`` counter detector, and the
    run fails unless >= 1 incident opens, its TOP-ranked suspect names
    the injected site (cross-layer triage found the right culprit, not
    just *a* culprit), and detection latency stays within the hysteresis
    bound. Both compiled steps must still trace exactly once. Raises
    RuntimeError on any violation."""
    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.resilience import (
        FaultPlan,
        FaultSpec,
        faults,
    )
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving import BatchEngine

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny", max_length=128)
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    be = BatchEngine(engine, n_slots=4, n_blocks=96, block_size=4,
                     prefill_chunk=8, paged_attn=_host_arm_attn())
    if be.incidents is None:
        raise RuntimeError("incident engine not attached — it must be "
                           "always-on by default")
    if stats_jsonl:
        be.stream_stats(stats_jsonl, interval_s=0.5)
    rng = np.random.default_rng(seed)
    start = time.monotonic()

    def one_request(gen: int = 8):
        prompt = rng.integers(0, config.vocab_size,
                              size=int(rng.integers(6, 12))).tolist()
        be.submit(prompt, max_new_tokens=gen)

    # Phase 1 — clean closed-loop load: the precision gate. Every level
    # detector builds its healthy baseline here; nothing may trip.
    for _ in range(warmup):
        one_request()
        be.run()
    clean = be.incidents.stats()
    if clean["total"] or clean["open"]:
        raise RuntimeError(
            f"clean workload opened {clean['total']} incident(s) — the "
            "detectors flapped on a healthy trace")

    # Phase 2 — seeded chaos: NaN-poisoned logit rows at engine.decode.
    # Each bite quarantines the slot-0 request, bumping requests_failed —
    # a counter detector structurally at zero on a healthy run, so the
    # trip is deterministic-given-the-plan, not a latency threshold.
    plan = FaultPlan([
        FaultSpec(site="engine.decode", kind="nan", p=0.6, row=0,
                  start_after=2),
    ], seed=seed)
    with faults.plan(plan):
        for _ in range(chaos_requests):
            one_request()
            be.run()
    if not plan.n_fired:
        raise RuntimeError("seeded NaN plan never fired — no chaos to "
                           "detect")

    m = be.metrics.as_dict()
    failed = int(m.get("requests_failed", 0))
    if not failed:
        raise RuntimeError("chaos phase quarantined nothing — the NaN "
                           "plan fired but no request failed")
    be.pool.check_invariants()
    for kind, n in be.trace_counts.items():
        if n > 1:
            raise RuntimeError(
                f"{kind} step retraced {n} times with the incident "
                "engine attached — detection must be data, not shape")

    dump = be.incidents.dump()
    rows = dump["incidents"]
    if not rows:
        raise RuntimeError(
            f"{failed} quarantines produced NO incident — the counter "
            "detector missed a structural failure burst")
    top = rows[0]
    suspects = top.get("suspects", [])
    if not suspects:
        raise RuntimeError("incident opened with an EMPTY suspect list — "
                           "triage saw none of the evidence")
    if suspects[0]["site"] != "engine.decode":
        raise RuntimeError(
            f"triage mis-attributed the incident: top suspect "
            f"{suspects[0]['site']!r} (score {suspects[0]['score']}), "
            "expected 'engine.decode' — the injected fault site must "
            "outrank downstream symptoms")
    lat = int(top["detect_latency_steps"])
    if lat > 4:
        raise RuntimeError(f"detection latency {lat} steps — counter "
                           "trips must be near-immediate")

    result = {
        "requests_submitted": warmup + chaos_requests,
        "requests_completed": int(m.get("requests_completed", 0)),
        "requests_failed": failed,
        "wall_s": round(time.monotonic() - start, 3),
        "faults_injected": plan.n_fired,
        "incidents_opened": dump["opened"],
        "incidents_open": be.incidents.n_open,
        "detect_latency_steps": lat,
        "top_suspect": suspects[0],
        "incident_severity": top["severity"],
        "trace_count_decode": be.trace_counts["decode"],
        "trace_count_prefill": be.trace_counts["prefill"],
    }
    if perfdb_path:
        from triton_distributed_tpu.obs.perfdb import PerfDB

        sample = be.perfdb_sample()
        rec = PerfDB(perfdb_path).append(
            suite="serve_smoke_incidents", metrics=sample,
            meta={"seed": seed, "warmup": warmup,
                  "chaos_requests": chaos_requests})
        result["perfdb_run_id"] = rec.run_id
    return result


def main_whatif(*, seed: int = 0, n_requests: int = 10,
                perfdb_path: str | None = None) -> dict:
    """The ``--whatif`` arm: record -> replay -> counterfactual.

    A 2-replica tiny-model fleet with its prefill budget throttled
    serves a short discretized-Poisson workload (geometric inter-arrival
    gaps in fleet STEPS, so the arrival process is Poisson-like yet
    fully deterministic for a seed) while the always-on ``ServeTrace``
    records it. The gate: the baseline replay through ``ReplayHarness``
    is bit-identical to the live run (same outputs, zero lost requests,
    zero retraces), and one counterfactual — the un-throttled prefill
    budget — produces a ranked ``WhatIfReport``. Raises RuntimeError on
    any violation."""
    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.obs.replay import (
        ReplayHarness,
        WhatIfConfig,
    )
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving.fleet import Fleet

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1],
                     set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    fleet = Fleet.build(engine, n_replicas=2, n_slots=4, n_blocks=24,
                        block_size=4, prefill_chunk=8, seed=seed,
                        paged_attn=_host_arm_attn())
    if fleet.serve_trace is None:
        raise RuntimeError("ServeTrace not attached — recording must be "
                           "always-on by default")
    for rep in fleet.replicas:
        rep.engine.prefill_budget = 2   # the counterfactual lifts this
    rng = np.random.default_rng(seed)
    # Discretized Poisson: geometric step gaps at ~1 arrival / 2 steps.
    arrive_at, step_at = [], 0
    for _ in range(n_requests):
        arrive_at.append(step_at)
        step_at += int(rng.geometric(0.5))
    start = time.monotonic()
    k = 0
    while k < n_requests or not all(
            rep.empty or rep.state == "DEAD" for rep in fleet.replicas):
        while k < n_requests and arrive_at[k] <= fleet.n_steps:
            n = int(rng.integers(4, 14))
            prompt = rng.integers(1, config.vocab_size, size=n).tolist()
            fleet.submit(prompt, 6, tenant=("acme", "globex")[k % 2])
            k += 1
        fleet.step()
        if fleet.n_steps > 2000:
            raise RuntimeError("whatif arm run did not settle")
    if not fleet.check_invariants():
        raise RuntimeError("fleet invariants violated")
    trace = fleet.serve_trace.finalize(fleet)
    if len(trace.arrivals) != n_requests:
        raise RuntimeError(
            f"trace recorded {len(trace.arrivals)} arrivals, expected "
            f"{n_requests}")

    harness = ReplayHarness(trace, donor=fleet.replicas[0].engine)
    base = harness.baseline()
    if not base.matches_trace or base.lost or base.retraces:
        raise RuntimeError(
            f"baseline replay diverged from the recording "
            f"(bit-identical={base.matches_trace}, lost={base.lost}, "
            f"retraces={base.retraces})")
    report = harness.sweep([
        WhatIfConfig(name="full-prefill", prefill_budget=8),
    ])
    win = report.winner()
    if win is None:
        raise RuntimeError("counterfactual sweep produced no ranked row")
    if win["lost"]:
        raise RuntimeError(f"counterfactual lost {win['lost']} requests")
    md = report.to_markdown()
    if "full-prefill" not in md:
        raise RuntimeError("what-if report is missing the counterfactual")

    result = {
        "requests_submitted": n_requests,
        "requests_completed": len(fleet.finished),
        "requests_failed": len(fleet.failed),
        "wall_s": round(time.monotonic() - start, 3),
        "whatif_baseline_bit_identical": True,
        "whatif_lost_requests": int(base.lost),
        "whatif_retraces": int(base.retraces),
        "whatif_baseline_goodput": round(report.baseline["goodput"], 6),
        "whatif_winner_goodput": round(win["goodput"], 6),
        "whatif_goodput_delta": round(win["d_goodput"], 6),
        "whatif_calib_samples": int(trace._n_samples),
        "cost_model_source": harness.cost.source,
        "trace_count_decode": max(rep.engine.trace_counts["decode"]
                                  for rep in fleet.replicas),
        "trace_count_prefill": max(rep.engine.trace_counts["prefill"]
                                   for rep in fleet.replicas),
    }
    if perfdb_path:
        from triton_distributed_tpu.obs.perfdb import PerfDB

        sample = fleet.perfdb_sample()
        sample["whatif_baseline_goodput"] = float(
            report.baseline["goodput"])
        sample["whatif_winner_goodput"] = float(win["goodput"])
        sample["whatif_goodput_delta"] = float(win["d_goodput"])
        sample["whatif_lost_requests"] = float(base.lost)
        sample["whatif_retraces"] = float(base.retraces)
        sample["whatif_calib_samples"] = float(trace._n_samples)
        rec = PerfDB(perfdb_path).append(
            suite="serve_smoke_whatif", metrics=sample,
            meta={"seed": seed, "n_requests": n_requests})
        result["perfdb_run_id"] = rec.run_id
    return result


def main_kvq(*, seed: int = 0, kv_dtype: str = "int8", gen: int = 64,
             perfdb_path: str | None = None) -> dict:
    """The ``--kvq`` arm: the quantized KV cache's serving contract.

    One quantized BatchEngine (``kv_dtype`` int8 by default) on a pool
    tight enough that two long generations preempt each other, serving
    a shared-prefix workload twice:

      * COLD pass: fresh cache — prefills write quantized blocks, the
        finished sequences donate them to the radix prefix cache.
      * WARM pass: the SAME requests again — admission must CoW-adopt
        the quantized cached blocks (nonzero ``prefix_hits``), and every
        output must be BYTE-IDENTICAL to its cold twin over ``gen``
        decode steps (64 by default; the tier-1 test runs 8). Per-row
        scales travel with their blocks, so warm == cold holds exactly in
        the quantized domain; any scale/block mispairing shows up as token
        divergence here.

    Also asserted: preemption churn actually happened (the contract is
    bit-exactness UNDER churn, not in steady state), zero retraces on
    both compiled steps (``trace_counts`` {1,1} — the quantized arenas
    ride the same fixed shapes), and pool invariants (free ∪ private ∪
    cached partition, scale arenas included) after each pass. Raises
    RuntimeError on any violation."""
    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving import BatchEngine

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny", max_length=256)
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    start = time.monotonic()

    rng = np.random.default_rng(seed)
    n_req = 4
    prefix = rng.integers(0, config.vocab_size, size=24).tolist()
    prompts = [prefix + rng.integers(0, config.vocab_size,
                                     size=4).tolist()
               for _ in range(n_req)]
    # Peak residency per request is ceil((28 + gen + 1) / 8) blocks (12 at
    # the default gen of 64); a pool of a third more (16) cannot hold two of
    # those, so the decode phase preempts and re-admits — the churn the
    # bit-exactness claim is about — while the other two requests wait in
    # the queue for a slot. The pool follows ``gen``, so a shorter run (the
    # tier-1 test's) still preempts.
    # Two slots and blocks of 8: where the fused kernel is interpreted
    # (any CPU run) a step costs ~0.09 s a slot, and two waves of gen
    # steps, twice, are some 330 steps with the recompute at gen 64.
    peak = -(-(len(prompts[0]) + gen + 1) // 8)
    be = BatchEngine(engine, n_slots=2, n_blocks=peak + peak // 3,
                     block_size=8, prefill_chunk=8, kv_dtype=kv_dtype)

    def one_pass(tag):
        rids = [be.submit(p, max_new_tokens=gen, req_id=f"{tag}-{i}")
                for i, p in enumerate(prompts)]
        done = be.run(max_steps=40000)
        be.pool.check_invariants()
        missing = [r for r in rids if r not in done]
        if missing:
            raise RuntimeError(f"kvq {tag} pass lost requests: {missing}")
        return [done[r] for r in rids]

    cold = one_pass("cold")
    hits_cold = be.metrics.snapshot()["counters"].get("prefix_hits", 0)
    warm = one_pass("warm")
    m = be.metrics.snapshot()["counters"]
    hits_warm = int(m.get("prefix_hits", 0)) - int(hits_cold)

    if warm != cold:
        bad = [i for i, (a, b) in enumerate(zip(cold, warm)) if a != b]
        raise RuntimeError(
            f"quantized warm outputs diverged from cold for requests "
            f"{bad} — CoW adoption of quantized blocks must be bit-exact "
            "in the quantized domain")
    if hits_warm <= 0:
        raise RuntimeError("warm pass adopted no quantized cached blocks "
                           "— the radix cache never hit")
    preemptions = int(m.get("preemptions", 0))
    if not preemptions:
        raise RuntimeError("no preemption churn — the pool was sized too "
                           "generously for the bit-exactness-under-churn "
                           "claim")
    for kind, n in be.trace_counts.items():
        if n > 1:
            raise RuntimeError(
                f"{kind} step retraced {n} times — the quantized KV mode "
                "must keep slot churn data, not shape")

    result = {
        "kv_dtype": kv_dtype,
        "kv_fingerprint": be.pool.kv_fingerprint(),
        "requests_submitted": 2 * n_req,
        "requests_completed": int(m.get("requests_completed", 0)),
        "gen": gen,
        "wall_s": round(time.monotonic() - start, 3),
        "warm_bit_identical": True,
        "prefix_hits_warm": hits_warm,
        "preemptions": preemptions,
        "trace_count_decode": be.trace_counts["decode"],
        "trace_count_prefill": be.trace_counts["prefill"],
    }
    if perfdb_path:
        from triton_distributed_tpu.obs.perfdb import PerfDB

        sample = be.perfdb_sample()
        sample["kvq_prefix_hits"] = float(hits_warm)
        sample["kvq_preemptions"] = float(preemptions)
        rec = PerfDB(perfdb_path).append(
            suite="serve_smoke_kvq", metrics=sample,
            meta={"seed": seed, "kv_dtype": kv_dtype, "gen": gen})
        result["perfdb_run_id"] = rec.run_id
    return result


def main(duration_s: float = 30.0, *, rate_hz: float = 4.0, n_slots: int = 4,
         n_blocks: int | None = 12, seed: int = 0, chaos: bool = False,
         perfdb_path: str | None = None, slo: bool = False,
         efficiency: bool = False, stats_jsonl: str | None = None) -> dict:
    """Run the load, return the metrics dict. Raises RuntimeError on any
    retrace beyond the first compile of each step kind; with ``chaos``,
    also on any violation of the graceful-degradation contract.
    ``perfdb_path`` appends the run's TTFT/TBT/throughput sample to the
    perf flight recorder's run database (obs/perfdb.py) so
    ``tools/perf_gate.py`` can gate serving latency across PRs.
    ``slo`` attaches the stock serving SLO set (generous thresholds) and
    reports its verdicts in the result; ``efficiency`` asserts the
    always-on efficiency ledger's accounting after the drain (every step's
    fractions telescoped to 1, MFU nonzero, bubble_frac < 1) and includes
    its stats in the result; ``stats_jsonl`` streams live
    ``stats_snapshot()`` lines to that path (``tools/serve_top.py`` tails
    it)."""
    import contextlib

    import jax

    from triton_distributed_tpu.models import Engine, ModelConfig
    from triton_distributed_tpu.obs import comm_ledger
    from triton_distributed_tpu.resilience import (
        FaultPlan,
        FaultSpec,
        RetryPolicy,
        Watchdog,
        faults,
    )
    from triton_distributed_tpu.runtime.mesh import make_mesh
    from triton_distributed_tpu.serving import BatchEngine

    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    # n_blocks below full residency so sustained load also exercises
    # admission control and preemption-by-recompute, not just steady state.
    # The chaos run gets a deep retry budget: at p=0.1 per step, 6 retries
    # put per-step exhaustion at 1e-7 — the smoke asserts degradation,
    # not luck.
    be = BatchEngine(engine, n_slots=n_slots, n_blocks=n_blocks,
                     block_size=4, prefill_chunk=8,
                     paged_attn=(_host_arm_attn()
                                 if slo or chaos or efficiency else "fused"),
                     retry=RetryPolicy(retries=6, base_delay_s=0.001)
                     if chaos else None)
    slo_engine = None
    if slo:
        # Generous thresholds: the smoke asserts the machinery evaluates
        # and stays healthy, not that CI hardware hits production latency.
        from triton_distributed_tpu.obs.slo import default_serving_slo

        slo_engine = be.attach_slo(
            default_serving_slo(ttft_p99_s=30.0, tbt_p99_s=5.0,
                                error_rate=0.9 if chaos else 0.5),
            eval_interval_s=0.25)
    if stats_jsonl:
        be.stream_stats(stats_jsonl, interval_s=0.5)

    plan_ctx = contextlib.nullcontext()
    if chaos:
        # Hotter than default_chaos_plan so a SHORT smoke reliably sees
        # both outcomes: a near-certain NaN quarantine early on plus
        # frequent (but always retryable) transient errors.
        plan_ctx = faults.plan(FaultPlan([
            FaultSpec(site="engine.decode", kind="error", p=0.1,
                      start_after=1),
            FaultSpec(site="pool.ensure", kind="error", p=0.05,
                      start_after=2),
            # No max_fires: a firing that lands on an EMPTY slot 0
            # quarantines nobody, so keep rolling until it bites. Only
            # slot 0 is ever poisoned — slots 1.. always have survivors.
            FaultSpec(site="engine.decode", kind="nan", p=0.35, row=0,
                      start_after=2),
        ], seed=seed))
        be.attach_watchdog(Watchdog(), step_deadline_s=60.0)

    rng = np.random.default_rng(seed)
    start = time.monotonic()
    deadline = start + duration_s
    next_arrival = start
    submitted = 0
    with comm_ledger.ledger(reset_first=True), plan_ctx:
        while True:
            now = time.monotonic()
            if now >= deadline and next_arrival >= deadline:
                break
            while next_arrival <= min(now, deadline):
                prompt = rng.integers(0, config.vocab_size,
                                      size=int(rng.integers(3, 12))).tolist()
                be.submit(prompt, max_new_tokens=int(rng.integers(2, 8)))
                submitted += 1
                next_arrival += float(rng.exponential(1.0 / rate_hz))
            if not be.step():       # idle: sleep until the next arrival
                time.sleep(min(0.02,
                               max(0.0, next_arrival - time.monotonic())))
        be.run()                    # drain in-flight + queued work

    m = be.metrics.as_dict()
    m["requests_submitted"] = submitted
    m["wall_s"] = round(time.monotonic() - start, 3)
    m["trace_count_decode"] = be.trace_counts["decode"]
    m["trace_count_prefill"] = be.trace_counts["prefill"]
    # Observability wiring: the comm-ledger byte-accounting cross-check
    # (recorded bytes must equal the perf model's analytical wire bytes for
    # AG and RS) plus whatever the serve run itself put in the ledger.
    m["comm_ledger"] = comm_ledger.snapshot()
    m["ledger_selfcheck"] = comm_ledger.selfcheck()
    if slo_engine is not None:
        m["slo_verdicts"] = slo_engine.verdicts()
        m["slo_breaches"] = slo_engine.n_breaches
        if not slo_engine.n_evaluations:
            raise RuntimeError("SLO attached but never evaluated")
    be.pool.check_invariants()
    # After drain every block is either free or parked in the prefix cache
    # with zero references (reclaimable). Anything else is a leak.
    if be.pool.n_free + be.pool.n_reclaimable != be.pool.n_blocks:
        raise RuntimeError("KV pool leaked blocks after drain")
    completed = int(m["requests_completed"])
    failed = int(m.get("requests_failed", 0))
    m["requests_failed"] = failed
    if completed + failed != submitted:
        raise RuntimeError(
            f"drain incomplete: {completed} ok + {failed} failed "
            f"!= {submitted} submitted")
    if chaos:
        # Graceful degradation, both halves: the faults actually hurt
        # someone (>=1 quarantined with an error attached) AND the batch
        # survived it (>=1 completed normally).
        if not failed:
            raise RuntimeError("chaos run quarantined nothing — fault "
                               "plan never bit")
        if not completed:
            raise RuntimeError("chaos run completed nothing — degradation "
                               "was not graceful")
        if any(r.error is None for r in be.failed.values()):
            raise RuntimeError("quarantined request missing error status")
    elif failed:
        raise RuntimeError(f"{failed} requests failed without chaos")
    for kind, n in be.trace_counts.items():
        if n > 1:
            raise RuntimeError(
                f"{kind} step retraced {n} times — slot churn must be "
                "data, not shape")
    if efficiency:
        # The efficiency ledger is always on; this arm asserts its
        # accounting contract held for a full synthetic-load run: every
        # step's attribution telescoped to 1.0, the modeled compute
        # fraction is nonzero (the ledger saw real work), and the host
        # bubble never swallowed the whole wall clock.
        eff = be.efficiency
        if eff is None or not eff.steps:
            raise RuntimeError("efficiency ledger recorded no steps")
        if not eff.frac_sum_ok:
            raise RuntimeError("efficiency ledger frac-sum violation — "
                               "per-step attribution did not telescope "
                               "to 1.0")
        if eff.lifetime_mfu() <= 0.0:
            raise RuntimeError("efficiency ledger reports zero MFU after "
                               "a loaded run")
        bubble = eff.lifetime_bubble_frac()
        if not bubble < 1.0:
            raise RuntimeError(f"bubble_frac {bubble} >= 1 — every "
                               "accounted second was a host gap")
        m["efficiency"] = eff.stats()
    if perfdb_path:
        from triton_distributed_tpu.obs.perfdb import PerfDB

        sample = be.perfdb_sample()
        if m["wall_s"]:
            sample["serve_tokens_per_s"] = round(
                float(m["tokens_generated"]) / float(m["wall_s"]), 2)
        rec = PerfDB(perfdb_path).append(
            suite="serve_smoke_chaos" if chaos else "serve_smoke",
            metrics=sample,
            meta={"duration_s": duration_s, "rate_hz": rate_hz,
                  "seed": seed})
        m["perfdb_run_id"] = rec.run_id
    return m


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean arrivals per second (Poisson)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos", action="store_true",
                    help="install the fault plan; assert graceful "
                         "degradation (>=1 quarantined, >=1 completed)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="run a FLEET of this many replicas behind the "
                         "router (>=2); with --chaos, a seeded kill "
                         "wedges one replica and the run asserts "
                         "quarantine + 100%% survivor completion")
    ap.add_argument("--perfdb", default=None,
                    help="append this run's TTFT/TBT/throughput sample to "
                         "the PerfDB JSONL at this path (tools/perf_gate.py "
                         "gates on it)")
    ap.add_argument("--slo", action="store_true",
                    help="attach the stock serving SLO set and report its "
                         "verdicts")
    ap.add_argument("--efficiency", action="store_true",
                    help="assert the always-on efficiency ledger's "
                         "accounting (frac sums 1.0, nonzero MFU, "
                         "bubble_frac < 1) and report its stats")
    ap.add_argument("--adaptive", action="store_true",
                    help="run the adaptive-control arm: overload burst "
                         "drives WARN, the controller actuates, recovery "
                         "walks back to OK with zero BREACH")
    ap.add_argument("--incidents", action="store_true",
                    help="run the incident-engine arm: clean phase must "
                         "open zero incidents; seeded NaN chaos must open "
                         ">=1 with the injected site top-ranked")
    ap.add_argument("--spec", action="store_true",
                    help="run the speculative-decoding arm: same workload "
                         "through spec and plain engines; assert zero "
                         "output divergence, nonzero accepted drafts, "
                         "zero retraces")
    ap.add_argument("--kvq", action="store_true",
                    help="run the quantized-KV-cache arm: int8 wire dtype, "
                         "warm CoW-adopted outputs bit-identical to cold "
                         "over 64 decode steps under preemption churn, "
                         "nonzero prefix hits, zero retraces")
    ap.add_argument("--kv-dtype", default="int8",
                    help="wire dtype for --kvq (int8 or fp8)")
    ap.add_argument("--whatif", action="store_true",
                    help="run the deterministic-replay arm: record a "
                         "short run, replay the baseline bit-identical, "
                         "produce one counterfactual what-if report")
    ap.add_argument("--restore", action="store_true",
                    help="run the crash-recovery arm: journaled Poisson "
                         "load, checkpoint, simulated power cut, "
                         "Fleet.restore; assert zero lost requests and "
                         ">=1 finish after the restore")
    ap.add_argument("--stats-jsonl", default=None,
                    help="stream live stats_snapshot() JSON lines here "
                         "(tools/serve_top.py tails this file)")
    args = ap.parse_args()
    try:
        if args.kvq:
            if (args.chaos or args.adaptive or args.spec
                    or args.incidents or args.restore or args.whatif
                    or args.replicas > 1):
                raise SystemExit("--kvq is its own arm; run it without "
                                 "--chaos/--adaptive/--spec/--incidents/"
                                 "--restore/--whatif/--replicas")
            metrics = main_kvq(seed=args.seed, kv_dtype=args.kv_dtype,
                               perfdb_path=args.perfdb)
        elif args.whatif:
            if (args.chaos or args.adaptive or args.spec
                    or args.incidents or args.restore
                    or args.replicas > 1):
                raise SystemExit("--whatif is its own arm; run it "
                                 "without --chaos/--adaptive/--spec/"
                                 "--incidents/--restore/--replicas")
            metrics = main_whatif(seed=args.seed,
                                  perfdb_path=args.perfdb)
        elif args.restore:
            if args.chaos or args.adaptive or args.spec or args.incidents:
                raise SystemExit("--restore is its own arm; run it "
                                 "without --chaos/--adaptive/--spec/"
                                 "--incidents")
            metrics = main_restore(
                args.duration, rate_hz=args.rate, seed=args.seed,
                n_replicas=max(2, args.replicas),
                perfdb_path=args.perfdb)
        elif args.incidents:
            if args.chaos or args.replicas > 1 or args.adaptive or args.spec:
                raise SystemExit("--incidents is its own arm; run it "
                                 "without --chaos/--replicas/--adaptive/"
                                 "--spec")
            metrics = main_incidents(seed=args.seed,
                                     perfdb_path=args.perfdb,
                                     stats_jsonl=args.stats_jsonl)
        elif args.spec:
            if args.chaos or args.replicas > 1 or args.adaptive:
                raise SystemExit("--spec is its own arm; run it without "
                                 "--chaos/--replicas/--adaptive")
            metrics = main_spec(seed=args.seed, perfdb_path=args.perfdb,
                                stats_jsonl=args.stats_jsonl)
        elif args.adaptive:
            if args.chaos or args.replicas > 1:
                raise SystemExit("--adaptive is its own arm; run it "
                                 "without --chaos/--replicas")
            metrics = main_adaptive(seed=args.seed,
                                    perfdb_path=args.perfdb,
                                    stats_jsonl=args.stats_jsonl)
        elif args.replicas > 1:
            if args.slo:
                # SLO objectives attach per-replica (the fleet health
                # machine reads them when present) — not a fleet flag yet.
                raise SystemExit("--slo is a single-engine flag; fleet "
                                 "replicas attach their own SLO engines")
            metrics = main_fleet(args.duration, rate_hz=args.rate,
                                 n_replicas=args.replicas, seed=args.seed,
                                 chaos=args.chaos,
                                 perfdb_path=args.perfdb,
                                 stats_jsonl=args.stats_jsonl)
        else:
            metrics = main(args.duration, rate_hz=args.rate,
                           seed=args.seed, chaos=args.chaos,
                           perfdb_path=args.perfdb, slo=args.slo,
                           efficiency=args.efficiency,
                           stats_jsonl=args.stats_jsonl)
    except RuntimeError as e:
        print(f"FAIL: {e}")
        raise SystemExit(1)
    print(json.dumps(metrics, default=float))
