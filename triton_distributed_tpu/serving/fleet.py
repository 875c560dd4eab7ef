"""Fault-tolerant serving fleet: N ``BatchEngine`` replicas behind a
cache- and SLO-aware ``Router``.

One ``BatchEngine`` is an error boundary for REQUESTS (a poisoned slot is
quarantined, the batch survives), but it is still a single point of
failure for TRAFFIC: a wedged step, a stale heartbeat, or a sustained SLO
breach takes down 100% of serving. The fleet generalizes the same
quarantine idea one level up — from slots to replicas:

  placement   ``Fleet.submit`` queues requests fleet-side; each step the
              ``Router`` (serving/router.py) places them on the replica
              with the best live signal bundle: longest cached-prefix
              ``match_len`` probe, per-replica SLO state (WARN/BREACH
              shed load), queue depth + free-block headroom.
  health      a per-replica state machine
                  HEALTHY -> DEGRADED -> QUARANTINED -> DRAINING -> DEAD
                       ^         |
                       +-- RECOVERED (after ``recovery_steps`` clean steps)
              driven by three independent detectors: consecutive step
              failures (``fail_threshold``), sustained SLO breach
              (``breach_quarantine_evals`` consecutive fleet steps at
              BREACH), and watchdog heartbeat staleness
              (``Heartbeat.stale()`` — the poll-only probe, no breach
              registration).
  drain       a quarantined replica is drained: every in-flight request
              leaves via the existing eviction-by-recompute path
              (``BatchEngine.drain`` — blocks released, generated output
              kept on the ``Request``) and requeues fleet-side for the
              router to place on a survivor. Requeue is budgeted by a
              ``RetryPolicy`` (``retries`` moves per request); an
              exhausted request lands in ``failed`` with the full reason
              CHAIN (every displacement that led there), never loops.
  backpressure fleet-wide admission gating: when the ROUTABLE replicas'
              aggregate (free+reclaimable)/total block headroom drops
              below ``admission_pressure`` while work is in flight,
              routing pauses — a dying replica's requeued load must not
              cascade the survivors into breach. Never applied to an
              idle fleet (no deadlock).

Determinism: all fleet logic is host-side control flow over the engines'
existing data-dynamism — no replica ever recompiles (``trace_counts``
stays {1,1} per replica through kills, drains, and requeues), and under
greedy sampling a request's output is bit-identical no matter which
replica (or how many, via recompute) served it: the replicas share one
model ``Engine`` (same params), and re-prefilling prompt+output is the
same eviction-by-recompute contract the single-engine scheduler already
honors. Chaos is seeded: the fleet fires the ``replica.<idx>.step`` fault
site BEFORE dispatching each replica's step (an injected kill never
corrupts engine state) and the router fires ``router.route`` before
reading signals, so ``FaultPlan`` replays bit-identical kill schedules
(``resilience.faults.default_fleet_chaos_plan``).
"""

from __future__ import annotations

import dataclasses
import itertools
import time

import numpy as np

from triton_distributed_tpu.obs import trace as _trace
from triton_distributed_tpu.obs.journey import JourneyRecorder
from triton_distributed_tpu.obs.slo import STATE_LEVEL
from triton_distributed_tpu.resilience import checkpoint as _ckpt
from triton_distributed_tpu.resilience import faults as _faults
from triton_distributed_tpu.resilience import guards as _guards
from triton_distributed_tpu.serving.batch_engine import (
    BatchEngine,
    StepBuildError,
    is_resource_error,
)
from triton_distributed_tpu.serving.metrics import Metrics
from triton_distributed_tpu.serving.router import Router
from triton_distributed_tpu.serving.scheduler import Request

# Replica health states. ROUTABLE replicas accept new placements and get
# stepped; the rest are on the way out (QUARANTINED drains next step,
# DRAINING is mid-teardown, DEAD is terminal).
HEALTHY = "HEALTHY"
DEGRADED = "DEGRADED"
RECOVERED = "RECOVERED"
QUARANTINED = "QUARANTINED"
DRAINING = "DRAINING"
DEAD = "DEAD"

ROUTABLE = frozenset({HEALTHY, DEGRADED, RECOVERED})
_SLO_NAMES = {v: k for k, v in STATE_LEVEL.items()}


@dataclasses.dataclass
class Replica:
    """One fleet member: a ``BatchEngine`` plus its health bookkeeping."""

    idx: int
    engine: BatchEngine
    state: str = HEALTHY
    consecutive_failures: int = 0
    breach_streak: int = 0       # consecutive fleet steps at SLO BREACH
    clean_streak: int = 0        # consecutive clean steps (recovery clock)
    requeued: int = 0            # requests displaced off this replica
    last_error: str | None = None
    quarantine_reason: str | None = None
    died_at_step: int | None = None   # fleet step of the DEAD transition
    revives: int = 0             # times revived from DEAD back to HEALTHY

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self.engine._slots)

    @property
    def queue_depth(self) -> int:
        return len(self.engine.scheduler)

    @property
    def empty(self) -> bool:
        return self.active_slots == 0 and self.queue_depth == 0

    def slo_level(self) -> int:
        """Worst objective state (0 OK / 1 WARN / 2 BREACH); 0 with no SLO
        engine attached."""
        slo = self.engine.slo
        return 0 if slo is None else slo.worst_level()

    def heartbeat_stale(self) -> bool:
        """Staleness matters only while the replica HAS work: an idle
        engine legitimately stops beating (``beat()`` fires per active
        step), so idle staleness is not a wedge."""
        hb = self.engine.heartbeat
        return (hb is not None and self.active_slots > 0 and hb.stale())


class Fleet:
    """N replicas + router + health machine + fleet-side request queue.

    ``engines``        the replica ``BatchEngine``s (index = replica id).
                       They should share one model ``Engine`` (same
                       params) so requeue-by-recompute is bit-exact; see
                       ``Fleet.build``.
    ``router``         a ``serving.router.Router`` (default one).
    ``requeue``        ``RetryPolicy`` whose ``retries`` is the per-request
                       DISPLACEMENT budget (a request survives at most
                       that many drains before failing with the reason
                       chain). Backoff fields are unused — requeues are
                       step-paced, not sleep-paced.
    ``fail_threshold`` consecutive step failures that quarantine a replica
                       (the first failure already marks it DEGRADED).
    ``breach_quarantine_evals`` consecutive fleet steps at SLO BREACH
                       before the breaching replica is quarantined.
    ``recovery_steps`` clean steps a DEGRADED replica needs to be marked
                       RECOVERED (one more clean step -> HEALTHY).
    ``admission_pressure`` fleet-wide routing backpressure threshold
                       (fraction of aggregate routable headroom; 0 = off).
    ``revive_cooldown_steps`` fleet steps a DEAD replica must stay dead
                       before ``revive()`` will take it back — a replica
                       that died to a persistent fault must not flap
                       DEAD->HEALTHY->DEAD every step.
    """

    def __init__(self, engines, *, router: Router | None = None,
                 requeue: _guards.RetryPolicy | None = None,
                 fail_threshold: int = 3,
                 breach_quarantine_evals: int = 3,
                 recovery_steps: int = 8,
                 admission_pressure: float = 0.0,
                 revive_cooldown_steps: int = 8,
                 serve_trace: bool = True):
        engines = list(engines)
        if not engines:
            raise ValueError("a fleet needs at least one replica")
        self.replicas = [Replica(idx=i, engine=e)
                         for i, e in enumerate(engines)]
        self.router = Router() if router is None else router
        self.requeue = (_guards.RetryPolicy(retries=3) if requeue is None
                        else requeue)
        self.fail_threshold = fail_threshold
        self.breach_quarantine_evals = breach_quarantine_evals
        self.recovery_steps = recovery_steps
        self.admission_pressure = admission_pressure
        self.revive_cooldown_steps = revive_cooldown_steps
        self.metrics = Metrics(windowed=False)
        self.n_steps = 0
        self._controller = None
        # Fleet-side request plumbing: requests wait here until the router
        # places them; a drained replica's requests come back here too.
        self._pending: list[Request] = []
        self._submitted: dict[object, Request] = {}
        self._requeues: dict[object, list[str]] = {}
        self._failed: dict[object, Request] = {}
        self._req_counter = 0
        # Fleet-wide arrival stamps: pre-assigning arrival_seq here (not in
        # a replica's scheduler) keeps FIFO order stable across requeues
        # AND keeps heap keys unique when requests from different replicas
        # land in one survivor's queue.
        self._arrival = itertools.count()
        self.state_log: list[dict] = []
        # Crash-consistent recovery (resilience/checkpoint.py): the
        # write-ahead journal (attach_journal), requests reconstructed
        # already-finished by ``restore`` (merged into ``finished`` — the
        # engines never saw them finish), and the construction spec
        # ``build``/``restore`` record so ``spawn()`` can mint an
        # identically-configured replica.
        self.journal = None
        self._restored_finished: dict[object, Request] = {}
        self._build_spec = None
        self._controller_snapshot = None
        # ONE journey recorder shared across every replica (replacing the
        # per-engine ones), so a request that drains off replica A and
        # finishes on replica B is a single stitched timeline. Disabled
        # only when every engine was built with ``journey=False``.
        if any(rep.engine.journey is not None for rep in self.replicas):
            self.journey = JourneyRecorder()
            for rep in self.replicas:
                rep.engine.journey = self.journey
        else:
            self.journey = None
        # Fleet-level incident engine: watches the counters only the fleet
        # sees (replica quarantines, requeue displacements, fleet-side
        # terminal failures). Per-replica engines keep their own detectors
        # and each gets its replica idx stamped so the merged view can
        # tell who tripped; ``_incidents_block()`` rolls everything up.
        if any(getattr(rep.engine, "incidents", None) is not None
               for rep in self.replicas):
            from triton_distributed_tpu.obs.incident import IncidentEngine
            for rep in self.replicas:
                if rep.engine.incidents is not None:
                    rep.engine.incidents.replica = rep.idx
            self.incidents = IncidentEngine(replica=-1)
            self.incidents.fault_log_source = lambda: (
                p.log if (p := _faults.get_plan()) is not None else ())
            self.incidents.controller_source = lambda: (
                self._controller.action_log
                if self._controller is not None else ())
        else:
            self.incidents = None
        # Always-on serving recorder (obs/replay.py): bounded-memory
        # arrival + per-step work capture feeding the deterministic
        # replay/what-if harness. One on_submit per request and one
        # O(replicas) counter read per step — cheap enough to leave on
        # (bench --serve --whatif gates the overhead); replay fleets
        # themselves run with serve_trace=False.
        if serve_trace:
            from triton_distributed_tpu.obs.replay import ServeTrace
            self.serve_trace = ServeTrace()
        else:
            self.serve_trace = None

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, engine, *, n_replicas: int = 3, router=None,
              requeue=None, fail_threshold: int = 3,
              breach_quarantine_evals: int = 3, recovery_steps: int = 8,
              admission_pressure: float = 0.0,
              revive_cooldown_steps: int = 8, serve_trace: bool = True,
              **batch_engine_kwargs
              ) -> "Fleet":
        """N identically-configured replicas over ONE model ``Engine``
        (shared params — requeue-by-recompute stays bit-exact; each
        replica still owns its private KVPool/Scheduler/RadixPrefixCache
        and compiles its own two steps, so ``trace_counts`` is per
        replica). ``batch_engine_kwargs`` forward to each ``BatchEngine``.
        """
        if n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        engines = [BatchEngine(engine, **batch_engine_kwargs)
                   for _ in range(n_replicas)]
        fleet = cls(engines, router=router, requeue=requeue,
                    fail_threshold=fail_threshold,
                    breach_quarantine_evals=breach_quarantine_evals,
                    recovery_steps=recovery_steps,
                    admission_pressure=admission_pressure,
                    revive_cooldown_steps=revive_cooldown_steps,
                    serve_trace=serve_trace)
        # Recorded so ``spawn()`` can build an identical replica later.
        fleet._build_spec = (engine, dict(batch_engine_kwargs))
        return fleet

    # -- request intake -----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *, priority: int = 0,
               req_id=None, tenant: str | None = None) -> object:
        """Queue one request fleet-side; the router places it on the next
        ``step()``. Returns the request id. ``tenant`` is the billing
        identity for the efficiency ledger's per-tenant cost table; it
        rides ON the Request (like the journey context), so attribution
        follows the request across drain and cross-replica requeue."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new_tokens>=1")
        total = len(prompt) + max_new_tokens
        # Validate against EVERY replica's geometry up front so a later
        # requeue can never land on a replica that cannot hold the request.
        for rep in self.replicas:
            pool = rep.engine.pool
            if total > pool.max_seq_len:
                raise ValueError(
                    f"prompt+max_new_tokens ({total}) exceeds replica "
                    f"{rep.idx}'s max_seq_len ({pool.max_seq_len})")
            if pool.blocks_for(total) > pool.n_blocks:
                raise ValueError(
                    f"request needs {pool.blocks_for(total)} blocks; "
                    f"replica {rep.idx} has {pool.n_blocks} total")
        if req_id is None:
            req_id = f"req-{self._req_counter}"
        if req_id in self._submitted:
            raise ValueError(f"duplicate req_id {req_id!r}")
        self._req_counter += 1
        req = Request(req_id=req_id, prompt=prompt,
                      max_new_tokens=max_new_tokens, priority=priority,
                      arrival_seq=next(self._arrival),
                      submit_t=time.monotonic(), tenant=tenant)
        if self.journal is not None:
            # The WAL contract: a request exists once its submit record is
            # DURABLE (RequestJournal fsyncs submit frames immediately).
            # Journal BEFORE registering, and let a journal fault
            # propagate to the caller — an unjournaled accepted request
            # would be silently lost by a crash, which is the one thing
            # this subsystem exists to prevent.
            # Schema 2: the arrival stamp (wall clock + fleet step index)
            # rides the submit frame so post-hoc tools can reconstruct
            # the arrival process and bill tenants without a live fleet.
            self.journal.append("submit", req_id=req_id, prompt=prompt,
                                max_new_tokens=int(max_new_tokens),
                                priority=int(priority),
                                arrival_seq=req.arrival_seq, tenant=tenant,
                                arrival_t=req.submit_t,
                                arrival_step=int(self.n_steps))
        self._submitted[req_id] = req
        self._pending.append(req)
        if self.serve_trace is not None:
            self.serve_trace.on_submit(req, self.n_steps)
        _trace.async_begin("request", req_id, prompt_len=len(prompt),
                           max_new_tokens=max_new_tokens)
        if self.journey is not None:
            # Fleet submits open in the "route" bucket: the first wait is
            # for a placement decision, not a replica queue.
            req.journey = self.journey.begin(
                req_id, phase="route", prompt_len=len(prompt),
                **({"tenant": tenant} if tenant else {}))
        return req_id

    # -- health machine -----------------------------------------------------

    def _transition(self, rep: Replica, new: str, reason: str) -> None:
        old, rep.state = rep.state, new
        self.state_log.append({"step": self.n_steps, "replica": rep.idx,
                               "from": old, "to": new, "reason": reason})
        self.metrics.inc("replica_transitions",
                         labels={"to": new})
        _trace.instant("replica_state", replica=rep.idx, old=old, new=new,
                       reason=reason)

    def _quarantine_replica(self, rep: Replica, reason: str) -> None:
        if rep.state not in ROUTABLE:
            return
        rep.quarantine_reason = reason
        rep.clean_streak = 0
        self.metrics.inc("replica_quarantines")
        self._transition(rep, QUARANTINED, reason)

    def _record_failure(self, rep: Replica, exc: Exception) -> None:
        rep.consecutive_failures += 1
        rep.clean_streak = 0
        rep.last_error = f"{type(exc).__name__}: {exc}"
        self.metrics.inc("replica_step_failures")
        _trace.instant("replica_step_failure", replica=rep.idx,
                       failures=rep.consecutive_failures,
                       error=rep.last_error)
        if rep.consecutive_failures >= self.fail_threshold:
            self._quarantine_replica(
                rep, f"{rep.consecutive_failures} consecutive step "
                     f"failures (last: {rep.last_error})")
        elif rep.state in (HEALTHY, RECOVERED):
            self._transition(rep, DEGRADED,
                             f"step failure: {rep.last_error}")

    def _update_health(self) -> None:
        """Poll the passive detectors (heartbeat staleness, SLO state) and
        run the recovery clock. Step-failure escalation happens inline in
        ``_step_replicas`` where the exception is caught."""
        for rep in self.replicas:
            if rep.state not in ROUTABLE:
                continue
            if rep.heartbeat_stale():
                self._quarantine_replica(
                    rep, f"heartbeat stale "
                         f"({rep.engine.heartbeat.age():.1f}s > "
                         f"{rep.engine.heartbeat.interval_s}s)")
                continue
            lvl = rep.slo_level()
            if lvl >= STATE_LEVEL["BREACH"]:
                rep.breach_streak += 1
                rep.clean_streak = 0
                if rep.breach_streak >= self.breach_quarantine_evals:
                    self._quarantine_replica(
                        rep, f"SLO breach sustained for "
                             f"{rep.breach_streak} steps")
                elif rep.state in (HEALTHY, RECOVERED):
                    self._transition(rep, DEGRADED, "SLO breach")
                continue
            rep.breach_streak = 0
            if lvl > 0:
                rep.clean_streak = 0
                if rep.state in (HEALTHY, RECOVERED):
                    self._transition(rep, DEGRADED, "SLO warn")
                continue
            # All detectors clean this step: advance the recovery clock.
            if rep.consecutive_failures:
                continue          # failing streak still open
            rep.clean_streak += 1
            if (rep.state == DEGRADED
                    and rep.clean_streak >= self.recovery_steps):
                self._transition(
                    rep, RECOVERED,
                    f"{rep.clean_streak} clean steps")
            elif rep.state == RECOVERED:
                self._transition(rep, HEALTHY, "recovery confirmed")

    def _drain(self) -> bool:
        """Tear down quarantined replicas: DRAINING replicas that emptied
        go DEAD; QUARANTINED replicas drain (requests requeue fleet-side)
        and become DRAINING. Two phases in this order so DRAINING is
        observable for at least one full fleet step."""
        moved = False
        for rep in self.replicas:
            if rep.state == DRAINING and rep.empty:
                rep.died_at_step = self.n_steps
                self._transition(rep, DEAD, "drained")
        for rep in self.replicas:
            if rep.state != QUARANTINED:
                continue
            reason = (f"replica {rep.idx} quarantined: "
                      f"{rep.quarantine_reason}")
            reqs = rep.engine.drain(reason=reason)
            hb = rep.engine.heartbeat
            if hb is not None:
                hb.stop_monitor()
            rep.requeued += len(reqs)
            for req in reqs:
                self._requeue(req, reason)
            moved = moved or bool(reqs)
            self._transition(rep, DRAINING,
                             f"drained {len(reqs)} request(s)")
        return moved

    # -- revival ------------------------------------------------------------

    def revive(self, idx: int, *, force: bool = False) -> bool:
        """Bring a DEAD replica back to HEALTHY. DEAD is only reached via
        DRAINING && empty, so the engine is already drained — revival is a
        host-side reset, NEVER a rebuild: the replica's two compiled steps
        are reused untouched (``trace_counts`` stays {1,1} through a
        kill+revive cycle).

        Cooldown-gated: returns False (no-op) until
        ``revive_cooldown_steps`` fleet steps have passed since the DEAD
        transition, unless ``force=True``. The reset: a defensive drain
        (anything left requeues fleet-side), the prefix cache dropped
        (stale KV from the dead residency must not be adopted), pool
        invariants verified, health counters cleared, and the heartbeat
        re-baselined + its monitor restarted if one was running before the
        quarantine teardown stopped it."""
        rep = self.replicas[idx]
        if rep.state != DEAD:
            raise ValueError(f"replica {idx} is {rep.state}, not DEAD")
        age = self.n_steps - (rep.died_at_step or 0)
        if not force and age < self.revive_cooldown_steps:
            return False
        eng = rep.engine
        reason = f"revive replica {idx}"
        for req in eng.drain(reason=reason):   # defensive: should be empty
            rep.requeued += 1
            self._requeue(req, reason)
        if eng.prefix_cache is not None:
            eng.prefix_cache.drop()
        eng.pool.check_invariants()
        rep.consecutive_failures = 0
        rep.breach_streak = 0
        rep.clean_streak = 0
        rep.last_error = None
        rep.quarantine_reason = None
        hb = eng.heartbeat
        if hb is not None:
            hb.reset()               # fresh staleness baseline, no raise
            if hb.monitored:
                hb.start_monitor()   # restartable by design; idempotent
        rep.revives += 1
        rep.died_at_step = None
        self.metrics.inc("replica_revives")
        self._transition(rep, HEALTHY,
                         f"revived after {age} steps dead "
                         f"(revive #{rep.revives})")
        return True

    # -- crash-consistent recovery (resilience/checkpoint.py) ---------------

    def _journal_safe(self, kind: str, **fields) -> None:
        """Best-effort journal append for records determinism can heal
        (requeue/fail chains replay from the suffix; a lost one only
        loses audit detail, never a request) — a journal fault degrades
        to a metric. Submit records do NOT come through here."""
        if self.journal is None:
            return
        try:
            self.journal.append(kind, **fields)
        except _faults.TransientFault:
            self.metrics.inc("journal_faults")

    def attach_journal(self, path: str, *, fsync_every: int = 8):
        """Open (or resume — torn tails heal) the write-ahead journal at
        ``path`` and propagate it to every replica engine: from here on,
        submits are durable before they are accepted and every
        emit/finish/fail/requeue is framed into the log. Returns the
        ``RequestJournal``."""
        self.journal = _ckpt.RequestJournal(path, fsync_every=fsync_every)
        for rep in self.replicas:
            rep.engine.journal = self.journal
        return self.journal

    def _snapshot_state(self) -> dict:
        # Peek the arrival counter without perturbing it (itertools.count
        # has no peek: read one, rebuild from the same value).
        nxt = next(self._arrival)
        self._arrival = itertools.count(nxt)
        eng0 = self.replicas[0].engine
        return {
            "n_steps": self.n_steps,
            "req_counter": self._req_counter,
            "next_arrival": nxt,
            "requests": {str(rid): req.to_wire()
                         for rid, req in self._submitted.items()},
            "requeues": {str(rid): list(chain)
                         for rid, chain in self._requeues.items()},
            "pool_geometry": eng0.pool.geometry(),
            "n_slots": eng0.n_slots,
            "spec": [rep.engine.spec.controller.snapshot()
                     if rep.engine.spec is not None else None
                     for rep in self.replicas],
            "controller": (self._controller.snapshot()
                           if self._controller is not None else None),
        }

    def checkpoint(self, ckpt_dir: str) -> dict:
        """Snapshot the fleet's HOST-SIDE truth to ``ckpt_dir``: request
        table with token histories, displacement chains, arrival/req
        counters, pool geometry (metadata only — KV bytes recompute via
        prefill on re-admission), per-replica SpecController windows, and
        the controller knob state. The manifest pins the journal sequence
        number at the snapshot barrier, so ``restore`` replays exactly
        the suffix written afterwards. Returns the manifest."""
        # A step in flight is read first: its tokens are on the requests
        # and in the journal before the barrier.
        for rep in self.replicas:
            if rep.state in ROUTABLE:
                rep.engine.flush()
        journal_seq, journal_path = -1, None
        if self.journal is not None:
            self.journal.flush(fsync=True)
            journal_seq = self.journal.next_seq - 1
            journal_path = self.journal.path
        manifest = _ckpt.save_checkpoint(
            ckpt_dir, self._snapshot_state(),
            journal_seq=journal_seq, journal_path=journal_path,
            meta={"n_replicas": len(self.replicas)})
        self._journal_safe("ckpt", journal_seq=journal_seq)
        self.metrics.inc("checkpoints")
        return manifest

    @classmethod
    def restore(cls, ckpt_dir: str, engine, *, journal_path=None,
                n_replicas: int | None = None, router=None, requeue=None,
                fail_threshold: int = 3, breach_quarantine_evals: int = 3,
                recovery_steps: int = 8, admission_pressure: float = 0.0,
                revive_cooldown_steps: int = 8, donor=None,
                **batch_engine_kwargs) -> "Fleet":
        """Build a fresh fleet and adopt a checkpoint + journal suffix.

        The determinism contract does the heavy lifting: an unfinished
        request re-enters the fleet queue as a plain pending request whose
        context is prompt + everything journaled so far — the router
        re-places it anywhere, ``adopt`` re-prefills (prefix-cache
        warm-start when possible), and greedy decode continues the
        bit-identical token stream. No device state is read back;
        restore IS requeue-by-recompute at fleet scope.

        ``n_replicas`` defaults to the checkpointed count (pass another
        value for elastic restore). ``donor`` (a ``BatchEngine`` with
        already-traced steps and identical geometry) lets every new
        replica share compiled steps instead of retracing — the
        kill-sweep tests restore dozens of fleets against one compile.
        Refuses a checkpoint from a different compiled world
        (``FingerprintMismatch``) or mismatched pool geometry."""
        state, manifest = _ckpt.load_checkpoint(ckpt_dir)
        if n_replicas is None:
            n_replicas = int(manifest.get("n_replicas", 1))
        fleet = cls.build(
            engine, n_replicas=n_replicas, router=router, requeue=requeue,
            fail_threshold=fail_threshold,
            breach_quarantine_evals=breach_quarantine_evals,
            recovery_steps=recovery_steps,
            admission_pressure=admission_pressure,
            revive_cooldown_steps=revive_cooldown_steps,
            **batch_engine_kwargs)
        if donor is not None:
            for rep in fleet.replicas:
                rep.engine.share_steps_from(donor)
        geo = state.get("pool_geometry", {})
        for rep in fleet.replicas:
            here = rep.engine.pool.geometry()
            if geo and here != geo:
                raise ValueError(
                    f"replica {rep.idx} pool geometry {here} != "
                    f"checkpointed {geo} — admission/preemption decisions "
                    "would diverge, breaking bit-identical resume")
        if journal_path is None:
            journal_path = manifest.get("journal_path")
        fleet._adopt_checkpoint(state, manifest, journal_path)
        return fleet

    def _adopt_checkpoint(self, state: dict, manifest: dict,
                          journal_path) -> None:
        import os

        suffix = []
        if journal_path and os.path.exists(journal_path):
            jr = _ckpt.read_journal(journal_path)
            barrier = int(manifest.get("journal_seq", -1))
            suffix = [r for r in jr.records if r["seq"] > barrier]
        reqs = _ckpt.replay_requests(suffix, base=state.get("requests", {}))
        self.n_steps = int(state.get("n_steps", 0))
        self._req_counter = int(state.get("req_counter", 0))
        self._arrival = itertools.count(int(state.get("next_arrival", 0)))
        chains = {rid: list(c)
                  for rid, c in state.get("requeues", {}).items()}
        n_pending = 0
        for wire in sorted(reqs.values(),
                           key=lambda w: (w.get("arrival_seq") is None,
                                          w.get("arrival_seq") or 0)):
            req = Request.from_wire(wire)
            rid = req.req_id
            chain = chains.get(rid, []) + wire.get("requeues", [])[
                len(chains.get(rid, [])):]
            if chain:
                self._requeues[rid] = chain
            self._submitted[rid] = req
            if (req.status == "pending"
                    and len(req.output) >= req.max_new_tokens):
                # Crashed between the last journaled emit and the finish
                # record: the output is already complete (and finish adds
                # no tokens), so the request finished — just unwitnessed.
                req.status = "ok"
            if req.status == "ok":
                self._restored_finished[rid] = req
            elif req.status == "failed":
                self._failed[rid] = req
            else:
                req.status = "pending"
                req.submit_t = time.monotonic()
                if self.journey is not None:
                    req.journey = self.journey.begin(
                        rid, phase="restore", restored=True,
                        prompt_len=len(req.prompt),
                        replayed_tokens=len(req.output))
                self._pending.append(req)
                n_pending += 1
        if reqs:
            self.metrics.inc("restored_requests", float(len(reqs)))
        if self.incidents is not None:
            self.incidents.annotate(
                "restore", checkpoint_step=int(state.get("n_steps", 0)),
                requests=len(reqs), replayed_records=len(suffix),
                pending=n_pending)
        # The controller snapshot applies when a controller attaches
        # (attach_controller below) — knob values re-actuate then.
        self._controller_snapshot = state.get("controller")
        for rep, snap in zip(self.replicas, state.get("spec") or ()):
            if snap and rep.engine.spec is not None:
                rep.engine.spec.controller.restore(snap)
        if journal_path:
            # Reopen for continued writes (heals any torn tail, resumes
            # the sequence) and mark the recovery in the log itself.
            self.attach_journal(journal_path)
            self._journal_safe("restore", requests=len(reqs),
                               pending=n_pending)

    # -- elastic scale ------------------------------------------------------

    def spawn(self) -> int:
        """Add one identically-configured replica, serving WITHOUT a
        retrace: the new engine adopts a live replica's compiled steps
        (``share_steps_from`` — same model Engine, same geometry, so the
        jitted closures are reusable as-is and ``trace_counts`` stays
        {1,1} on every sharer). Returns the new replica's index."""
        if self._build_spec is None:
            raise ValueError("spawn() needs the construction spec — build "
                             "the fleet via Fleet.build()/restore()")
        engine, kwargs = self._build_spec
        eng = BatchEngine(engine, **kwargs)
        donor = next((rep.engine for rep in self.replicas
                      if rep.state != DEAD), self.replicas[0].engine)
        eng.share_steps_from(donor)
        idx = len(self.replicas)
        rep = Replica(idx=idx, engine=eng)
        self.replicas.append(rep)
        if self.journey is not None:
            eng.journey = self.journey
        if eng.incidents is not None:
            eng.incidents.replica = idx
        eng.journal = self.journal
        if self._controller is not None:
            # A fleet controller actuates knobs on EVERY replica; push the
            # current values so the newcomer doesn't sit at construction
            # defaults until the next move.
            for name, value in self._controller.knob_values().items():
                self._controller._set_knob(name, value)
        self.metrics.inc("replica_spawns")
        self._transition(rep, HEALTHY, f"spawned as replica {idx}")
        if self.incidents is not None:
            self.incidents.annotate("spawn", replica=idx)
        return idx

    def retire(self, idx: int) -> int:
        """Administratively remove a replica from service: drain its
        requests back to the fleet queue (full displacement reason
        chains; the requeue budget applies) and mark it DEAD — the same
        teardown a quarantine gets, minus the health verdict. Returns
        the number of requests drained to survivors."""
        rep = self.replicas[idx]
        if rep.state == DEAD:
            raise ValueError(f"replica {idx} is already DEAD")
        if sum(r.state in ROUTABLE for r in self.replicas
               if r.idx != idx) < 1:
            raise ValueError("refusing to retire the last routable "
                             "replica — the fleet could serve nothing")
        reason = f"replica {idx} retired"
        reqs = rep.engine.drain(reason=reason)
        hb = rep.engine.heartbeat
        if hb is not None:
            hb.stop_monitor()
        rep.requeued += len(reqs)
        for req in reqs:
            self._requeue(req, reason)
        rep.died_at_step = self.n_steps
        self.metrics.inc("replica_retirements")
        self._transition(rep, DEAD,
                         f"retired ({len(reqs)} request(s) drained)")
        if self.incidents is not None:
            self.incidents.annotate("retire", replica=idx,
                                    drained=len(reqs))
        return len(reqs)

    # -- control plane ------------------------------------------------------

    def attach_controller(self, controller=None, **kwargs):
        """Attach the adaptive control plane at FLEET scope (one
        controller per plant — do not also attach per-engine ones): every
        ``step()`` it observes aggregate fleet state and actuates the
        shared knobs (per-replica ``prefill_budget`` and
        ``admission_pressure``, fleet backpressure, router WARN shed,
        cache reclaim) plus cooldown-gated ``revive()`` of DEAD replicas.
        Returns the controller."""
        from triton_distributed_tpu.serving.controller import Controller
        if controller is None:
            controller = Controller(fleet=self, **kwargs)
        self._controller = controller
        if self._controller_snapshot is not None:
            # Restored fleet: re-adopt the checkpointed knob state (and
            # re-actuate the values onto the rebuilt replicas).
            controller.restore(self._controller_snapshot)
            self._controller_snapshot = None
        return controller

    @property
    def controller(self):
        return self._controller

    # -- requeue / failure --------------------------------------------------

    def _fail(self, req: Request, reason: str) -> None:
        chain = self._requeues.get(req.req_id, [])
        req.status = "failed"
        req.error = " -> ".join([*chain, reason]) if chain else reason
        req.finish_t = time.monotonic()
        self._failed[req.req_id] = req
        self._journal_safe("fail", req_id=req.req_id, error=req.error)
        self.metrics.inc("requests_failed")
        _trace.async_end("request", req.req_id, failed=True,
                         error=req.error)
        if self.journey is not None:
            self.journey.finish(req.req_id, status="failed",
                                error=req.error, keep=True)

    def _requeue(self, req: Request, reason: str) -> None:
        """Put a displaced request back in the fleet queue, or fail it with
        the full displacement chain once the ``RetryPolicy`` budget is
        spent (no infinite drain->requeue loops)."""
        chain = self._requeues.setdefault(req.req_id, [])
        chain.append(reason)
        if len(chain) > self.requeue.retries:
            self.metrics.inc("requeue_exhausted")
            self._fail(req, f"requeue budget exhausted "
                            f"({self.requeue.retries} allowed)")
            return
        self._pending.append(req)
        self._journal_safe("requeue", req_id=req.req_id, reason=reason)
        self.metrics.inc("requeues")
        _trace.instant("requeue", req=req.req_id, attempt=len(chain),
                       reason=reason)
        if self.journey is not None:
            self.journey.event(req.req_id, "requeue", attempt=len(chain),
                               reason=reason)

    # -- routing ------------------------------------------------------------

    def _signals(self, rep: Replica, tokens: list[int]) -> dict:
        """The live signal bundle the router scores — see
        ``Router`` docstring for the schema. The prefix probe degrades to
        a cold miss under an injected ``cache.lookup`` fault (same policy
        as the engine's own probe)."""
        eng = rep.engine
        match = 0
        cache = eng.prefix_cache
        if cache is not None and cache.enabled and len(tokens) > 1:
            try:
                match = cache.match_len(tokens, max_len=len(tokens) - 1)
            except _faults.TransientFault:
                self.metrics.inc("route_probe_faults")
                match = 0
        pool = eng.pool
        return {
            "match_frac": match / len(tokens) if tokens else 0.0,
            "headroom": (pool.n_free + pool.n_reclaimable) / pool.n_blocks,
            "load": (rep.queue_depth + rep.active_slots) / eng.n_slots,
            "slo_level": rep.slo_level(),
        }

    def _backpressured(self, routable: list[Replica]) -> bool:
        if self.admission_pressure <= 0.0:
            return False
        busy = any(rep.active_slots for rep in routable)
        if not busy:
            return False          # idle fleet always admits (no deadlock)
        avail = sum(rep.engine.pool.n_free + rep.engine.pool.n_reclaimable
                    for rep in routable)
        total = sum(rep.engine.pool.n_blocks for rep in routable)
        return avail / total < self.admission_pressure

    def _route_pending(self) -> bool:
        if not self._pending:
            return False
        routable = [rep for rep in self.replicas if rep.state in ROUTABLE]
        if not routable:
            if all(rep.state == DEAD for rep in self.replicas):
                # Terminal: nothing will ever serve these.
                while self._pending:
                    self._fail(self._pending.pop(0),
                               "no routable replicas (fleet dead)")
            return False
        if self._backpressured(routable):
            self.metrics.inc("fleet_backpressure")
            _trace.instant("fleet_backpressure", waiting=len(self._pending))
            return False
        placed = False
        pending, self._pending = self._pending, []
        while pending:
            req = pending.pop(0)
            tokens = req.prompt + req.output
            candidates = [(rep.idx, self._signals(rep, tokens))
                          for rep in routable]
            try:
                decision = self.router.route(tokens, candidates,
                                             tenant=req.tenant)
            except _faults.TransientFault as e:
                # Faulted placement defers THIS request and everything
                # behind it to the next step — degradation, not loss, and
                # FIFO order is preserved.
                self.metrics.inc("routes_deferred")
                _trace.instant("route_deferred", req=req.req_id,
                               error=str(e))
                self._pending = [req, *pending]
                return placed
            rep = self.replicas[decision.replica]
            if self.journey is not None:
                # The route hop carries the WHOLE decision — winner score,
                # every candidate's score and weighted component breakdown
                # — so explain_request can show why this replica won.
                self.journey.hop(
                    req.req_id, "route", where=rep.idx,
                    score=round(decision.score, 6),
                    scores={str(k): round(v, 6)
                            for k, v in decision.scores.items()},
                    breakdown={str(k): {c: round(v, 6)
                                        for c, v in comp.items()}
                               for k, comp in decision.breakdown.items()},
                    **({"tenant": req.tenant} if req.tenant else {}))
            rep.engine.adopt(req)
            placed = True
            self.metrics.inc("requests_routed")
            _trace.instant("route", req=req.req_id, replica=rep.idx,
                           score=round(decision.score, 4),
                           match_frac=round(
                               decision.signals[rep.idx]["match_frac"], 4))
        return placed

    # -- stepping -----------------------------------------------------------

    def _step_replicas(self) -> bool:
        """One engine step per routable replica, each behind its
        ``replica.<idx>.step`` fault site (fired BEFORE the engine runs, so
        an injected kill never half-mutates engine state — the drained
        requests recompute from intact ``Request`` objects)."""
        busy = False
        for rep in self.replicas:
            if rep.state not in ROUTABLE:
                continue
            try:
                if _faults._PLAN is not None:
                    _faults.fire(f"replica.{rep.idx}.step")
                stepped = rep.engine.step()
            except Exception as e:  # noqa: BLE001 — replica error boundary
                # A step that cannot be built (trace/lower/compile/first
                # allocation) or a device out of memory is not a replica
                # fault: quarantining would hide it behind failed requests
                # and a clean exit.
                if isinstance(e, StepBuildError) or is_resource_error(e):
                    raise
                self._record_failure(rep, e)
                continue
            if rep.consecutive_failures:
                rep.consecutive_failures = 0
                self.metrics.inc("replica_recoveries")
                _trace.instant("replica_recovered", replica=rep.idx)
            busy = busy or stepped
        return busy

    def step(self) -> bool:
        """One fleet iteration: health poll -> drain/teardown -> route ->
        step every routable replica. Returns False when nothing happened
        (fleet idle). While the tracer records, the call is the span
        ``fleet.step``; ``fleet.route`` and each replica's ``engine.step``
        lie inside it, and what is left is the fleet's own turn (health,
        incidents, controller, drain, ``serve_trace``)."""
        with _trace.span("fleet.step", replicas=len(self.replicas),
                         pending=len(self._pending)):
            self.n_steps += 1
            self._update_health()
            if self.incidents is not None:
                fm = self.metrics.as_dict()
                self.incidents.observe({
                    "quarantines": fm.get("replica_quarantines", 0.0),
                    "requeues": fm.get("requeues", 0.0),
                    "requests_failed": fm.get("requests_failed", 0.0),
                })
            if self._controller is not None:
                self._controller.on_step()
            moved = self._drain()
            with _trace.span("fleet.route") as sp:
                c = self.metrics.counters
                n0 = sp and c.get("requests_routed", 0.0)
                routed = self._route_pending()
                if sp is not None:
                    sp.set(routed=int(c.get("requests_routed", 0.0) - n0))
            busy = self._step_replicas()
            if self.serve_trace is not None:
                self.serve_trace.on_step(self)
            return moved or routed or busy

    def run(self, max_steps: int | None = None) -> dict:
        """Step until idle (or ``max_steps``); returns ``{req_id:
        [token ids]}`` for every successful request. Failed requests (over
        requeue budget, engine-level quarantine, dead fleet) are in
        ``failed`` with reason chains — a chaos run completes instead of
        crashing."""
        steps = 0
        idle = 0
        while max_steps is None or steps < max_steps:
            if self.step():
                idle = 0
            elif not self._pending and all(
                    rep.empty or rep.state == DEAD
                    for rep in self.replicas):
                break
            else:
                idle += 1
                if idle > 1000:
                    raise RuntimeError(
                        "fleet made no progress for 1000 consecutive idle "
                        "steps (fault plan blocking all routing?)")
            steps += 1
        return {rid: list(req.output)
                for rid, req in self.finished.items()}

    # -- views --------------------------------------------------------------

    @property
    def finished(self) -> dict:
        # Requests ``restore`` reconstructed already-complete never pass
        # through an engine again — they merge here so zero-lost
        # accounting and ``check_invariants`` see them finished.
        out: dict = dict(self._restored_finished)
        for rep in self.replicas:
            out.update(rep.engine.finished)
        return out

    @property
    def failed(self) -> dict:
        """Terminal failures: fleet-level (requeue budget, dead fleet) and
        engine-level (in-slot quarantine), merged."""
        out = dict(self._failed)
        for rep in self.replicas:
            out.update(rep.engine.failed)
        return out

    @property
    def pending(self) -> list[Request]:
        return list(self._pending)

    def request(self, req_id) -> Request:
        """The handle a caller keeps for a request it submitted: the
        ``Request`` itself, whose ``output`` grows as tokens are read,
        ``status`` ends as "ok" or "failed" and ``finish_t`` is set when it
        has finished, wherever it is (pending, on a replica, requeued,
        done). Raises ``KeyError`` for an id this fleet was never given."""
        return self._submitted[req_id]

    def requeue_chain(self, req_id) -> list[str]:
        """The displacement reason chain recorded for ``req_id`` (empty if
        it was never requeued)."""
        return list(self._requeues.get(req_id, ()))

    def check_invariants(self) -> bool:
        """Fleet-wide ownership audit: every replica pool's invariants
        hold, no request is owned by two replicas (slot or queue), nothing
        fleet-pending is also replica-owned, and every submitted request
        is in EXACTLY ONE lifecycle state (pending / owned / finished /
        failed). Raises ``AssertionError`` on violation."""
        owner: dict = {}
        for rep in self.replicas:
            eng = rep.engine
            # A request whose last token is in flight has left its slot
            # and is not finished yet: read the step first.
            eng.flush()
            eng.pool.check_invariants()
            held = ([s.req.req_id for s in eng._slots if s is not None]
                    + [r.req_id for r in eng.scheduler.pending()])
            for rid in held:
                if rid in owner:
                    raise AssertionError(
                        f"request {rid} owned by replicas {owner[rid]} "
                        f"and {rep.idx}")
                owner[rid] = rep.idx
        pending_ids = {req.req_id for req in self._pending}
        both = pending_ids & set(owner)
        if both:
            raise AssertionError(
                f"requests both fleet-pending and replica-owned: "
                f"{sorted(map(str, both))}")
        fin, fail = self.finished, self.failed
        for rid in self._submitted:
            n = ((rid in owner) + (rid in pending_ids) + (rid in fin)
                 + (rid in fail))
            if n != 1:
                raise AssertionError(
                    f"request {rid} is in {n} lifecycle states "
                    f"(owned={rid in owner}, pending={rid in pending_ids},"
                    f" finished={rid in fin}, failed={rid in fail})")
        return True

    # -- observability ------------------------------------------------------

    def replica_table(self) -> list[dict]:
        """One row per replica — what ``serve_top --fleet`` and
        ``pod_check --fleet`` render."""
        rows = []
        for rep in self.replicas:
            m = rep.engine.metrics.as_dict()
            lookups = m.get("prefix_lookups", 0.0)
            rows.append({
                "idx": rep.idx,
                "state": rep.state,
                "slo": _SLO_NAMES.get(rep.slo_level(), "OK"),
                "queue": rep.queue_depth,
                "active": rep.active_slots,
                "slots": rep.engine.n_slots,
                "prefix_hit_rate": round(
                    m.get("prefix_hits", 0.0) / lookups, 4) if lookups
                    else 0.0,
                "requeued": rep.requeued,
                "revives": rep.revives,
                "tokens": int(m.get("tokens_generated", 0.0)),
                "completed": len(rep.engine._finished),
                "failed": len(rep.engine._failed),
                "failures": rep.consecutive_failures,
                "reason": rep.quarantine_reason,
            })
        return rows

    def stats_snapshot(self) -> dict:
        """Fleet frame for ``serve_top``: engine-shaped aggregates (so the
        existing panes render unchanged) plus the ``fleet`` block with the
        per-replica health table."""
        agg_counters: dict = {}
        pool = {"n_blocks": 0, "n_free": 0, "n_used": 0, "n_cached": 0,
                "n_reclaimable": 0}
        active = total_slots = queue = 0
        for rep in self.replicas:
            m = rep.engine.metrics.as_dict()
            for k in ("requests_admitted", "requests_completed",
                      "requests_failed", "tokens_generated", "preemptions",
                      "admission_backpressure", "slo_breaches"):
                agg_counters[k] = agg_counters.get(k, 0.0) + m.get(k, 0.0)
            for k in pool:
                pool[k] += getattr(rep.engine.pool, k)
            active += rep.active_slots
            total_slots += rep.engine.n_slots
            queue += rep.queue_depth
        fm = self.metrics.as_dict()
        agg_counters["requests_failed"] = (
            agg_counters.get("requests_failed", 0.0)
            + fm.get("requests_failed", 0.0))
        return {
            "t": round(time.monotonic(), 3),
            "wall_time": round(time.time(), 3),
            "slots": {"active": active, "total": total_slots},
            "queue_depth": queue + len(self._pending),
            "pool": pool,
            "counters": agg_counters,
            "windows": {},
            "fleet": {
                "n_replicas": len(self.replicas),
                "routable": sum(rep.state in ROUTABLE
                                for rep in self.replicas),
                "pending": len(self._pending),
                "requeues": int(fm.get("requeues", 0.0)),
                "requeue_exhausted": int(fm.get("requeue_exhausted", 0.0)),
                "quarantines": int(fm.get("replica_quarantines", 0.0)),
                "backpressure": int(fm.get("fleet_backpressure", 0.0)),
                "revives": int(fm.get("replica_revives", 0.0)),
                "steps": self.n_steps,
                "replicas": self.replica_table(),
            },
            **({"controller": self._controller.stats()}
               if self._controller is not None else {}),
            **({"journey": self.journey.stats()}
               if self.journey is not None else {}),
            **({"efficiency": eff} if (eff := self._efficiency_block())
               else {}),
            **({"spec": spec} if (spec := self._spec_block()) else {}),
            **({"incidents": inc} if (inc := self._incidents_block())
               else {}),
        }

    def _spec_block(self) -> dict:
        """Fleet-wide speculation rollup: per-replica live k + acceptance
        (what serve_top's spec pane renders) and the aggregate acceptance
        rate recomputed from SUMMED proposed/accepted counts — acceptance
        is a ratio, and ratios never average across replicas."""
        per = {}
        proposed = accepted = 0
        for rep in self.replicas:
            spec = getattr(rep.engine, "spec", None)
            if spec is None:
                continue
            st = spec.controller.stats()
            per[rep.idx] = {"drafter": spec.name, **st}
            proposed += st["proposed"]
            accepted += st["accepted"]
        if not per:
            return {}
        return {
            "replicas": per,
            "proposed": proposed,
            "accepted": accepted,
            "accept_rate": (round(accepted / proposed, 4)
                            if proposed else 0.0),
        }

    def _efficiency_block(self) -> dict:
        """Fleet-wide efficiency rollup: aggregate MFU/MBU/bubble from
        summed per-replica ledger TOTALS (ratios never average), the
        per-replica rows, the merged per-tenant cost table (conserved
        across kill+requeue because billing happened where the work ran),
        and every replica's worst-bubble steps tagged with its idx."""
        from triton_distributed_tpu.obs.efficiency import EfficiencyLedger
        ledgers = {rep.idx: rep.engine.efficiency for rep in self.replicas
                   if getattr(rep.engine, "efficiency", None) is not None}
        if not ledgers:
            return {}
        replicas = {}
        worst = []
        for idx, led in ledgers.items():
            st = led.stats()
            worst.extend({**row, "replica": idx}
                         for row in st.pop("worst_bubble", []))
            st.pop("tenants", None)     # merged fleet-wide below
            replicas[idx] = st
        worst.sort(key=lambda r: -r["bubble_s"])
        return {
            "aggregate": EfficiencyLedger.aggregate(ledgers.values()),
            "replicas": replicas,
            "tenants": EfficiencyLedger.merge_tenant_tables(
                led.tenant_table() for led in ledgers.values()),
            "worst_bubble": worst[:8],
        }

    def _incidents_block(self) -> dict:
        """Fleet-wide incident rollup: per-replica incident dumps (plus
        the fleet-level engine's own, keyed -1) merged by overlapping step
        windows — replicas step in lockstep, so one fault that trips three
        replicas' detectors in the same window is ONE fleet incident."""
        from triton_distributed_tpu.obs.incident import IncidentEngine
        dumps = {rep.idx: rep.engine.incidents.dump()
                 for rep in self.replicas
                 if getattr(rep.engine, "incidents", None) is not None}
        if self.incidents is not None:
            dumps[-1] = self.incidents.dump()
        if not dumps or not any(d["incidents"] for d in dumps.values()):
            return {}
        return IncidentEngine.merge(dumps)

    def perfdb_sample(self) -> dict:
        """Flat fleet metrics for the perf flight recorder — per-replica
        engine samples aggregate by SUM for counters; ``retraces`` sums so
        the {1,1}-per-replica compile contract gates as one number (0)."""
        out: dict = {}
        for rep in self.replicas:
            for k, v in rep.engine.perfdb_sample().items():
                if (k.endswith("_ms") or k.startswith("pool_")
                        or k.startswith("journey_")
                        or k in ("mfu", "mbu", "bubble_frac",
                                 "spec_accept_rate", "detect_latency_steps")
                        or k.startswith(("tenant_", "eff_", "incidents_"))):
                    # Latency/pool shape is per-replica; journey metrics
                    # come from ONE recorder shared by every replica, so
                    # summing would count the fleet N times (added once
                    # below). Efficiency RATIOS likewise never sum —
                    # fleet-level mfu/mbu/bubble_frac are recomputed from
                    # summed totals below; tenant tables merge there too.
                    # Incident counts come back MERGED (same window across
                    # replicas is one fleet incident) rather than summed.
                    continue
                out[k] = out.get(k, 0.0) + float(v)
        if self.journey is not None:
            out.update(self.journey.perfdb_sample())
        spec = self._spec_block()
        if spec:
            # Fleet acceptance = summed accepts over summed proposals
            # (the per-replica ratio was skipped above, not summed).
            out["spec_accept_rate"] = float(spec["accept_rate"])
        eff = self._efficiency_block()
        if eff and eff["aggregate"].get("steps"):
            agg = eff["aggregate"]
            out["mfu"] = float(agg["mfu"])
            out["mbu"] = float(agg["mbu"])
            out["bubble_frac"] = float(agg["bubble_frac"])
            out["eff_steps"] = float(agg["steps"])
            out["tenant_count"] = float(len(eff["tenants"]))
            for row in eff["tenants"]:
                out[f"tenant_tokens{{tenant={row['tenant']}}}"] = float(
                    row["tokens"])
        fm = self.metrics.as_dict()
        out["requests_failed"] = (out.get("requests_failed", 0.0)
                                  + fm.get("requests_failed", 0.0))
        for k in ("requeues", "requeue_exhausted", "replica_quarantines",
                  "fleet_backpressure", "requests_routed",
                  "replica_revives", "replica_spawns",
                  "replica_retirements", "restored_requests"):
            out[k] = float(fm.get(k, 0.0))
        inc = self._incidents_block()
        if inc or any(getattr(rep.engine, "incidents", None) is not None
                      for rep in self.replicas):
            out["incidents_open"] = float(inc.get("open", 0))
            out["incidents_total"] = float(inc.get("total", 0))
            out["detect_latency_steps"] = float(
                inc.get("detect_latency_steps", 0))
        out["n_replicas"] = float(len(self.replicas))
        out["replicas_dead"] = float(sum(rep.state == DEAD
                                         for rep in self.replicas))
        if self._controller is not None:
            out.update(self._controller.perfdb_sample())
        return out
