"""Request-journey tracing: fleet-wide causal timelines per request.

Every signal the serving stack already produces — tracer spans, blackbox
events, SLO transitions, fault firings, controller actions — is scoped to
one replica or one subsystem. This module adds the Dapper-style causal
layer above them: a ``JourneyContext`` (request id + monotonically
numbered hop ids) travels WITH the ``Request`` object through
``Router.route`` -> ``Fleet``/replica adopt -> ``Scheduler`` admission ->
``BatchEngine`` prefill/decode -> preemption/requeue -> completion, and a
``JourneyRecorder`` stitches the emitted journey-keyed events into one
timeline per request with a critical-path **latency attribution**:

  queue      waiting in a replica scheduler (submit/adopt -> admit)
  route      waiting fleet-side for a placement decision
  prefill    admitted and consuming prompt tokens (chunked; the recorder
             also splits consumed chunks by the runtime ``prefill_budget``
             in force, so controller narrowing is visible per request)
  decode     admitted and emitting one token per step
  preempted  evicted-by-recompute gap (preempt -> re-admit, same replica)
  requeue    fleet-scope displacement (drain -> re-route, new replica)
  restore    crash-recovery gap: a request replayed from the write-ahead
             journal (resilience/checkpoint.py) re-begins its timeline in
             this phase; the next route decision closes it, so the bucket
             is the restore-to-placement wait

Every instant between submit and finish is in exactly ONE phase, so the
per-bucket fractions sum to the total latency (the ``explain_request``
acceptance bar: 1.0 +/- 1e-6). The prefix-cache hit discount is reported
alongside (cached tokens adopted instead of recomputed) — it is time NOT
spent, so it rides the summary rather than the fraction sum.

Bounded, always-on (the PR 10 flight-recorder discipline): in-flight
requests hold an O(1) streaming accumulator plus a capped event list;
at finish the full event detail is retained only for requests the
``TailSampler`` kept (or that erred / were displaced — the forensically
interesting tail), everyone else keeps the O(1) attribution summary in a
bounded deque. Controller actions / SLO transitions / fault firings are
global events in their own ring, attached to a journey at stitch time
when they overlap its lifetime. Pure host-side data: journeys never touch
compiled state (``trace_counts`` stays {1,1}, outputs bit-identical).

Exports: ``stats()`` feeds ``stats_snapshot``/``perfdb_sample`` with
fleet-level windowed percentiles (``journey.queue_frac_p99`` ...);
``export_chrome_trace`` writes ``trace.p{rank}.journey.json`` — matched
by ``merge_chrome_traces``'s ``trace.p*.json`` glob, so journey rows land
next to the host-span and device-probe rows in ``trace.merged.json``.
Same timebase as the host tracer (``time.monotonic``), so the rows
align. ``tools/explain_request.py`` renders one journey as a forensic
markdown report. Design note: docs/observability.md.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import time

from triton_distributed_tpu.obs.metrics import Metrics

# The attribution buckets, in render order. See module docstring.
BUCKETS = ("queue", "route", "prefill", "decode", "preempted", "requeue",
           "restore")

# Event kind -> phase entered. Kinds absent here ("prefill_chunk",
# "first_token", annotations) leave the phase untouched.
_PHASE_AFTER = {
    "route": "queue",       # placement decided; now in the replica queue
    "adopt": "queue",
    "admit": "prefill",     # at least 1 token always recomputes at admit
    "decode_start": "decode",
    "preempt": "preempted",
    "drain": "requeue",
    "requeue": "requeue",
}

# Terminal kinds: close the accumulator at this event's timestamp.
_TERMINAL = {"finish": "ok", "quarantine": "failed", "fail": "failed"}

_SEGMENT_CAP = 128          # phase segments kept per journey
_ROUTE_CAP = 8              # route decisions kept per journey
_WINDOWS = ((10.0, "10s"), (300.0, "5m"))


@dataclasses.dataclass
class JourneyContext:
    """The per-request trace context: the request id plus monotonically
    numbered hop ids. Travels ON the ``Request`` object (scheduler.py), so
    hop numbering survives preemption, drain, and cross-replica requeue —
    the whole point: one id space per request across the fleet."""

    req_id: object
    n_hops: int = 0
    hops: list = dataclasses.field(default_factory=list)

    def next_hop(self, kind: str, *, where=None, t: float | None = None
                 ) -> int:
        """Allocate the next hop id for a queue-to-queue move (submit,
        route, preempt, drain). ``where`` is the replica index when the
        hop lands somewhere specific."""
        hop = self.n_hops
        self.n_hops += 1
        self.hops.append({"hop": hop, "kind": kind, "where": where,
                          **({"t": round(t, 6)} if t is not None else {})})
        return hop


class _Accum:
    """Streaming stitcher: replay journey events through the phase state
    machine, accumulating per-bucket seconds. The SAME code runs online
    (``JourneyRecorder.event`` feeds each event as it happens) and
    post-hoc (``Journey.stitch`` replays a dumped event list), so the live
    summary and a forensic reconstruction can never disagree."""

    def __init__(self):
        self.t0 = None
        self.phase = None
        self._t_phase = None
        self.buckets = {b: 0.0 for b in BUCKETS}
        self.segments: list = []          # (phase, t_start, t_end)
        self.budget_split: dict = {}      # str(budget) -> {chunks, tokens}
        self.routes: list = []            # compact route-decision trail
        self.cached_tokens = 0
        self.prefill_tokens = 0
        self.n_admits = 0
        self.n_preempts = 0
        self.n_requeues = 0
        self.status = None
        self.error = None

    def _enter(self, phase: str, t: float) -> None:
        if self.phase is not None and t > self._t_phase:
            self.buckets[self.phase] += t - self._t_phase
            if len(self.segments) < _SEGMENT_CAP:
                self.segments.append((self.phase, self._t_phase, t))
        elif self.phase is None:
            self.t0 = t
        self.phase = phase
        self._t_phase = t

    def feed(self, ev: dict) -> None:
        kind = ev.get("kind")
        t = float(ev.get("t", 0.0))
        if self.t0 is None:
            # First event opens the journey; its declared phase (``route``
            # for fleet submits, ``queue`` for direct engine submits) is
            # the opening bucket.
            self._enter(ev.get("phase", "queue"), t)
            if kind not in ("submit", "begin"):
                # Post-hoc stitch of a truncated ring: open, then fall
                # through so this event's own transition still applies.
                pass
        if kind == "admit":
            self.n_admits += 1
            self.cached_tokens += int(ev.get("cached", 0))
        elif kind == "prefill_chunk":
            d = self.budget_split.setdefault(
                str(int(ev.get("budget", 0))), {"chunks": 0, "tokens": 0})
            d["chunks"] += 1
            d["tokens"] += int(ev.get("tokens", 0))
            self.prefill_tokens += int(ev.get("tokens", 0))
        elif kind == "route":
            if len(self.routes) < _ROUTE_CAP:
                self.routes.append({
                    "hop": ev.get("hop"), "replica": ev.get("replica"),
                    "score": ev.get("score")})
        elif kind == "preempt":
            self.n_preempts += 1
        elif kind in ("drain", "requeue"):
            self.n_requeues += 1
        if kind in _TERMINAL:
            self.close(t, status=_TERMINAL[kind],
                       error=ev.get("error") or ev.get("reason"))
            return
        nxt = _PHASE_AFTER.get(kind)
        if nxt is not None:
            self._enter(nxt, t)

    def close(self, t_end: float, *, status: str = "ok",
              error: str | None = None) -> None:
        if self.status is not None:
            return                        # already terminal
        if self.phase is not None and t_end > self._t_phase:
            self.buckets[self.phase] += t_end - self._t_phase
            if len(self.segments) < _SEGMENT_CAP:
                self.segments.append((self.phase, self._t_phase, t_end))
        self._t_phase = t_end
        self.status = status
        self.error = error

    def summary(self, t_end: float | None = None) -> dict:
        t1 = self._t_phase if t_end is None else t_end
        total = max(0.0, (t1 - self.t0) if self.t0 is not None else 0.0)
        fracs = {b: (self.buckets[b] / total if total > 0.0 else 0.0)
                 for b in BUCKETS}
        return {
            "total_s": round(total, 9),
            "attribution_s": {b: round(self.buckets[b], 9)
                              for b in BUCKETS},
            "fracs": {b: round(fracs[b], 9) for b in BUCKETS},
            "dominant": max(BUCKETS, key=lambda b: fracs[b]),
            "cached_tokens": self.cached_tokens,
            "prefill_tokens": self.prefill_tokens,
            "budget_split": dict(self.budget_split),
            "n_admits": self.n_admits,
            "n_preempts": self.n_preempts,
            "n_requeues": self.n_requeues,
        }


@dataclasses.dataclass
class Journey:
    """One stitched request timeline: the attribution summary plus (for
    tail-kept requests) the full event detail, phase segments, hop chain,
    route-decision trail, and the global events (controller actions, SLO
    transitions, fault firings) that overlapped the request's lifetime."""

    req_id: object
    status: str
    t0: float
    t1: float
    summary: dict
    events: list
    segments: list
    hops: list
    globals_: list = dataclasses.field(default_factory=list)
    error: str | None = None
    events_dropped: int = 0

    @property
    def total_s(self) -> float:
        return self.summary["total_s"]

    @property
    def fracs(self) -> dict:
        return self.summary["fracs"]

    def as_dict(self) -> dict:
        return {
            "req": str(self.req_id), "status": self.status,
            "error": self.error,
            "t0": round(self.t0, 6), "t1": round(self.t1, 6),
            "summary": self.summary,
            "segments": [[p, round(a, 6), round(b, 6)]
                         for p, a, b in self.segments],
            "hops": list(self.hops),
            "events": list(self.events),
            "events_dropped": self.events_dropped,
            "globals": list(self.globals_),
        }

    @classmethod
    def stitch(cls, events, *, req_id=None, hops=(), globals_events=(),
               status: str | None = None, error: str | None = None
               ) -> "Journey":
        """Join a bag of journey-keyed event dicts into one causal
        timeline and compute the latency attribution. Events are ordered
        by ``(t, seq)`` (the blackbox satellite: ``seq`` disambiguates
        same-tick events), replayed through the same ``_Accum`` state
        machine the live recorder runs, and the in-flight global events
        are attached. This is the post-hoc path ``explain_request`` uses
        on a dumped ring; the live path produces identical summaries."""
        evs = sorted(events, key=lambda e: (float(e.get("t", 0.0)),
                                            int(e.get("seq", 0))))
        if not evs:
            raise ValueError("cannot stitch a journey from zero events")
        acc = _Accum()
        for ev in evs:
            acc.feed(ev)
        t1 = float(evs[-1].get("t", 0.0))
        if acc.status is None:
            acc.close(t1, status=status or "in_flight", error=error)
        t0 = acc.t0 if acc.t0 is not None else t1
        inflight = [g for g in globals_events
                    if t0 <= float(g.get("t", 0.0)) <= t1]
        return cls(
            req_id=req_id if req_id is not None else evs[0].get("req"),
            status=acc.status, t0=t0, t1=t1,
            summary=acc.summary(t1), events=evs,
            segments=list(acc.segments), hops=list(hops),
            globals_=inflight,
            error=error if error is not None else acc.error)

    def chrome_events(self, *, pid: int, tid: int) -> list[dict]:
        """Chrome trace-event rows for ONE journey: an X slice per phase
        segment on this journey's thread, plus an instant per hop."""
        rows = [{"name": "thread_name", "ph": "M", "ts": 0, "pid": pid,
                 "tid": tid, "args": {"name": f"req {self.req_id}"}}]
        for phase, a, b in self.segments:
            rows.append({"name": phase, "cat": "journey", "ph": "X",
                         "ts": a * 1e6, "dur": max(b - a, 0.0) * 1e6,
                         "pid": pid, "tid": tid,
                         "args": {"req": str(self.req_id)}})
        for hop in self.hops:
            if "t" in hop:
                rows.append({"name": f"hop:{hop['kind']}",
                             "cat": "journey", "ph": "i", "s": "t",
                             "ts": hop["t"] * 1e6, "pid": pid, "tid": tid,
                             "args": {"hop": hop["hop"],
                                      "where": hop.get("where")}})
        return rows


class _Pending:
    __slots__ = ("ctx", "accum", "events", "dropped", "attrs")

    def __init__(self, ctx: JourneyContext, attrs: dict):
        self.ctx = ctx
        self.accum = _Accum()
        self.events: list = []
        self.dropped = 0
        self.attrs = attrs


class JourneyRecorder:
    """Always-on, bounded journey recording (see module docstring).

    One recorder per serving plant: a standalone ``BatchEngine`` owns one;
    a ``Fleet`` owns one SHARED across its replicas so cross-replica
    requeues stay one journey. Same timebase as the host tracer
    (``time.monotonic``) so exported Chrome rows align; tests and the
    deterministic ``explain_request --chaos`` demo swap ``clock`` for a
    virtual step counter, which makes every timestamp — and therefore the
    whole report — reproducible byte-for-byte."""

    def __init__(self, *, clock=time.monotonic, keep: int = 256,
                 summary_cap: int = 1024, max_events: int = 256,
                 global_cap: int = 512, max_pending: int = 4096,
                 slowest_k: int = 16):
        self.clock = clock
        self.max_events = int(max_events)
        self.max_pending = int(max_pending)
        self.slowest_k = int(slowest_k)
        self._pending: dict = {}
        self.kept: collections.deque = collections.deque(maxlen=keep)
        self.summaries: collections.deque = collections.deque(
            maxlen=summary_cap)
        self._globals: collections.deque = collections.deque(
            maxlen=global_cap)
        self._slowest: list = []          # [(total_s, req_id, summary)]
        self._seq = 0
        self._metrics = Metrics(windowed=True)
        self.n_begun = 0
        self.n_finished = 0
        self.n_kept = 0
        self.n_events = 0
        self.n_event_drops = 0
        self.n_pending_drops = 0
        self.n_global_events = 0

    # -- recording ----------------------------------------------------------

    def _stamp(self, kind: str, fields: dict) -> dict:
        ev = {"t": round(self.clock(), 9), "seq": self._seq, "kind": kind}
        self._seq += 1
        ev.update(fields)
        return ev

    def begin(self, req_id, *, ctx: JourneyContext | None = None,
              phase: str = "queue", **attrs) -> JourneyContext | None:
        """Open a journey. ``phase`` names the opening wait bucket:
        ``"queue"`` for a direct engine submit, ``"route"`` for a fleet
        submit (the request waits for a placement decision first).
        Returns the context to attach to the ``Request`` (None when the
        pending table is full — counted, never silent)."""
        if req_id in self._pending:
            return self._pending[req_id].ctx
        if len(self._pending) >= self.max_pending:
            self.n_pending_drops += 1
            return None
        if ctx is None:
            ctx = JourneyContext(req_id=req_id)
        p = _Pending(ctx, dict(attrs))
        self._pending[req_id] = p
        self.n_begun += 1
        ev = self._stamp("submit", {"req": str(req_id), "phase": phase,
                                    **attrs})
        ctx.next_hop("submit", t=ev["t"])
        ev["hop"] = 0
        p.accum.feed(ev)
        p.events.append(ev)
        return ctx

    def event(self, req_id, kind: str, **fields) -> None:
        """Record one journey-keyed event for an in-flight request.
        Unknown ids are ignored (begin was dropped at the pending cap, or
        the request predates the recorder)."""
        p = self._pending.get(req_id)
        if p is None:
            return
        ev = self._stamp(kind, {"req": str(req_id), **fields})
        p.accum.feed(ev)
        self.n_events += 1
        if len(p.events) < self.max_events:
            p.events.append(ev)
        else:
            p.dropped += 1
            self.n_event_drops += 1

    def hop(self, req_id, kind: str, *, where=None, **fields) -> None:
        """A queue-to-queue move: allocate the next hop id on the
        request's context and record the event carrying it."""
        p = self._pending.get(req_id)
        if p is None:
            return
        t = round(self.clock(), 9)
        hop = p.ctx.next_hop(kind, where=where, t=t)
        ev = {"t": t, "seq": self._seq, "kind": kind, "req": str(req_id),
              "hop": hop, **({"replica": where} if where is not None
                             else {}), **fields}
        self._seq += 1
        p.accum.feed(ev)
        self.n_events += 1
        if len(p.events) < self.max_events:
            p.events.append(ev)
        else:
            p.dropped += 1
            self.n_event_drops += 1

    def global_event(self, kind: str, **fields) -> None:
        """Record a request-independent event (controller action, SLO
        transition, fault firing) into the bounded global ring; stitch
        attaches it to every journey whose lifetime overlaps it."""
        self._globals.append(self._stamp(kind, fields))
        self.n_global_events += 1

    def finish(self, req_id, *, status: str = "ok",
               error: str | None = None,
               keep: bool | None = None) -> Journey | None:
        """Close a journey: flush the accumulator, record the O(1)
        summary, and retain the full ``Journey`` detail when the caller's
        ``TailSampler`` verdict says so (or the journey is forensically
        interesting on its own: it failed or was displaced)."""
        p = self._pending.pop(req_id, None)
        if p is None:
            return None
        term = "finish" if status == "ok" else "fail"
        ev = self._stamp(term, {"req": str(req_id),
                                **({"error": error} if error else {})})
        t1 = ev["t"]          # ONE clock read: buckets flush exactly here
        p.accum.feed(ev)
        if len(p.events) < self.max_events:
            p.events.append(ev)
        else:
            p.dropped += 1
        summary = p.accum.summary(t1)
        summary["req"] = str(req_id)
        summary["status"] = status
        self.n_finished += 1
        self.summaries.append(summary)
        total = summary["total_s"]
        self._metrics.observe("journey_total_s", total)
        for b in BUCKETS:
            self._metrics.observe(f"journey_{b}_frac",
                                  summary["fracs"][b])
        self._note_slowest(total, req_id, summary)
        keep = bool(keep) or status != "ok" \
            or p.accum.n_requeues > 0 or p.accum.n_preempts > 0
        if not keep:
            return None
        t0 = p.accum.t0 if p.accum.t0 is not None else t1
        j = Journey(
            req_id=req_id, status=status, t0=t0, t1=t1, summary=summary,
            events=p.events, segments=list(p.accum.segments),
            hops=list(p.ctx.hops),
            globals_=[g for g in self._globals
                      if t0 <= float(g.get("t", 0.0)) <= t1],
            error=error if error is not None else p.accum.error,
            events_dropped=p.dropped)
        self.kept.append(j)
        self.n_kept += 1
        return j

    def _note_slowest(self, total: float, req_id, summary: dict) -> None:
        row = (total, str(req_id), summary)
        self._slowest.append(row)
        self._slowest.sort(key=lambda r: (-r[0], r[1]))
        del self._slowest[self.slowest_k:]

    # -- views --------------------------------------------------------------

    def lookup(self, req_id) -> Journey | None:
        """The kept journey for ``req_id`` (None when it was summarized
        away or never seen)."""
        for j in self.kept:
            if str(j.req_id) == str(req_id):
                return j
        return None

    def slowest(self, k: int | None = None) -> list[dict]:
        """Top-k finished requests by total latency, each with its
        dominant attribution bucket — the serve_top pane."""
        rows = self._slowest[:k if k is not None else self.slowest_k]
        return [{"req": rid, "total_s": round(total, 6),
                 "dominant": s["dominant"],
                 "frac": s["fracs"][s["dominant"]],
                 "status": s["status"], "requeues": s["n_requeues"],
                 "preempts": s["n_preempts"]}
                for total, rid, s in rows]

    def mean_fracs(self) -> dict:
        """Mean attribution fraction per bucket over the bounded summary
        deque — the cheap aggregate the serve_smoke stats feed carries."""
        if not self.summaries:
            return {b: 0.0 for b in BUCKETS}
        n = len(self.summaries)
        return {b: round(sum(s["fracs"][b] for s in self.summaries) / n, 9)
                for b in BUCKETS}

    def stats(self) -> dict:
        """JSON-able block for ``stats_snapshot``: counters, windowed
        per-bucket fraction percentiles, the mean attribution, and the
        slowest-journeys table."""
        windows: dict = {}
        for w_s, label in _WINDOWS:
            d: dict = {}
            for b in BUCKETS:
                w = self._metrics.window(f"journey_{b}_frac", w_s)
                if w:
                    d[f"{b}_frac"] = w
            wt = self._metrics.window("journey_total_s", w_s)
            if wt:
                d["total_s"] = wt
            windows[label] = d
        return {
            "begun": self.n_begun, "finished": self.n_finished,
            "in_flight": len(self._pending), "kept": len(self.kept),
            "event_drops": self.n_event_drops,
            "pending_drops": self.n_pending_drops,
            "windows": windows,
            "mean_fracs": self.mean_fracs(),
            "slowest": self.slowest(8),
        }

    def perfdb_sample(self) -> dict:
        """Flat journey metrics for the perf flight recorder:
        ``journey_{bucket}_frac_p99`` over the 5-minute window (mean
        fallback when the window is empty) plus volume counters."""
        out: dict = {"journey_finished": float(self.n_finished),
                     "journey_kept": float(len(self.kept))}
        means = self.mean_fracs()
        for b in BUCKETS:
            w = self._metrics.window(f"journey_{b}_frac", 300.0)
            out[f"journey_{b}_frac_p99"] = float(
                w["p99"] if w and w.get("p99") is not None else means[b])
        return out

    # -- dumps / chrome export ----------------------------------------------

    def dump(self) -> dict:
        """JSON-able forensic bundle: counters, every retained summary,
        the kept journeys with full event detail, and the global-event
        ring — what ``explain_request`` reconstructs from."""
        return {
            "counters": {
                "begun": self.n_begun, "finished": self.n_finished,
                "kept": self.n_kept, "event_drops": self.n_event_drops,
                "pending_drops": self.n_pending_drops,
                "global_events": self.n_global_events,
            },
            "summaries": list(self.summaries),
            "journeys": [j.as_dict() for j in self.kept],
            "globals": list(self._globals),
        }

    def dump_json(self, path: str) -> str:
        """Write ``dump()`` to ``path`` (dirs created); returns the
        path."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.dump(), f, default=str)
        return path

    def chrome_events(self, *, pid: int | None = None) -> list[dict]:
        """Chrome trace-event rows for every kept journey, on a dedicated
        ``journeys`` process row (pid offset past the per-rank host/device
        pids so merged traces never collide)."""
        if pid is None:
            try:
                import jax
                pid = 10_000 + jax.process_index()
            except Exception:
                pid = 10_000
        rows = [{"name": "process_name", "ph": "M", "ts": 0, "pid": pid,
                 "args": {"name": "journeys"}}]
        for tid, j in enumerate(self.kept):
            rows.extend(j.chrome_events(pid=pid, tid=tid))
        return rows

    def export_chrome_trace(self, dir: str) -> str:
        """Write ``{dir}/trace.p{rank}.journey.json`` — the name matches
        ``merge_chrome_traces``'s ``trace.p*.json`` glob, so journey rows
        merge next to the host-span (``trace.p{rank}.json``) and device
        (``trace.p{rank}.dev.json``) rows."""
        try:
            import jax
            rank = jax.process_index()
        except Exception:
            rank = 0
        os.makedirs(dir, exist_ok=True)
        path = os.path.join(dir, f"trace.p{rank}.journey.json")
        with open(path, "w") as f:
            json.dump({"traceEvents":
                       self.chrome_events(pid=10_000 + rank),
                       "displayTimeUnit": "ms"}, f, default=str)
        return path
