"""Host-side span tracer: nested spans, ring buffer, Chrome-trace export.

The reference answers "where are the waits" by merging per-rank chrome
traces by hand (``group_profile``, utils.py:500); XProf answers it for
device time but says nothing about HOST structure — which request a step
belonged to, how long the scheduler deliberated, where TTFT was spent.
This tracer fills that gap:

- ``span(name, **attrs)`` — a nestable context manager recording
  ``time.monotonic()`` timestamps (the ONE clock of this module: the clock
  of ``Request.submit_t``, of the serving histograms and of the
  benchmark's window) into a per-process ring buffer (bounded: a serving
  loop traces indefinitely without growing).
- Recording is on while the tracer is ``enable()``d OR a profiler capture
  is live (``jax.profiler.TraceAnnotation.is_enabled()``: true between
  ``start_trace`` and ``stop_trace``). A capture therefore gets the
  program's spans for exactly the span the device trace covers, with no
  switch: every span enters a ``jax.profiler.TraceAnnotation`` scope that
  carries its attributes, so the host spans land INSIDE the XPlane
  timeline (``/host:CPU``), attributes as the event's stats, and line up
  with device activity.
- While the process-global tracer records, every garbage collection is a
  ``gc_pause`` span (``generation``, ``collected``): a ``gc.callbacks``
  hook that installs itself with the first recorded event and takes itself
  out at the first collection that finds recording off.
- ``instant(name)`` / ``async_begin``/``async_end`` — point events and
  non-nested (request-lifetime) intervals, Chrome ``i``/``b``/``e`` phases.
- ``export_chrome_trace(dir)`` — writes the ring buffer as Chrome
  trace-event JSON to ``{dir}/trace.p{process_index}.json``; each process
  writes its own file and ``merge_chrome_traces(dir)`` concatenates them
  into one Perfetto-loadable ``trace.merged.json`` (pid = process index),
  the cross-rank merge the reference does by hand.

Off (the default, and no capture live) a span site is an attribute check
and one call of ``is_enabled()`` returning a shared ``nullcontext`` —
about 0.1 us for the call and 0.4 us with the ``with`` statement around
it, cheap enough to leave call sites in the serving hot loop permanently.
Ring-buffer wraps are COUNTED (``Tracer.dropped``, module-level
``dropped_spans()``) and surfaced in the Chrome-export metadata and the
serving ``trace_dropped_spans`` gauge — a truncated trace is never
mistaken for a complete one.

``TailSampler`` is the always-on production sampling layer on top: every
request's lifecycle events buffer cheaply while in flight, and at finish
the trace is KEPT only when the request was head-sampled (a seeded,
deterministic fraction), ran slow (``mark_slow`` fires the moment any
single token exceeds ``slow_s``, so an in-flight straggler is already
kept when an SLO breach snapshot fires), or errored. Everything else is
dropped and counted — tail visibility at bounded cost.

``group_profile`` (the XProf capture context re-exported through
``runtime/utils.py``) lives here too: it creates the trace directory up
front and guards against nested/double ``start_trace`` (``jax.profiler``
raises on re-entry; the guard makes the inner context a no-op instead).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import glob
import json
import os
import random
import threading
import time
from typing import Any

import jax

# True between ``jax.profiler.start_trace`` and ``stop_trace``. Looked up
# once: where a jax lacks it, recording follows the ``enabled`` flag alone.
_capture_live = getattr(jax.profiler.TraceAnnotation, "is_enabled",
                        lambda: False)


@dataclasses.dataclass
class SpanRecord:
    """One completed span (or point/async event) in the ring buffer."""

    name: str
    t_start: float            # time.monotonic() seconds
    t_end: float              # == t_start for instant events
    depth: int                # nesting depth at entry (0 = top level)
    tid: int                  # host thread ident
    phase: str = "X"          # Chrome phase: X complete, i instant, b/e async
    async_id: Any = None      # correlation id for b/e pairs
    attrs: dict | None = None


class Tracer:
    """Per-process span recorder with a bounded ring buffer."""

    # Whether collections are recorded as ``gc_pause`` spans. A collection
    # is the PROCESS's, so the process-global tracer records them; an
    # isolated instance (the tests') holds what its owner recorded, exactly.
    gc_pauses = False

    def __init__(self, capacity: int = 1 << 16):
        self.enabled = False
        self._records: collections.deque[SpanRecord] = collections.deque(
            maxlen=capacity)
        self._local = threading.local()
        # Ring-wrap evictions since the last reset(): the deque drops the
        # oldest record silently, so the count lives here and surfaces as
        # the ``trace_dropped_spans`` metric and in the Chrome-export
        # summary — a truncated trace announces itself.
        self.dropped = 0
        self._gc_hooked = False
        self._gc_open = None      # (t0, annotation) of a collection running

    def _append(self, rec: SpanRecord) -> None:
        if (self._records.maxlen is not None
                and len(self._records) == self._records.maxlen):
            self.dropped += 1
        self._records.append(rec)

    def recording(self) -> bool:
        """Whether a span site records: the flag, or a live capture."""
        return self.enabled or _capture_live()

    # -- gc pauses ------------------------------------------------------------

    def _hook_gc(self) -> None:
        self._gc_hooked = True      # asked once, also where none is taken
        if self.gc_pauses:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` entry: one ``gc_pause`` span a collection while
        recording; out of the list at the first that finds it off."""
        if phase == "start":
            if not self.recording():
                self._gc_hooked = False
                gc.callbacks.remove(self._on_gc)
                return
            self._gc_open = (time.monotonic(), _annotate(
                "gc_pause", {"generation": info["generation"]}))
        elif self._gc_open is not None:
            t_end = time.monotonic()
            (t0, annotation), self._gc_open = self._gc_open, None
            attrs = {"generation": info["generation"],
                     "collected": info["collected"]}
            if annotation is not None:
                annotation.set_metadata(collected=info["collected"])
                annotation.__exit__(None, None, None)
            self._append(SpanRecord(
                name="gc_pause", t_start=t0, t_end=t_end,
                depth=len(self._stack()), tid=threading.get_ident(),
                attrs=attrs))

    # -- state --------------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def enable(self, capacity: int | None = None) -> None:
        if capacity is not None:
            self._records = collections.deque(self._records,
                                              maxlen=capacity)
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        self._records.clear()
        self._local = threading.local()
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._records)

    @property
    def records(self) -> list[SpanRecord]:
        return list(self._records)

    # -- recording ----------------------------------------------------------

    def span(self, name: str, **attrs):
        """Nestable timed scope. Returns a shared no-op context when not
        recording (an attribute check and ``is_enabled()`` on the hot
        path)."""
        if not (self.enabled or _capture_live()):
            return _NULL_CONTEXT
        return _SpanContext(self, name, attrs)

    def _event(self, name: str, phase: str, depth: int, async_id,
               attrs: dict) -> None:
        if not self._gc_hooked:
            self._hook_gc()
        now = time.monotonic()
        self._append(SpanRecord(
            name=name, t_start=now, t_end=now, depth=depth,
            tid=threading.get_ident(), phase=phase, async_id=async_id,
            attrs=attrs or None))

    def instant(self, name: str, **attrs) -> None:
        """Point event (Chrome ``i`` phase): preemptions, first tokens."""
        if self.recording():
            self._event(name, "i", len(self._stack()), None, attrs)

    def async_begin(self, name: str, async_id, **attrs) -> None:
        """Open a non-nested interval (Chrome async ``b``): request
        lifetimes that straddle many engine steps."""
        if self.recording():
            self._event(name, "b", 0, async_id, attrs)

    def async_end(self, name: str, async_id, **attrs) -> None:
        if self.recording():
            self._event(name, "e", 0, async_id, attrs)

    def between(self, t0: float, t1: float) -> list[SpanRecord]:
        """The records whose ``t_start`` lies in ``[t0, t1)``
        (``time.monotonic()`` seconds), oldest first: what a reader takes
        for a window it timed on the same clock. Spans are appended as they
        CLOSE, so the ring is ordered by ``t_end``: where ``dropped`` is
        not 0, a window is whole if the oldest record left closed before it
        opened."""
        return sorted((r for r in self._records if t0 <= r.t_start < t1),
                      key=lambda r: r.t_start)

    # -- export -------------------------------------------------------------

    def chrome_events(self) -> list[dict]:
        """Ring buffer as Chrome trace-event dicts (ts/dur in microseconds,
        pid = jax process index so merged multi-rank traces separate).
        Leads with ``M`` (metadata) events naming the process row
        ``rank N`` and each host thread — merged multi-rank traces show
        labeled rows, not bare pids."""
        try:
            pid = jax.process_index()
        except RuntimeError:
            pid = 0
        events: list[dict] = [{
            "name": "process_name", "ph": "M", "ts": 0, "pid": pid,
            "args": {"name": f"rank {pid}"},
        }]
        named_tids: set[int] = set()
        for r in self._records:
            tid = r.tid % (1 << 31)
            if tid not in named_tids:
                named_tids.add(tid)
                events.append({
                    "name": "thread_name", "ph": "M", "ts": 0, "pid": pid,
                    "tid": tid, "args": {"name": f"host thread {tid}"},
                })
        for r in self._records:
            ev: dict[str, Any] = {
                "name": r.name,
                "ph": r.phase,
                "ts": r.t_start * 1e6,
                "pid": pid,
                "tid": r.tid % (1 << 31),
            }
            if r.phase == "X":
                ev["dur"] = max(r.t_end - r.t_start, 0.0) * 1e6
            elif r.phase == "i":
                ev["s"] = "t"
            else:  # b / e
                ev["cat"] = "request"
                ev["id"] = str(r.async_id)
            if r.attrs:
                ev["args"] = {k: _jsonable(v) for k, v in r.attrs.items()}
            events.append(ev)
        return events

    def export_chrome_trace(self, dir: str) -> str:
        """Write ``{dir}/trace.p{process_index}.json`` and return its path."""
        os.makedirs(dir, exist_ok=True)
        try:
            pid = jax.process_index()
        except RuntimeError:
            pid = 0
        path = os.path.join(dir, f"trace.p{pid}.json")
        payload = {
            "traceEvents": self.chrome_events(),
            "displayTimeUnit": "ms",
            "metadata": {"process_index": pid, "wall_time": time.time(),
                         "recorded_spans": len(self._records),
                         "dropped_spans": self.dropped},
        }
        with open(path, "w") as f:
            json.dump(payload, f)
        return path


def _jsonable(v):
    return v if isinstance(v, (int, float, str, bool, type(None))) else str(v)


def _annotate(name: str, attrs: dict):
    """An entered ``TraceAnnotation`` carrying ``attrs`` (made plain: the
    profiler takes numbers and strings), or None with no live backend."""
    try:
        annotation = jax.profiler.TraceAnnotation(
            name, **{k: _jsonable(v) for k, v in attrs.items()})
        annotation.__enter__()
    except Exception:
        return None          # host timing only
    return annotation


class _SpanContext:
    """Class-based (generator-free) span context: ~2x cheaper to enter and
    exception-transparent."""

    __slots__ = ("_tracer", "_name", "_attrs", "_t0", "_depth",
                 "_annotation")

    def __init__(self, tracer: Tracer, name: str, attrs: dict):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._annotation = None

    def __enter__(self):
        tracer = self._tracer
        if not tracer._gc_hooked:
            tracer._hook_gc()
        stack = tracer._stack()
        self._depth = len(stack)
        stack.append(self._name)
        self._annotation = _annotate(self._name, self._attrs)
        self._t0 = time.monotonic()
        return self

    def set(self, **attrs):
        """Attach attributes discovered mid-span (e.g. counts); they reach
        the profile too (``set_metadata``)."""
        self._attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(
                **{k: _jsonable(v) for k, v in attrs.items()})
        return self

    def __exit__(self, exc_type, exc, tb):
        t_end = time.monotonic()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack()
        if stack and stack[-1] == self._name:
            stack.pop()
        self._tracer._append(SpanRecord(
            name=self._name, t_start=self._t0, t_end=t_end,
            depth=self._depth, tid=threading.get_ident(),
            attrs=self._attrs or None))
        return False


_NULL_CONTEXT = contextlib.nullcontext()

# The process-global tracer: module-level functions below are the public
# API; the class exists for tests that want an isolated instance.
_TRACER = Tracer()
_TRACER.gc_pauses = True


def get_tracer() -> Tracer:
    return _TRACER


def enable(capacity: int | None = None) -> None:
    _TRACER.enable(capacity)


def disable() -> None:
    _TRACER.disable()


def enabled() -> bool:
    """Whether a span site records now (the flag, or a live capture): what
    a caller asks before it builds an event's attributes."""
    return _TRACER.recording()


def reset() -> None:
    _TRACER.reset()


# The recording calls ARE the global tracer's methods: a site in the serving
# hot loop pays no second call for the indirection.
span = _TRACER.span
instant = _TRACER.instant
async_begin = _TRACER.async_begin
async_end = _TRACER.async_end


def export_chrome_trace(dir: str) -> str:
    return _TRACER.export_chrome_trace(dir)


def dropped_spans() -> int:
    """Ring-wrap evictions on the process-global tracer since reset()."""
    return _TRACER.dropped


@contextlib.contextmanager
def tracing(capacity: int | None = None):
    """Scoped enable/disable (restores the prior state of the flag)."""
    prior = _TRACER.enabled
    _TRACER.enable(capacity)
    try:
        yield _TRACER
    finally:
        _TRACER.enabled = prior


def merge_chrome_traces(dir: str, out_name: str = "trace.merged.json") -> str:
    """Concatenate every ``trace.p*.json`` under ``dir`` into one Chrome
    trace (events already carry distinct pids) — the reference's manual
    per-rank chrome-trace merge, as one call.

    ``ph:"M"`` process/thread metadata events (process_name, thread_name,
    sort indices) are deduplicated by (name, pid, tid, args): one rank
    contributing host + device + journey rows repeats the same metadata
    in each file, and Perfetto renders the duplicates as ghost tracks.
    First occurrence wins; non-metadata events pass through untouched and
    in file order."""
    events: list[dict] = []
    seen_meta: set = set()
    for path in sorted(glob.glob(os.path.join(dir, "trace.p*.json"))):
        with open(path) as f:
            for ev in json.load(f).get("traceEvents", []):
                if ev.get("ph") == "M":
                    key = (ev.get("name"), ev.get("pid"), ev.get("tid"),
                           json.dumps(ev.get("args", {}), sort_keys=True))
                    if key in seen_meta:
                        continue
                    seen_meta.add(key)
                events.append(ev)
    out = os.path.join(dir, out_name)
    with open(out, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return out


# ---------------------------------------------------------------------------
# Per-request tail sampling
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RequestTrace:
    """One request's buffered lifecycle events + the keep decision."""

    req_id: object
    t_begin: float                      # time.monotonic at begin()
    head_sampled: bool = False
    kept_reason: str | None = None      # "head" | "slow" | "error" | None
    attrs: dict = dataclasses.field(default_factory=dict)
    events: list = dataclasses.field(default_factory=list)
    n_event_drops: int = 0              # per-request buffer overflow

    def event(self, name: str, t: float, max_events: int, **attrs) -> None:
        if len(self.events) >= max_events:
            self.n_event_drops += 1
            return
        self.events.append({"t": round(t - self.t_begin, 6), "name": name,
                            **{k: _jsonable(v) for k, v in attrs.items()}})

    def as_dict(self) -> dict:
        return {"req_id": str(self.req_id),
                "head_sampled": self.head_sampled,
                "kept_reason": self.kept_reason,
                "attrs": {k: _jsonable(v) for k, v in self.attrs.items()},
                "events": list(self.events),
                "event_drops": self.n_event_drops}


class TailSampler:
    """Always-on per-request trace sampling: keep ALL slow/errored
    requests (the tail — the ones worth debugging) plus a deterministic
    ``head_frac`` of everything else, at bounded memory.

    ``head_frac``   fraction of requests kept unconditionally, decided at
                    ``begin()`` from a seeded RNG — deterministic over
                    submit order, so reruns sample the same requests.
    ``slow_s``      a request becomes tail-kept the moment any single
                    latency the engine reports (TTFT, one TBT gap, or the
                    final e2e) exceeds this. ``mark_slow`` makes the keep
                    IMMEDIATE, so a breach snapshot taken while the
                    straggler is still in flight already contains it.
    ``keep``        bounded ring of kept traces (oldest evicted+counted).
    ``max_events``/``max_pending`` per-request and in-flight caps — every
                    bound is explicit and every overflow is counted.
    """

    def __init__(self, *, head_frac: float = 0.05, slow_s: float | None
                 = 1.0, keep: int = 256, max_events: int = 64,
                 max_pending: int = 4096, seed: int = 0):
        if not 0.0 <= head_frac <= 1.0:
            raise ValueError(f"head_frac {head_frac} not in [0, 1]")
        self.head_frac = head_frac
        self.slow_s = slow_s
        self.max_events = max_events
        self.max_pending = max_pending
        self._rng = random.Random(seed)
        self._pending: dict[object, RequestTrace] = {}
        self.kept: collections.deque[RequestTrace] = collections.deque(
            maxlen=keep)
        self.n_begun = 0
        self.n_kept_head = 0
        self.n_kept_tail = 0
        self.n_dropped = 0          # finished un-kept (the sampled-out bulk)
        self.n_overflow = 0         # begins refused by the pending cap

    def begin(self, req_id, **attrs) -> None:
        if len(self._pending) >= self.max_pending:
            self.n_overflow += 1
            return
        self.n_begun += 1
        rt = RequestTrace(req_id=req_id, t_begin=time.monotonic(),
                          head_sampled=self._rng.random() < self.head_frac,
                          attrs=dict(attrs))
        self._pending[req_id] = rt

    def event(self, req_id, name: str, **attrs) -> None:
        rt = self._pending.get(req_id)
        if rt is not None:
            rt.event(name, time.monotonic(), self.max_events, **attrs)

    def _keep(self, rt: RequestTrace, reason: str) -> None:
        if rt.kept_reason is None:
            rt.kept_reason = reason
            if reason == "head":
                self.n_kept_head += 1
            else:
                self.n_kept_tail += 1
            self.kept.append(rt)

    def mark_slow(self, req_id, **attrs) -> None:
        """Tail-keep an IN-FLIGHT request (e.g. one token gap already blew
        ``slow_s``) so breach-time snapshots see the offender now."""
        rt = self._pending.get(req_id)
        if rt is not None:
            rt.attrs.update(attrs)
            self._keep(rt, "slow")

    def finish(self, req_id, *, latency_s: float | None = None,
               error: str | None = None, **attrs) -> bool:
        """Close a request and decide; returns True when the trace was
        kept (head sample, slow, or errored)."""
        rt = self._pending.pop(req_id, None)
        if rt is None:
            return False
        rt.attrs.update(attrs)
        if latency_s is not None:
            rt.attrs["latency_s"] = round(latency_s, 6)
        if error is not None:
            rt.attrs["error"] = error
            self._keep(rt, "error")
        elif (self.slow_s is not None and latency_s is not None
                and latency_s > self.slow_s):
            self._keep(rt, "slow")
        elif rt.head_sampled:
            self._keep(rt, "head")
        if rt.kept_reason is None:
            self.n_dropped += 1
        return rt.kept_reason is not None

    @property
    def n_pending(self) -> int:
        return len(self._pending)

    def stats(self) -> dict:
        return {"begun": self.n_begun, "pending": self.n_pending,
                "kept_head": self.n_kept_head,
                "kept_tail": self.n_kept_tail, "dropped": self.n_dropped,
                "overflow": self.n_overflow, "retained": len(self.kept)}


# ---------------------------------------------------------------------------
# XProf capture context (the group_profile implementation)
# ---------------------------------------------------------------------------

_PROFILE_ACTIVE = False


@contextlib.contextmanager
def group_profile(name: str = "trace", *, enabled: bool = True,
                  dir: str = "/tmp/tdtpu_trace"):
    """Profiling context (analog of reference ``group_profile``
    utils.py:500).

    The reference merges per-rank chrome traces by hand; on TPU
    ``jax.profiler`` captures every local device into one XPlane trace, so
    the cross-rank merge reduces to each process writing
    ``{dir}/{name}/p{process_index}``, viewable together in XProf/Perfetto.

    Hardened over the seed version: the trace directory is created up
    front (``start_trace`` assumes it exists), and nested/double entry is
    guarded — ``jax.profiler.start_trace`` raises on re-entry, so an inner
    ``group_profile`` (e.g. bench's ``TDT_BENCH_PROFILE`` around a kernel
    that also profiles itself) becomes a no-op scope instead of an error.
    """
    global _PROFILE_ACTIVE
    if not enabled or _PROFILE_ACTIVE:
        yield
        return
    try:
        pid = jax.process_index()
    except RuntimeError:
        pid = 0
    path = os.path.join(dir, name, f"p{pid}")
    os.makedirs(path, exist_ok=True)
    jax.profiler.start_trace(path)
    _PROFILE_ACTIVE = True
    try:
        yield
    finally:
        _PROFILE_ACTIVE = False
        jax.profiler.stop_trace()
