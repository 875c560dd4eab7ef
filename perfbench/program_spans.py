"""The program's own spans of the traced span: the host's turn from inside.

Beside ``system.py`` this is the one module that touches the program under
test, and the one place a reader reaches its tracer:
``triton_distributed_tpu.obs.trace.get_tracer()``. That tracer is
process-global (a ring of records in the program's module, not a part of
the fleet), so it outlives ``Served.close()`` and is read after the run
like every other record. It records whenever a profiler capture is live,
on ``time.monotonic()``: a ``--trace 1`` run holds the program's spans of
exactly ``rec.trace["host_window"]``, and nothing here switches anything
on. A program without such spans (an older commit) leaves every reader of
this file with nothing to read: they return None and do not raise.

The spans (named by the program; ``BatchEngine.step``'s docstring):
``fleet.step`` holds ``fleet.route`` and each replica's ``engine.step``,
which holds ``engine.admit``, ``engine.blocks``, ``engine.observe``,
``engine.dispatch``, the WAIT for a step's tokens (``decode_step`` /
``mixed_step``) and ``engine.retire``; ``gc_pause`` is one collection.
The host's turn of a step is its ``fleet.step`` less the waits inside it.
"""

from __future__ import annotations

import bisect
import statistics

from perfbench import stats

STEP = "fleet.step"
WAITS = ("decode_step", "mixed_step")
DISPATCH = "engine.dispatch"
OBSERVE = "engine.observe"
PHASES = ("fleet.route", "engine.step", "engine.admit", "engine.blocks",
          OBSERVE, DISPATCH, *WAITS, "engine.retire", "gc_pause")
KEY = "program_spans"          # where ``rec.trace`` keeps what was read


def spans(rec, tracer=None):
    """The tracer's completed spans that began inside the traced span,
    oldest first; cached on ``rec.trace``. None where the run has no trace,
    the program's tracer cannot give a window's records, or its ring
    wrapped inside the span (records were dropped and the oldest one left
    closed after the span opened: the span's first records may be gone).
    The first call prints the ``host_turn`` phase line."""
    if rec.trace is None or not rec.trace.get("host_window"):
        return None
    if KEY in rec.trace:
        return rec.trace[KEY]
    if tracer is None:
        from triton_distributed_tpu.obs import trace

        tracer = trace.get_tracer()
    found = None
    between = getattr(tracer, "between", None)
    if between is not None:
        t0, t1 = rec.trace["host_window"]
        oldest = next(iter(tracer.records), None)
        wrapped = bool(tracer.dropped) and oldest is not None \
            and oldest.t_end >= t0
        if not wrapped:
            found = [r for r in between(t0, t1) if r.phase == "X"]
    rec.trace[KEY] = found
    if found:
        from perfbench import core

        core.say("host_turn", **summary(rec, found))
    return found


def ms(r) -> float:
    return (r.t_end - r.t_start) * 1e3


def steps(records) -> list:
    """One entry a ``fleet.step`` span, oldest first: its length, the
    milliseconds of each phase inside it (same thread, begun and ended
    inside it), and whether it moved a step (dispatched one or waited for
    one's tokens: an idle call is no turn)."""
    by_tid: dict = {}
    for r in records:
        if r.name != STEP:
            by_tid.setdefault(r.tid, []).append(r)
    starts = {tid: [r.t_start for r in rs] for tid, rs in by_tid.items()}
    out = []
    for f in records:
        if f.name != STEP:
            continue
        inner = by_tid.get(f.tid, [])
        lo = bisect.bisect_left(starts.get(f.tid, []), f.t_start)
        phases: dict = {}
        for r in inner[lo:]:
            if r.t_start >= f.t_end:
                break
            if r.t_end <= f.t_end:
                phases[r.name] = phases.get(r.name, 0.0) + ms(r)
        wait = sum(phases.get(w, 0.0) for w in WAITS)
        out.append({"ms": ms(f), "wait_ms": wait, "turn_ms": ms(f) - wait,
                    "phases": phases, "t_start": f.t_start,
                    "moved": DISPATCH in phases
                    or any(w in phases for w in WAITS)})
    return out


def turns_ms(rec) -> list:
    """The host's turn of every ``fleet.step`` of the span that moved a
    step: the span's length less the waits for tokens inside it."""
    records = spans(rec)
    return [s["turn_ms"] for s in steps(records) if s["moved"]] \
        if records else []


def lengths_ms(rec, name: str) -> list:
    records = spans(rec)
    return [ms(r) for r in records if r.name == name] if records else []


def per_step_ms(rec, name: str) -> list:
    """Milliseconds of ``name`` inside each ``fleet.step`` that moved a
    step (summed where a fleet steps several replicas)."""
    records = spans(rec)
    return [s["phases"].get(name, 0.0) for s in steps(records)
            if s["moved"]] if records else []


def median_or_none(values):
    return statistics.median(values) if values else None


def spread(values) -> dict:
    return {"n": len(values), "median_ms": statistics.median(values),
            "p99_ms": stats.percentile(values, 99), "max_ms": max(values)}


def summary(rec, records) -> dict:
    """What the ``host_turn`` line says: count, median, 99th percentile and
    maximum of every span by name, of ``fleet.step``'s own remainder (what
    no phase inside it covers: health, drain, the replica loop, the fleet's
    observers) and of the turn; the phases of the span's longest
    ``fleet.step``; and the outside measurement of the same calls, the
    median wall of the benchmark's ``served.step()`` calls of the span."""
    by_name: dict = {}
    for r in records:
        by_name.setdefault(r.name, []).append(ms(r))
    out = {name: spread(by_name[name]) for name in (STEP, *PHASES)
           if name in by_name}
    per_step = steps(records)
    if per_step:
        # engine.step covers its own phases; gc_pause lies inside whatever
        # it interrupted: neither is taken out twice.
        out["fleet.step.remainder"] = spread([
            s["ms"] - s["phases"].get("fleet.route", 0.0)
            - s["phases"].get("engine.step", 0.0) for s in per_step])
        moved = [s["turn_ms"] for s in per_step if s["moved"]]
        if moved:
            out["host_turn"] = spread(moved)
        longest = max(per_step, key=lambda s: s["ms"])
        out["longest_fleet_step"] = {
            "ms": longest["ms"], "wait_ms": longest["wait_ms"],
            "at_s": longest["t_start"] - rec.t_open,
            "phases": longest["phases"]}
    t0, t1 = rec.trace["host_window"]
    walls = [(s[1] - s[0]) * 1e3 for s in rec.steps if t0 <= s[0] < t1]
    if walls:
        out["bench.step"] = spread(walls)
    return out
