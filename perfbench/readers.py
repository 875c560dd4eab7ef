"""Shared arithmetic of the metric readers (``end_to_end/`` and
``layer_metrics/``). Each reader is a module with ``read(rec)``, where
``rec`` is ``perfbench.core.Records``; it returns one number, or None where
it finds nothing to read (the harness then leaves the metric out)."""

from __future__ import annotations

import statistics

from perfbench import stats


def ttft_ms(rec) -> list:
    """Time to first token of every request due in the window, counted
    from the time it was DUE. A request with no token by the end of the
    drain is given the whole wait until then. The standing requests, in
    flight before the window opened, have no wait to report."""
    return [((t.stamps[0] if t.stamps else rec.t_end) - t.due_t) * 1e3
            for t in rec.tracked if not t.standing]


def gaps_ms(rec) -> list:
    out = []
    for t in rec.tracked:
        out += stats.token_gaps(t.stamps, rec.t_open, rec.t_close)
    return [g * 1e3 for g in out]


def window_steps(rec, kind: str) -> list:
    return [s for s in rec.steps
            if s[2] == kind and rec.t_open <= s[1] < rec.t_close]


def step_ms(rec, kind: str):
    walls = [(s[1] - s[0]) * 1e3 for s in window_steps(rec, kind)]
    return statistics.median(walls) if walls else None


def percentile_or_none(values, p):
    return stats.percentile(values, p) if values else None


def trace_share(rec, pattern: str):
    """Device time of the operations whose name matches ``pattern`` over
    device busy time, from the reduced trace."""
    from perfbench import xplane

    if rec.trace is None or not rec.trace["busy_s"]:
        return None
    secs = xplane.seconds_matching(rec.trace["ops_s"], pattern)
    return 100.0 * secs / rec.trace["busy_s"] if secs else None
