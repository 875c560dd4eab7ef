"""Safety checks over a traced kernel: the four hazard classes.

(a) **semaphore balance** — at kernel exit every semaphore's accumulated
    signals minus waits is exactly zero on every rank.  A nonzero residue
    either deadlocks a later invocation or silently credits it with stale
    signals (state leak across collective calls sharing a collective_id).
(b) **DMA completion** — every started copy's send-side and recv-side
    increments are fully retired by matching waits.  An undrained send
    means the source buffer can be reused while the DMA engine still reads
    it; an unawaited recv means nobody ordered themselves after arrival.
(c) **happens-before on buffers** — each destination-range access on the
    receiving rank is ordered after the wait that retired the covering
    recv increment (and source-range writes on the sender after the send
    drain): the classic DMA race.
(d) **global deadlock-freedom** — the cross-rank replay runs to
    completion; if it wedges, report each stuck wait and any wait-for
    cycle among the blocked ranks.
"""

from __future__ import annotations

import dataclasses

from triton_distributed_tpu.analysis import comm_graph, events, registry
from triton_distributed_tpu.analysis.events import _fmt_sem


CHECKS = ("deadlock", "sem-balance", "dma-completion", "buffer-race",
          "trace-error")


@dataclasses.dataclass(frozen=True)
class Violation:
    check: str          # one of CHECKS (or 'ast' from ast_checks)
    kernel: str
    world: int
    rank: int | None
    detail: str

    def __str__(self) -> str:
        where = f" rank {self.rank}" if self.rank is not None else ""
        return (f"[{self.check}] {self.kernel} world={self.world}{where}: "
                f"{self.detail}")


def check_kernel(name: str, world: int) -> list[Violation]:
    """Trace one registered kernel at one world size and run all checks."""
    entry = registry.get(name)
    spec = entry.build(world)
    try:
        trace = events.trace_kernel(spec, world)
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        return [Violation("trace-error", name, world, None,
                          f"{type(e).__name__}: {e}")]
    sim = comm_graph.simulate(trace.logs)
    return check_trace(trace, sim, kernel=name, world=world)


def check_trace(trace: events.TraceResult, sim: comm_graph.SimResult, *,
                kernel: str, world: int) -> list[Violation]:
    vs: list[Violation] = []

    # (d) deadlock-freedom — short-circuits the others: counts and
    # attribution are not meaningful for a wedged replay.
    if not sim.completed:
        for b in sim.blocked:
            vs.append(Violation("deadlock", kernel, world, b.rank,
                                comm_graph.describe_blocked(b)))
        for cyc in sim.cycles:
            vs.append(Violation(
                "deadlock", kernel, world, None,
                "wait-for cycle among ranks " +
                " -> ".join(map(str, cyc + [cyc[0]]))))
        return vs

    # (a) semaphore balance.
    for (rank, sem), n in sorted(sim.leftover.items()):
        vs.append(Violation(
            "sem-balance", kernel, world, rank,
            f"semaphore {_fmt_sem(sem)} exits with +{n} unconsumed "
            "signal(s)/byte(s) — leaks into the next invocation"))

    # (b) DMA completion.
    for rec in trace.dmas:
        for side, eid in (("send", rec.send_eid), ("recv", rec.recv_eid)):
            if eid is None:
                continue
            rem = sim.inc_remaining.get(eid, 0)
            if rem:
                sem = rec.send_sem if side == "send" else rec.recv_sem
                vs.append(Violation(
                    "dma-completion", kernel, world,
                    rec.src_rank if side == "send" else rec.dst_rank,
                    f"{rec.describe()}: {side}-side increment on "
                    f"{_fmt_sem(sem)} never fully awaited "
                    f"({rem} byte(s) outstanding) — missing "
                    f"wait_{side} / quiet"))

    # (c) happens-before on buffers.
    vs.extend(_race_check(trace, sim, kernel, world))
    return vs


def _overlap(a_lo, a_hi, b_lo, b_hi, a_runs=(), b_runs=()) -> bool:
    """Do two byte ranges of one buffer share a byte? ``*_runs``: a strided
    view's contiguous runs (``events.FakeRef.runs``), of which [lo, hi) is
    only the bounding box: two copies into different rows of a staging
    slot's two planes interleave without touching."""
    if not (a_lo < b_hi and b_lo < a_hi):
        return False
    if not a_runs and not b_runs:
        return True
    return any(x_lo < y_hi and y_lo < x_hi
               for x_lo, x_hi in a_runs or ((a_lo, a_hi),)
               for y_lo, y_hi in b_runs or ((b_lo, b_hi),))


def _avail_seq(sim: comm_graph.SimResult, eid: int | None,
               on_rank: int) -> int | None:
    """Seq (on ``on_rank``) of the last wait that consumed increment
    ``eid``; None if the increment was never fully retired there."""
    if eid is None or sim.inc_remaining.get(eid, 0):
        return None
    waits = [w for (w, _amt) in sim.consumption.get(eid, ())
             if w.rank == on_rank]
    return max(w.seq for w in waits) if waits else None


def _race_check(trace: events.TraceResult, sim: comm_graph.SimResult,
                kernel: str, world: int) -> list[Violation]:
    vs: list[Violation] = []
    for rec in trace.dmas:
        # Destination side: accesses to the written range on the receiving
        # rank must happen after the wait retiring the recv increment.
        # Remote arrivals are unordered against the whole receiver program,
        # so the hazard window is the entire prefix before that wait; a
        # local copy is issued by the consumer itself, so only accesses
        # between start and wait race it.
        avail = _avail_seq(sim, rec.recv_eid, rec.dst_rank)
        start = rec.start_seq if rec.kind == "local" else -1
        for ev in trace.logs[rec.dst_rank]:
            if ev.kind not in ("read", "write") or ev.dma == rec.did:
                continue
            if ev.buf != rec.dst_buf:
                continue
            if not _overlap(ev.lo, ev.hi, rec.dst_lo, rec.dst_hi, ev.runs,
                            rec.dst_runs):
                continue
            if ev.seq <= start:
                continue
            if avail is None or ev.seq < avail:
                vs.append(Violation(
                    "buffer-race", kernel, world, rec.dst_rank,
                    f"{ev.kind} of {ev.buf}[{ev.lo}:{ev.hi}] at event "
                    f"{ev.seq} is not ordered after the arrival wait of "
                    f"{rec.describe()}"
                    + ("" if avail is not None else
                       " (arrival is never awaited on the destination)")))
        # Source side (remote only): the sender must not overwrite the
        # source range before the send drain — write-after-read hazard
        # against the DMA engine's read.
        if rec.kind != "remote":
            continue
        savail = _avail_seq(sim, rec.send_eid, rec.src_rank)
        if savail is None:
            continue  # dma-completion already reports the missing drain
        for ev in trace.logs[rec.src_rank]:
            if ev.kind != "write" or ev.dma == rec.did:
                continue
            if ev.buf != rec.src_buf:
                continue
            if not _overlap(ev.lo, ev.hi, rec.src_lo, rec.src_hi, ev.runs,
                            rec.src_runs):
                continue
            if rec.start_seq < ev.seq < savail:
                vs.append(Violation(
                    "buffer-race", kernel, world, rec.src_rank,
                    f"write to {ev.buf}[{ev.lo}:{ev.hi}] at event {ev.seq} "
                    f"lands inside the in-flight window of "
                    f"{rec.describe()} (source reclaimed before its "
                    "wait_send)"))
    return vs


def check_kernel_worlds(name: str, worlds) -> list[Violation]:
    out: list[Violation] = []
    for w in worlds:
        out.extend(check_kernel(name, w))
    return out
