#!/usr/bin/env python3
"""python3 tools/host_turn_probe.py --workload <cell> --seed <n> [--record 0|1] [--gc 0|1] [--witness 0|1]

A probe beside the benchmark, not a benchmark run: one untraced run of a
cell (``perfbench.core.run_cell``, ``--trace 0``) with the program's tracer
enabled for the WHOLE run (``--record 1``: ``tamper`` calls
``obs.trace.enable(1 << 18)`` before warm-up) or left off (``--record 0``,
the other side of a pair). Two uses:

- what recording costs: pairs of ``--record 1`` / ``--record 0`` on one
  seed, end-to-end metrics side by side;
- the stall hunt: with the window recorded, every ``fleet.step`` of the
  window over ``--stall-ms`` is printed with the phases inside it, so a
  step of seconds names what held it (the ``decode_step`` / ``mixed_step``
  wait: the runtime or the machine; ``gc_pause``; a host phase), and every
  ``gc_pause`` over a tenth of that with its generation. ``--gc 1`` times
  the collections alone (a ``gc.callbacks`` entry of the probe's own, no
  record kept by the program): recording changes WHEN a full collection
  falls, so a stall of an unrecorded run is held against this list.
  ``--witness 1`` tells a stall of the machine from one of this process:
  another process (plain Python, no JAX, no chip) and another thread of
  this one each sleep a millisecond at a time and keep every gap between
  two wakings over a quarter of ``--stall-ms``, on ``time.monotonic()``,
  which processes share. A step over the limit then says the longest gap
  of each that overlaps it: both stood still, the machine froze; the
  thread alone, something in this process (the interpreter's lock held,
  the process stopped); neither, the loop's own thread or the device.

Either way the line also carries every program counter that moved while
the window was driven (``counters``: the step kinds, and of the mixed
step's plan ``prefill_rows_filled``, ``prefill_rows_extra``,
``prefill_rows_deferred``, ``mixed_step_tokens``, which no reader of the
benchmark takes), so ``--record 0`` is also the plain run with the
program's own account of it.

Prints the run's own lines, then one ``{"probe": "host_turn", ...}`` line.
Run from the root of a checkout, on the chip (it refuses a CPU as the
benchmark does).
"""

import argparse
import gc
import json
import os
import subprocess
import sys
import threading
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


WITNESS = """
import os, sys, time
limit, last, parent = float(sys.argv[1]), time.monotonic(), os.getppid()
while os.getppid() == parent:       # ends with the probe, however that ends
    time.sleep(0.001)
    now = time.monotonic()
    if now - last > limit:
        print(last, now, flush=True)
    last = now
"""


class Witnesses:
    """Another process and another thread, each waking every millisecond
    and keeping the ``(t0, t1)`` of every gap over ``limit_s``."""

    def __init__(self, limit_s: float):
        self.limit_s = limit_s
        self.thread_gaps: list = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._process = subprocess.Popen(
            [sys.executable, "-c", WITNESS, str(limit_s)],
            stdout=subprocess.PIPE, text=True)
        self._thread.start()

    def _watch(self):
        last = time.monotonic()
        while not self._stop.wait(0.001):
            now = time.monotonic()
            if now - last > self.limit_s:
                self.thread_gaps.append((last, now))
            last = now

    def close(self) -> dict:
        """Stops both; every gap kept, by witness."""
        self._stop.set()
        self._thread.join(timeout=5)
        self._process.terminate()
        out, _ = self._process.communicate(timeout=5)
        return {"thread": self.thread_gaps,
                "process": [tuple(map(float, ln.split()))
                            for ln in out.splitlines()]}


def main(argv=None) -> int:
    from perfbench import core, program_spans
    from triton_distributed_tpu.obs import trace

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--record", type=int, choices=(0, 1), default=1)
    ap.add_argument("--gc", type=int, choices=(0, 1), default=0)
    ap.add_argument("--witness", type=int, choices=(0, 1), default=0)
    ap.add_argument("--stall-ms", type=float, default=100.0)
    args = ap.parse_args(argv)

    collections, began = [], []

    def on_gc(phase, info):
        if phase == "start":
            began.append(time.monotonic())
        elif began:
            t0 = began.pop()
            if (time.monotonic() - t0) * 1e3 > args.stall_ms / 10:
                collections.append((t0, time.monotonic(), info["generation"]))

    if args.gc:
        gc.callbacks.append(on_gc)

    driven = {}
    drive = core.drive

    def kept_drive(served, *a, **kw):
        witnesses = Witnesses(args.stall_ms / 4e3) if args.witness else None
        counters = served.be.metrics.counters
        at_open = dict(counters)
        try:
            driven.update(drive(served, *a, **kw))
        finally:
            if witnesses is not None:
                driven["gaps"] = witnesses.close()
        # the program's counters over the drive (the window and its drain)
        driven["counters"] = {
            k: n - at_open.get(k, 0.0) for k, n in sorted(counters.items())
            if n != at_open.get(k, 0.0)}
        return driven

    core.drive = kept_drive
    try:
        result = core.run_cell(
            args.workload, args.seed, args.seconds, 0,
            t_start=time.monotonic(),
            tamper=(lambda served: trace.enable(1 << 18)) if args.record
            else None)
    except core.BenchFailure as e:
        sys.stderr.write(f"host_turn_probe: {e}\n")
        return 2
    finally:
        core.drive = drive
        trace.disable()
        if args.gc:
            gc.callbacks.remove(on_gc)

    walls = [(s[1] - s[0]) * 1e3 for s in driven["steps"]]
    gaps = driven.get("gaps", {})

    def longest_gap_ms(witness, t0, t1):
        return max(((b - a) * 1e3 for a, b in gaps[witness]
                    if a < t1 and b > t0), default=0.0)

    out = {"probe": "host_turn", "workload": args.workload,
           "seed": args.seed, "record": args.record,
           "correct": result["correct"], "failed": result["failed"],
           "metrics": {k: v["value"] for k, v in result["metrics"].items()},
           "longest_step_ms": max(walls, default=0.0),
           "counters": driven["counters"],
           "steps_over_stall": [
               {"at_s": s[0] - driven["t_open"], "ms": (s[1] - s[0]) * 1e3,
                "kind": s[2],
                **{f"{w}_gap_ms": longest_gap_ms(w, s[0], s[1]) for w in gaps}}
               for s in driven["steps"]
               if (s[1] - s[0]) * 1e3 > args.stall_ms]}
    if gaps:
        # every gap of the window, a step's or not: what the witnesses saw
        # while the loop saw nothing
        out["gaps"] = {
            w: [{"at_s": a - driven["t_open"], "ms": (b - a) * 1e3}
                for a, b in kept
                if driven["t_open"] <= a < driven["t_close"]]
            for w, kept in gaps.items()}
    if args.gc:
        out["collections"] = [
            {"at_s": t0 - driven["t_open"], "ms": (t1 - t0) * 1e3,
             "generation": gen} for t0, t1, gen in collections
            if driven["t_open"] <= t0 < driven["t_close"]]
    if args.record:
        # The window as a reader of a traced run would see its span: the
        # same summary (``program_spans.summary``), over all of it.
        tracer = trace.get_tracer()
        t_open, t_close = driven["t_open"], driven["t_close"]
        window = types.SimpleNamespace(
            t_open=t_open, steps=driven["steps"],
            trace={"host_window": (t_open, t_close)})
        records = [r for r in tracer.between(t_open, t_close)
                   if r.phase == "X"]
        out.update(
            records=len(tracer), dropped=tracer.dropped,
            spans=program_spans.summary(window, records),
            stalls=[{"at_s": s["t_start"] - t_open, "ms": s["ms"],
                     "wait_ms": s["wait_ms"], "phases": s["phases"]}
                    for s in program_spans.steps(records)
                    if s["ms"] > args.stall_ms],
            pauses=[{"at_s": r.t_start - t_open,
                     "ms": program_spans.ms(r), **r.attrs}
                    for r in records if r.name == "gc_pause"
                    and program_spans.ms(r) > args.stall_ms / 10])
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
