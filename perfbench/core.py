"""One run of one cell: set-up, the measured window, the comparison with the
plain reference, the result line.

Driven by data: the cell, its configuration, its traffic mix and the
metrics it reports are found by name from ``BENCHMARK.json`` and the files
under ``perfbench/`` (see README.md); what the model is, is its family's
(``families/``). No cell's, model's or metric's name appears in this file.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import sys
import time


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WARM_OUTPUT = 4            # tokens each warm-up request generates
# What the reference reads, where the cell's file states no "sample":
# finished requests compared, and prompt + served tokens over all of them.
SAMPLE = {"requests": 3, "token_budget": 9000}
KV_SAMPLE_EVERY = 8        # steps between readings of the pool's live share
TRACE_SPAN_S = 3.0         # the traced span is the end of the window: the
                           # seconds that writing the trace takes then fall
                           # into the drain


class BenchFailure(RuntimeError):
    """The run cannot give a result (no chip, a retrace, a broken cell)."""


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def load_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, its configuration, its traffic parameters (the
    mix's file, then ``cells/<workload>.json`` laid over it), the limits of
    ``correct`` and the sample it reads (laid over the defaults the same
    way) and the metrics it reports. ``root`` holds ``BENCHMARK.json``."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_file = configs[cell["config"]]["file"]
    cfg = load_json(root, cfg_file)
    bench_dir = os.path.dirname(os.path.dirname(cfg_file))
    traffic = load_json(root, bench_dir, "traffic", cell["traffic"] + ".json")
    cell_file = os.path.join(root, bench_dir, "cells", workload + ".json")
    limits, sample = {}, dict(SAMPLE)
    if os.path.exists(cell_file):
        with open(cell_file) as f:
            extra = json.load(f)
        traffic.update(extra.get("traffic", {}))
        limits = extra.get("limits", {})
        sample.update(extra.get("sample", {}))

    def reported(group):
        return [m for m in bench[group]
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "config": cfg, "traffic": traffic,
            "limits": limits, "sample": sample,
            "end_to_end": reported("end_to_end"),
            "per_layer": reported("per_layer")}


@dataclasses.dataclass
class Tracked:
    planned: object
    req: object
    due_t: float
    submit_t: float
    stamps: list = dataclasses.field(default_factory=list)
    done_t: float | None = None
    standing: bool = False   # in flight before the window opened: no wait
                             # for a first token is taken from it


@dataclasses.dataclass
class Records:
    """What a run hands to the metric readers."""

    t_open: float
    t_close: float
    t_end: float
    setup_s: float
    tracked: list            # every request: the standing ones, then those
                             # submitted in the window
    steps: list              # per fleet.step(): (t0, t1, kind, decode rows,
                             # tokens emitted, context read by those rows)
    kv_live: list            # sampled live share of the pool
    counters: dict           # the program's counters, change over the window
    queue_wait_s: list       # its queue_wait_s samples of the window
    sizes: object            # the configuration's, as its family made them
    family: object           # the family's module: its counts of operations
                             # and bytes are family.<count>(sizes, ...)
    n_slots: int
    n_chips: int
    device_kind: str
    trace: dict | None = None     # reduced device trace (--trace 1)

    @property
    def finished(self):
        return [t for t in self.tracked if t.done_t is not None
                and t.req.status != "failed"]


def stamp(served, plan, active, t1, t_open):
    """After a step: stamp the tokens each active request has grown by.
    Returns the requests still active, the tokens emitted and the context
    the emitting rows had read."""
    tokens = context = 0
    still = []
    for tr in active:
        grown = len(tr.req.output) - len(tr.stamps)
        if grown > 0:
            context += len(tr.planned.prompt) + len(tr.stamps)
            tr.stamps.extend([t1] * grown)
            tokens += grown
        if served.done(tr.req):
            tr.done_t = t1
            plan.on_finish(tr.planned, max(0.0, t1 - t_open))
        else:
            still.append(tr)
    return still, tokens, context


def open_standing(served, plan, *, clock=time.monotonic) -> list:
    """Set-up's last part: submit the plan's standing requests and step
    until each has its first token (its context is prefilled), so that the
    window opens on rows that decode. A standing request that finishes
    here already is told to the plan as finished at the opening."""
    tracked = []
    for p in plan.standing():
        now = clock()
        tracked.append(Tracked(p, served.submit(p.prompt, p.max_new_tokens),
                               now, now, standing=True))
    active = list(tracked)
    for _ in range(100_000):
        if all(tr.stamps for tr in active):
            break
        served.step()
        active, _, _ = stamp(served, plan, active, clock(), float("inf"))
    bad = [tr.req.status for tr in tracked
           if not tr.stamps or tr.req.status == "failed"]
    if bad:
        raise BenchFailure(f"standing requests did not start: {bad}")
    return tracked


def drive(served, plan, seconds: float, drain_limit_s: float, *,
          standing=(), clock=time.monotonic, sleep=time.sleep, annotate=None,
          on_tick=None):
    """The measured loop. One thread: submit what is due, step the fleet,
    stamp tokens by the growth of each request's output. After the close
    it steps on only until every request has its first token (or the drain
    limit): what is still decoding then is counted by its status."""
    ann = annotate or (lambda name: contextlib.nullcontext())
    tracked = list(standing)
    active = [tr for tr in tracked if tr.done_t is None]
    steps, kv_live = [], []
    before = c1 = served.counters()
    t_open = clock()
    t_close = t_open + seconds
    closed = False
    n_step = 0
    while True:
        now = clock()
        if not closed:
            # What fell due while the last step ran is still sent, also
            # when the window has closed meanwhile: it was due inside it.
            with ann("bench.submit"):
                for p in plan.take_due(min(now - t_open, seconds)):
                    req = served.submit(p.prompt, p.max_new_tokens)
                    tr = Tracked(p, req, t_open + p.due_s, clock())
                    tracked.append(tr)
                    active.append(tr)
            if now >= t_close:
                closed = True
                plan.close()
        if closed and (all(tr.stamps for tr in active)
                       or now >= t_close + drain_limit_s):
            break
        if not active:
            nxt = plan.next_due_s()
            wait = (t_close if nxt is None else t_open + nxt) - clock()
            if wait > 0:
                with ann("bench.wait_due"):
                    sleep(min(wait, 0.05))
            continue
        c0 = c1
        t0 = clock()
        with ann("bench.step"):
            served.step()
        t1 = clock()
        c1 = served.counters()
        active, tokens, context = stamp(served, plan, active, t1, t_open)
        kind = ("mixed" if c1["prefill_steps"] > c0["prefill_steps"] else
                "decode" if c1["decode_steps"] > c0["decode_steps"] else
                "idle")
        steps.append((t0, t1, kind,
                      int(c1["decode_rows"] - c0["decode_rows"]), tokens,
                      context))
        n_step += 1
        if n_step % KV_SAMPLE_EVERY == 0:
            kv_live.append(served.kv_live_share())
        if on_tick is not None:
            on_tick(t1 - t_open)
    t_end = clock()
    after = served.counters()
    return {"t_open": t_open, "t_close": t_close, "t_end": t_end,
            "tracked": tracked, "active": active, "steps": steps,
            "kv_live": kv_live,
            "counters": {k: after[k] - before[k] for k in after}}


def warm_up(served, sizes, seed: int, chunk: int, block: int) -> dict:
    """Serve a few seeded requests so that every program the window can
    drive has run before it opens: the mixed step, the decode step, and the
    pool's block copy, which the prefix cache runs when a prompt shares part
    of a block with a finished one (at a 152k vocabulary a first token
    meets a cached one in about one run in three, so it cannot be left to
    chance)."""
    from perfbench import lengths

    rng = lengths.rng_for(seed, 9)
    first = {}

    def serve(prompts):
        reqs = [served.submit(p, WARM_OUTPUT) for p in prompts]
        for _ in range(100_000):
            c0 = served.counters()
            t0 = time.monotonic()
            served.step()
            dt = time.monotonic() - t0
            c1 = served.counters()
            for kind, key in (("mixed", "prefill_steps"),
                              ("decode", "decode_steps")):
                if kind not in first and c1[key] > c0[key]:
                    first[kind] = dt
            if all(served.done(r) for r in reqs):
                break
        if not all(served.ok(r) for r in reqs):
            raise BenchFailure(f"warm-up requests did not finish: "
                               f"{[r.status for r in reqs]}")

    long = lengths.token_ids(rng, 2 * chunk + chunk // 2, sizes.vocab_size)
    serve([long, lengths.token_ids(rng, chunk // 2, sizes.vocab_size)])
    shared = block + max(1, block // 2)
    serve([long[:shared] + lengths.token_ids(rng, chunk, sizes.vocab_size)])
    if set(first) != {"mixed", "decode"}:
        raise BenchFailure(f"warm-up did not run both steps: {first}")
    return first


class CompileCounter:
    """Counts backend compilations (JAX's monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1

    def close(self):
        from jax._src import monitoring

        with contextlib.suppress(Exception):
            monitoring._unregister_event_duration_listener_by_callback(
                self._on)


def pick_sample(finished, seed: int, requests: int, token_budget: int,
                arrived: int = 0) -> "list[int]":
    """The finished requests the reference reads, as indices into
    ``finished``: the longest, then others drawn from the seed, inside the
    token budget. Where the cell's ``"sample"`` states ``"arrived": n``, the
    ``n`` places after the longest go to requests that ARRIVED in the window
    (``standing`` false), in the order the seed drew, where any finished:
    such a request was prefilled by the window's own mixed steps and serves
    its whole answer, so every run reads that path and not only the
    standing population set-up prefilled."""
    from perfbench import lengths

    if not finished:
        return []

    def size(i):
        return len(finished[i].planned.prompt) + len(finished[i].req.output)

    order = sorted(range(len(finished)), key=lambda i: -size(i))
    rest = order[1:]
    lengths.rng_for(seed, 7).shuffle(rest)
    picked, budget = [], token_budget

    def take(candidates, places):
        nonlocal budget
        for i in candidates:
            if len(picked) >= places:
                break
            if i in picked or (picked and size(i) > budget):
                continue
            picked.append(i)
            budget -= size(i)

    take(order[:1], min(1, requests))
    take([i for i in rest if not finished[i].standing],
         min(requests, 1 + arrived))
    take(rest, requests)
    return picked


def set_up(spec: dict, seed: int, *, t_start: float, allow_cpu: bool = False,
           engine_overrides: dict | None = None, tamper=None):
    """Everything before the window except the traffic plan: the device
    check, the compile cache, the configuration's family, weights from the
    seed, ``Fleet.build``, and the warm-up of every program the window can
    drive."""
    phases: dict = {"start_s": time.monotonic() - t_start}
    import jax

    from perfbench import families, peaks, system

    phases["jax_import_s"] = time.monotonic() - t_start
    cell, cfg = spec["cell"], spec["config"]
    workload = cell["name"]
    devices = jax.devices()
    phases["devices_s"] = time.monotonic() - t_start
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if not allow_cpu:
        if dev["platform"] != "tpu" or len(devices) < cell["chips"]:
            raise BenchFailure(
                f"{workload} needs {cell['chips']} TPU chip(s); JAX found "
                f"{len(devices)} x {dev['platform']!r}. It does not run on "
                f"anything else.")
        peaks.peaks_for(dev["kind"])        # an unknown chip is an error
    devices = devices[:cell["chips"]]
    dev["count"] = len(devices)
    cache_dir = system.enable_compile_cache()
    say("device", compile_cache_dir=cache_dir, **dev)

    family = families.load_family(cfg)
    sizes = family.sizes(cfg)
    compiles = CompileCounter()
    phases["import_s"] = time.monotonic() - t_start
    served = system.Served(cfg, family, sizes, seed, devices,
                           engine_overrides=engine_overrides, phases=phases)
    if tamper is not None:
        tamper(served)
    say("build", n_layers=sizes.n_layers, d_model=sizes.d_model,
        vocab=sizes.vocab_size, max_length=sizes.max_length,
        n_slots=served.n_slots, n_blocks=served.n_blocks, **phases)
    t0 = time.monotonic()
    chunk = cfg["serve"]["fleet"].get("prefill_chunk", 32)
    first = warm_up(served, sizes, seed, chunk, served.block_size)
    phases["warm_s"] = time.monotonic() - t0
    say("first_call", mixed_s=first["mixed"], decode_s=first["decode"],
        warm_s=phases["warm_s"], compilations=compiles.n)
    return served, family, sizes, devices, dev, phases, compiles


def run_cell(workload: str, seed: int, seconds: float, trace: int, *,
             t_start: float | None = None, root: str = ROOT,
             allow_cpu: bool = False, engine_overrides: dict | None = None,
             control: bool = False, all_finished: bool = False,
             tamper=None) -> dict:
    """Run one cell once and return the result object (the last line).

    ``allow_cpu``, ``engine_overrides`` and ``tamper`` are the tests' entry:
    the command itself never sets them. ``control=True`` also reads the
    lower-precision control, and ``all_finished=True`` hands the reference
    EVERY finished request in place of the cell's sample (both
    ``limits.py``'s, never a benchmark run's)."""
    t_start = time.monotonic() if t_start is None else t_start
    spec = load_cell(workload, root)
    traffic = spec["traffic"]

    from perfbench import check
    from perfbench.traffic_kinds import load_kind

    served, family, sizes, devices, dev, phases, compiles = set_up(
        spec, seed, t_start=t_start, allow_cpu=allow_cpu,
        engine_overrides=engine_overrides, tamper=tamper)
    t0 = time.monotonic()
    plan = load_kind(traffic["kind"])(
        traffic, seed=seed, seconds=seconds, vocab=sizes.vocab_size,
        max_total=sizes.max_length, n_slots=served.n_slots)
    t1 = time.monotonic()
    standing = open_standing(served, plan)
    phases["plan_s"], phases["standing_s"] = t1 - t0, time.monotonic() - t1
    say("standing", requests=len(standing), plan_s=phases["plan_s"],
        standing_s=phases["standing_s"],
        context_tokens=sum(len(t.planned.prompt) for t in standing))
    in_flight_open = sum(t.done_t is None for t in standing)
    served.queue_wait_new()
    compiled_before = compiles.n

    tracer = None
    if trace:
        from perfbench import xplane

        span = min(TRACE_SPAN_S, 0.4 * seconds)
        tracer = xplane.SpanTracer(
            os.path.join(ROOT, ".cache", "perfbench_trace", workload),
            start_s=seconds - span, span_s=span)
    setup_s = time.monotonic() - t_start
    out = drive(served, plan, seconds, float(traffic.get("drain_limit_s", 0)),
                standing=standing, annotate=tracer.annotate if tracer else None,
                on_tick=tracer.tick if tracer else None)
    if tracer:
        tracer.stop()
    compiled_inside = compiles.n - compiled_before
    compiles.close()

    health = served.health()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    say("memory", peak_bytes_in_use=peak, **health,
        compilations_in_window=compiled_inside)
    if compiled_inside or not served.sound(health):
        raise BenchFailure(f"the window compiled {compiled_inside} "
                           f"program(s) or the fleet is not sound: {health}")

    rec = records_of(out, served, family, sizes, len(devices), dev["kind"],
                     setup_s)
    attempted, failed = count_requests(rec)
    say("window", attempted=attempted, failed=failed,
        standing=len(standing), finished=len(rec.finished),
        in_flight_open=in_flight_open,
        in_flight_close=sum(t.submit_t < rec.t_close and (
            t.done_t is None or t.done_t >= rec.t_close)
            for t in rec.tracked),
        steps=len(rec.steps),
        longest_step_ms=max((s[1] - s[0] for s in rec.steps), default=0) * 1e3,
        window_s=rec.t_close - rec.t_open,
        drain_s=rec.t_end - rec.t_close,
        tokens_in_window=sum(s[4] for s in rec.steps
                             if s[1] < rec.t_close))

    finished = rec.finished
    picked = (list(range(len(finished))) if all_finished
              else pick_sample(finished, seed, **spec["sample"]))
    sample = [(list(finished[i].planned.prompt), list(finished[i].req.output))
              for i in picked]
    served.close()
    t0 = time.monotonic()
    verdict = check.compare(family, sizes, seed, sample, devices[0],
                            limits=spec["limits"], control=control)
    for row, i in zip(verdict.get("per_request", ()), picked):
        row["standing"] = finished[i].standing
    verdict["seconds"] = time.monotonic() - t0
    say("correct", **verdict)
    correct = bool(verdict["correct"] and attempted > 0)
    # Each number compared beside its limit, as the last lines on standard
    # error: where a run is not correct, the end of it is what is kept.
    for name, limit in verdict["limits"].items():
        print(f"perfbench: compared {name} "
              f"{verdict.get('compared', {}).get(name)} limit {limit}",
              file=sys.stderr, flush=True)

    if tracer:
        from perfbench import xplane

        rec.trace = xplane.reduce_trace(tracer.path, tracer.window)
    group = spec["per_layer"] if trace else spec["end_to_end"]
    package = "layer_metrics" if trace else "end_to_end"
    metrics = {}
    for m in group:
        mod = importlib.import_module(reader_module(package, m["name"]))
        value = mod.read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {**dev, "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = rec.trace["breakdown"]
    result["check"] = verdict      # the numbers compared come last
    return result


def records_of(out: dict, served, family, sizes, n_chips: int,
               device_kind: str, setup_s: float = 0.0) -> Records:
    """What ``drive`` returned, with what the readers need beside it."""
    return Records(
        t_open=out["t_open"], t_close=out["t_close"], t_end=out["t_end"],
        setup_s=setup_s, tracked=out["tracked"], steps=out["steps"],
        kv_live=out["kv_live"], counters=out["counters"],
        queue_wait_s=served.queue_wait_new(), sizes=sizes, family=family,
        n_slots=served.n_slots, n_chips=n_chips, device_kind=device_kind)


def reader_module(package: str, metric: str) -> str:
    """A metric's reader is the module of its name under ``end_to_end/`` or
    ``layer_metrics/`` (``-`` becomes ``_``). A quantity that moves another
    end-to-end metric in other cells is entered once for each under
    ``<name>.<suffix>``, and all of them share the reader ``<name>``."""
    return f"perfbench.{package}.{metric.split('.')[0].replace('-', '_')}"


def count_requests(rec: Records) -> tuple[int, int]:
    """Requests attempted and failed. Failed: refused or failed by the
    program, finished short of what it asked for, or still without a first
    token when the drain ended. A request that is still decoding then was
    attempted and has not failed."""
    failed = 0
    for t in rec.tracked:
        short = (t.done_t is not None
                 and len(t.req.output) != t.req.max_new_tokens)
        if t.req.status == "failed" or short or not t.stamps:
            failed += 1
    return len(rec.tracked), failed


def main(argv=None, *, t_start: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                          t_start=t_start)
    except BenchFailure as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0
