"""From the profiler's ``.xplane.pb`` to busy and idle time, device time by
operation name, and the longest idle gaps by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else. The reduction works
on a plain structure (``load`` gives it, tests hand-make it):

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [(name, start_ns, duration_ns), ...]}]}]}

Device planes are those named ``/device:TPU:<n>``. On such a plane the line
``XLA Ops`` holds one event per operation that ran on the chip and
``XLA Modules`` one per compiled program. The host's plane
(``/host:CPU``) holds, among much else, the benchmark's own annotations
(``bench.step``, ``bench.submit``, ``bench.wait_due``), which is how an idle
gap on the device is put down to what the host was doing.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import time


DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait_due")
TOP = 10


class SpanTracer:
    """Profiles one span of the window: opens ``start_s`` into it and closes
    ``span_s`` later. ``tick`` is called by the loop after every step."""

    def __init__(self, path: str, *, start_s: float, span_s: float):
        self.path, self.start_s, self.span_s = path, start_s, span_s
        self.state = "waiting"
        self.window = None
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path, exist_ok=True)

    def annotate(self, name: str):
        import jax

        if self.state != "tracing":
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def tick(self, now_s: float) -> None:
        import jax

        if self.state == "waiting" and now_s >= self.start_s:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # no per-call Python events
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.path, profiler_options=options)
            self._t0 = time.monotonic()
            self.state = "tracing"
        elif self.state == "tracing" and now_s >= self.start_s + self.span_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.window = (self._t0, time.monotonic())
        self.state = "done"


def find_xplane(path: str) -> str:
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(profile: dict) -> list:
    return [p for p in profile["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, line_name: str) -> list:
    return [ev for ln in plane["lines"] if ln["name"] == line_name
            for ev in ln["events"]]


def host_spans(profile: dict) -> list:
    """(name, start_ns, end_ns) of the benchmark's annotations."""
    out = []
    for p in profile["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if name in HOST_SPANS:
                    out.append((name, s, s + d))
    return sorted(out, key=lambda x: x[1])


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def blame(gap, spans) -> str:
    """The host span that covers most of an idle gap, or ``between_steps``
    (the benchmark's own loop and whatever else the host ran)."""
    best, cover = "between_steps", 0.0
    by_name = {}
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0:
            by_name[name] = by_name.get(name, 0.0) + ov
    for name, ov in by_name.items():
        if ov > cover:
            best, cover = name, ov
    return best if cover >= 0.5 * (gap[1] - gap[0]) else "between_steps"


def self_seconds(events) -> dict:
    """Seconds by name with every event's nested events taken out: the
    ``XLA Ops`` line nests (a ``while`` covers the operations of its body),
    so plain sums would count the body twice."""
    out: dict = {}
    stack = []                      # (end_ns, name, self_ns as a 1-list)
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            _, n, own = stack.pop()
            out[n] = out.get(n, 0.0) + own[0] / 1e9
        if stack:
            stack[-1][2][0] -= min(d, stack[-1][0] - s)
        stack.append((s + d, name, [d]))
    for _, n, own in stack:
        out[n] = out.get(n, 0.0) + own[0] / 1e9
    return out


def short_name(name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(...)`` -> ``fusion.3 bf16[..] fusion``:
    the instruction, the shape it makes and its opcode."""
    m = re.match(r"%?([\w.\-]+) = (\(?[\w\[\],]*)[^ ]* ([\w\-]+)\(", name)
    return " ".join(m.groups())[:120] if m else name[:120]


def reduce_profile(profile: dict) -> dict:
    """Busy seconds (union of the device's operation intervals, mean over
    the chips), the traced span (first to last device event, the same for
    every chip), self seconds by operation name and seconds by program name
    (mean over the chips), and the idle gaps by what the host was doing."""
    planes = device_planes(profile)
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane")
    per_chip = []
    lo = min(s for p in planes for _, s, _ in line_events(p, OPS_LINE))
    hi = max(s + d for p in planes for _, s, d in line_events(p, OPS_LINE))
    spans = host_spans(profile)
    ops: dict = {}
    modules: dict = {}
    gap_blame: dict = {}
    for p in planes:
        evs = line_events(p, OPS_LINE)
        busy = merged((s, s + d) for _, s, d in evs)
        per_chip.append(sum(e - s for s, e in busy) / 1e9)
        for name, secs in self_seconds(evs).items():
            ops[name] = ops.get(name, 0.0) + secs / len(planes)
        for name, _, d in line_events(p, MODULES_LINE):
            modules[name] = modules.get(name, 0.0) + d / 1e9 / len(planes)
        if p is planes[0]:
            edges = [lo] + [x for se in busy for x in se] + [hi]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if g1 > g0:
                    who = blame((g0, g1), spans)
                    gap_blame[who] = gap_blame.get(who, 0.0) + (g1 - g0) / 1e9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gap_blame.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(per_chip) / len(per_chip),
        "busy_s_per_chip": per_chip,
        "window_s": (hi - lo) / 1e9,
        "ops_s": ops, "modules_s": modules,
        "module_events": {p["name"]: line_events(p, MODULES_LINE)
                          for p in planes[:1]},
        "breakdown": {"device_ops": [[short_name(n), s] for n, s in top_ops],
                      "idle_gaps": [[n, s] for n, s in top_gaps]},
    }


def reduce_trace(path: str, host_window) -> dict:
    out = reduce_profile(load(path))
    out["host_window"] = host_window
    return out


def seconds_matching(by_name: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(s for n, s in by_name.items() if rx.search(n))
