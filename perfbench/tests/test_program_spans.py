"""The readers of the program's own spans (``program_spans`` and the three
``host_*`` metrics) on hand-made records: the arithmetic of the host's turn,
and None where there is nothing to read (no trace, a program without the
spans, a ring that wrapped inside the traced span)."""

import json

import pytest

from perfbench import core, program_spans
from perfbench.layer_metrics import (host_dispatch_ms, host_observe_ms,
                                     host_turn_ms)
from triton_distributed_tpu.obs.trace import SpanRecord, Tracer

MS = 1e-3
T0 = 100.0      # the traced span opens here on the host's clock


def span(name, start_ms, dur_ms, tid=1, phase="X", **attrs):
    t = T0 + start_ms * MS
    return SpanRecord(name=name, t_start=t, t_end=t + dur_ms * MS, depth=0,
                      tid=tid, phase=phase, attrs=attrs or None)


def a_step(at_ms, *, wait_ms, kind="decode_step", dispatch_ms=1.0,
           observe_ms=0.5, extra_ms=0.0):
    """One ``fleet.step`` as the program nests it, in the order the spans
    CLOSE (the ring's order): route, the engine's phases, the engine's step,
    the fleet's. Lengths: route 0.1, admit 0.2, blocks 0.3, observe,
    dispatch, the wait, retire 0.4, and 0.2 + ``extra_ms`` of the fleet's
    own around them."""
    t = at_ms + 0.1
    out = [span("fleet.route", t, 0.1)]
    t += 0.1
    eng = t
    for name, d in (("engine.admit", 0.2), ("engine.blocks", 0.3),
                    ("engine.observe", observe_ms),
                    ("engine.dispatch", dispatch_ms), (kind, wait_ms),
                    ("engine.retire", 0.4)):
        if d:
            out.append(span(name, t, d))
            t += d
    out.append(span("engine.step", eng, t - eng))
    out.append(span("fleet.step", at_ms, t - at_ms + 0.1 + extra_ms))
    return out


def tracer_of(records, capacity=1 << 10):
    tracer = Tracer(capacity=capacity)
    for r in records:
        tracer._append(r)
    return tracer


def record_of(trace, steps=()):
    return core.Records(
        t_open=T0 - 37.0, t_close=T0 + 3.0, t_end=T0 + 4.0, setup_s=1.0,
        tracked=[], steps=list(steps), kv_live=[], counters={},
        queue_wait_s=[], sizes=None, family=None, n_slots=4, n_chips=1,
        device_kind="cpu", trace=trace)


def traced(window=(T0, T0 + 3.0)):
    return {"host_window": window, "busy_s": 1.0, "ops_s": {}}


# Three decode steps and a mixed one. Turn = length - wait:
# 0.1 + 0.1 + 0.2 + 0.3 + observe + dispatch + 0.4 + 0.1 (+ extra).
STEPS = (a_step(0, wait_ms=7.0)                                  # turn 2.7
         + a_step(12, wait_ms=6.0, dispatch_ms=2.0)              # turn 3.7
         + a_step(24, wait_ms=20.0, kind="mixed_step",
                  dispatch_ms=4.0, observe_ms=1.5)               # turn 6.7
         + a_step(60, wait_ms=7.5, extra_ms=1.0))                # turn 3.7


def test_the_three_readers_arithmetic(capsys):
    rec = record_of(traced(), steps=[
        (T0 + 0 * MS, T0 + 9.8 * MS, "decode", 4, 4, 40),
        (T0 + 12 * MS, T0 + 21.8 * MS, "decode", 4, 4, 44),
        (T0 + 24 * MS, T0 + 50.8 * MS, "mixed", 3, 3, 33),
        (T0 + 60 * MS, T0 + 72 * MS, "decode", 4, 4, 48),
        (T0 - 5 * MS, T0 - 1 * MS, "decode", 4, 4, 36)])     # before the span
    records = program_spans.spans(rec, tracer_of(STEPS))
    assert records[0].name == "fleet.step"      # oldest first, by its start
    assert [r.t_start for r in records] == sorted(r.t_start for r in records)
    assert program_spans.turns_ms(rec) == pytest.approx([2.7, 3.7, 6.7, 3.7])
    assert host_turn_ms.read(rec) == pytest.approx(3.7)
    # engine.dispatch 1, 2, 4, 1; engine.observe 0.5, 0.5, 1.5, 0.5
    assert host_dispatch_ms.read(rec) == pytest.approx(1.5)
    assert host_observe_ms.read(rec) == pytest.approx(0.5)
    # ONE phase line, printed by the first reader that asked
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    (line,) = [ln for ln in lines if ln["phase"] == "host_turn"]
    assert line["fleet.step"]["n"] == 4
    assert line["fleet.step"]["median_ms"] == pytest.approx((9.7 + 11.2) / 2)
    assert line["decode_step"] == {"n": 3, "median_ms": pytest.approx(7.0),
                                   "p99_ms": pytest.approx(7.5),
                                   "max_ms": pytest.approx(7.5)}
    assert line["mixed_step"]["n"] == 1
    assert line["host_turn"]["max_ms"] == pytest.approx(6.7)
    # what no phase inside it covers: 0.2 of the fleet's own, once 1.2
    assert line["fleet.step.remainder"]["median_ms"] == pytest.approx(0.2)
    assert line["fleet.step.remainder"]["max_ms"] == pytest.approx(1.2)
    longest = line["longest_fleet_step"]
    assert longest["ms"] == pytest.approx(26.7)
    assert longest["wait_ms"] == pytest.approx(20.0)
    assert longest["at_s"] == pytest.approx(37.024)
    assert longest["phases"]["engine.dispatch"] == pytest.approx(4.0)
    # the outside measurement of the same calls: the bench's walls of the
    # span's steps (the one before the span is not among them)
    assert line["bench.step"]["n"] == 4
    assert line["bench.step"]["median_ms"] == pytest.approx((9.8 + 12) / 2)


def test_an_idle_call_is_no_turn_and_other_threads_spans_stay_out():
    idle = [span("fleet.route", 80.1, 0.1),
            span("engine.observe", 80.3, 0.4),
            span("engine.step", 80.2, 0.6),
            span("fleet.step", 80, 1.0)]
    # another thread's engine.dispatch overlapping the first step in time
    other = [span("engine.dispatch", 3, 5.0, tid=2)]
    # a read with nothing to dispatch behind it (the idle flush) is a turn
    flush = [span("engine.observe", 90.2, 0.5), span("decode_step", 90.7, 3.0),
             span("engine.retire", 93.7, 0.3), span("engine.step", 90.1, 4.0),
             span("fleet.step", 90, 4.2)]
    rec = record_of(traced())
    program_spans.spans(rec, tracer_of(STEPS + idle + other + flush))
    turns = program_spans.turns_ms(rec)
    assert turns == pytest.approx([2.7, 3.7, 6.7, 3.7, 1.2])
    # the per-step reader follows the turns; the length reader takes every
    # span of the name, the other thread's too
    assert program_spans.per_step_ms(rec, "engine.observe") == pytest.approx(
        [0.5, 0.5, 1.5, 0.5, 0.5])
    assert sorted(program_spans.lengths_ms(rec, "engine.dispatch")) == \
        pytest.approx([1.0, 1.0, 2.0, 4.0, 5.0])
    first = program_spans.steps(rec.trace[program_spans.KEY])[0]
    assert first["phases"]["engine.dispatch"] == pytest.approx(1.0)


def test_only_what_began_inside_the_traced_span_is_read():
    before = a_step(-30, wait_ms=7.0, dispatch_ms=9.0)
    after = a_step(3100, wait_ms=7.0, dispatch_ms=9.0)
    marks = [span("first_token", 5, 0.0, phase="i"),
             span("request", 6, 0.0, phase="b")]
    rec = record_of(traced())
    records = program_spans.spans(rec, tracer_of(before + STEPS + marks
                                                 + after))
    assert len(records) == len(STEPS)
    assert all(r.phase == "X" for r in records)
    assert host_dispatch_ms.read(rec) == pytest.approx(1.5)
    # cached on the trace: a second tracer is not asked
    assert program_spans.spans(rec, tracer_of([])) is records


@pytest.mark.parametrize("case", ["no_trace", "no_window", "no_spans",
                                  "older_program", "wrapped"])
def test_nothing_to_read_is_none_and_raises_nothing(case, capsys):
    if case == "no_trace":
        rec, tracer = record_of(None), tracer_of(STEPS)
    elif case == "no_window":       # the window closed before the span opened
        rec, tracer = record_of(traced(window=None)), tracer_of(STEPS)
    elif case == "no_spans":        # a capture in which the program ran dry
        rec, tracer = record_of(traced()), tracer_of([])
    elif case == "older_program":   # its tracer has no ``between``
        class Old:
            records, dropped = list(STEPS), 0
        rec, tracer = record_of(traced()), Old()
    else:
        # a ring of 8 that took 36 records: the oldest left closed after
        # the span opened, so the span's first records are gone
        rec, tracer = record_of(traced()), tracer_of(STEPS, capacity=8)
        assert tracer.dropped == len(STEPS) - 8
    assert not program_spans.spans(rec, tracer)
    for reader in (host_turn_ms, host_dispatch_ms, host_observe_ms):
        assert reader.read(rec) is None
    assert "host_turn" not in capsys.readouterr().out


def test_a_ring_that_wrapped_before_the_span_opened_is_read():
    old = a_step(-900, wait_ms=7.0) + a_step(-800, wait_ms=7.0)
    tracer = tracer_of(old + a_step(-30, wait_ms=7.0) + STEPS,
                       capacity=len(STEPS) + 10)
    assert tracer.dropped == 3 * 9 - 10     # the oldest: all before the span
    rec = record_of(traced())
    assert len(program_spans.spans(rec, tracer)) == len(STEPS)
    assert host_turn_ms.read(rec) == pytest.approx(3.7)


def test_the_new_entries_name_their_cells_and_find_their_readers():
    """Four entries at the end of ``per_layer``, each with its cells, each
    cell reporting the end-to-end metric the entry moves; ``host_turn_ms``
    is one reader under two entries."""
    bench = core.load_json(core.ROOT, "BENCHMARK.json")
    new = {m["name"]: m for m in bench["per_layer"][-4:]}
    assert list(new) == ["host_turn_ms.reasoning", "host_turn_ms.chat",
                         "host_dispatch_ms", "host_observe_ms"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, m in new.items():
        assert (m["source"], m["unit"], m["better"], m["layer"]) == (
            "program_span", "ms", "lower", "scheduler and admission")
        assert m["workloads"] and set(m["workloads"]) <= set(
            e2e[m["moves"]]["workloads"])
        mod = core.reader_module("layer_metrics", name)
        assert mod.endswith(name.split(".")[0])
        assert callable(__import__(mod, fromlist=["read"]).read)
    assert new["host_turn_ms.chat"]["workloads"] == ["qwen3-1.7b.chat"]
    for cell in new["host_dispatch_ms"]["workloads"]:
        names = [m["name"] for m in core.load_cell(cell)["per_layer"]]
        assert "host_turn_ms.reasoning" in names
        assert "host_turn_ms.chat" not in names
