"""The host's turn has spans (``obs.trace``): ``Fleet.step()`` and
``BatchEngine.step()`` record their phases whenever the tracer is enabled
or a profiler capture is live, on ``time.monotonic()``, and cost one call a
site when neither holds. Tiny sizes and the plain path
(``conftest.PLAIN_PATH``): what is tested is host code.
"""

import gc
import glob
import time

import jax
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.obs import trace
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving.fleet import Fleet

# engine.step's phases in the order they run, and what the table says each
# carries.
PHASES = ["engine.admit", "engine.blocks", "engine.observe",
          "engine.dispatch", "engine.retire"]
ATTRS = {
    "fleet.step": {"replicas", "pending"},
    "fleet.route": {"routed"},
    "engine.step": {"dispatched", "read"},
    "engine.admit": {"admitted", "waiting", "released"},
    "engine.blocks": {"preempted", "drafts_dropped"},
    "engine.dispatch": {"kind", "decode_rows", "prefill_rows", "overlapped"},
    "engine.retire": {"tokens", "finished"},
}
# what a mixed step's plan adds to ``engine.dispatch`` and ``mixed_step``
MIXED_PLAN = {"prefill_rows", "mixed_step_tokens", "prefill_rows_filled",
              "prefill_rows_extra", "prefill_rows_deferred"}


@pytest.fixture(scope="module")
def engine():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    return Engine(ModelConfig.from_name("tiny"), mesh=mesh, mode="xla",
                  block_n=8)


@pytest.fixture
def fleet(engine):
    return Fleet.build(engine, n_replicas=1, n_slots=4, n_blocks=32,
                       block_size=4, prefill_chunk=8, **PLAIN_PATH)


@pytest.fixture(autouse=True)
def clean_tracer():
    """The process-global tracer off and empty, and no hook of an earlier
    test left in ``gc.callbacks`` (a hook leaves at the first collection
    that finds recording off)."""
    trace.disable()
    trace.reset()
    gc.collect()
    yield
    trace.disable()
    trace.reset()
    gc.collect()


def serve_some(fleet, steps: int, prompt=range(1, 12), new: int = 6):
    rid = fleet.submit(list(prompt), new)
    for _ in range(steps):
        fleet.step()
    return rid


def inside(child, parent) -> bool:
    return (parent.t_start <= child.t_start and child.t_end <= parent.t_end
            and child.depth > parent.depth)


def test_off_a_step_records_nothing_and_hooks_nothing(fleet):
    be = fleet.replicas[0].engine
    hooks = list(gc.callbacks)
    for i in range(50):
        if i % 25 == 0:
            be.submit([1, 2, 3, 4], 24)
        be.step()
    for _ in range(3):
        fleet.step()
    assert be.metrics.counters["decode_steps"] > 40
    assert len(trace.get_tracer()) == 0
    assert gc.callbacks == hooks
    assert not trace.enabled() and not trace.get_tracer().recording()
    # one shared no-op context a site, no object made
    assert trace.span("engine.step") is trace.span("fleet.step")


def test_one_fleet_step_yields_the_tables_spans_nested(fleet):
    serve_some(fleet, 3)        # the prompt read (two rows of one step)
    with trace.tracing() as tracer:
        tracer.reset()
        fleet.step()
        spans = [r for r in tracer.records if r.phase == "X"
                 and r.name != "gc_pause"]
    by_name = {r.name: r for r in spans}
    assert [r.name for r in sorted(spans, key=lambda r: r.t_start)] == [
        "fleet.step", "fleet.route", "engine.step", *PHASES[:4],
        "decode_step", "engine.retire"]
    step, eng = by_name["fleet.step"], by_name["engine.step"]
    assert step.depth == 0
    assert inside(by_name["fleet.route"], step) and inside(eng, step)
    assert by_name["fleet.route"].depth == eng.depth == 1
    for name in (*PHASES, "decode_step"):
        assert inside(by_name[name], eng) and by_name[name].depth == 2, name
    # the phases follow one another, none inside the next
    order = [by_name[n] for n in (*PHASES[:4], "decode_step", "engine.retire")]
    for a, b in zip(order, order[1:]):
        assert a.t_end <= b.t_start
    for name, keys in ATTRS.items():
        assert keys <= set(by_name[name].attrs), name
    assert by_name["engine.observe"].attrs is None
    # fleet.step is its children and a remainder that is its own turn
    ms = lambda r: r.t_end - r.t_start                       # noqa: E731
    remainder = ms(step) - ms(by_name["fleet.route"]) - ms(eng)
    assert 0 <= remainder < ms(step)
    assert ms(eng) >= sum(ms(r) for r in order)
    assert eng.attrs == {"dispatched": "decode", "read": "decode"}
    d = by_name["engine.dispatch"].attrs
    assert (d["kind"], d["decode_rows"], d["prefill_rows"],
            d["overlapped"]) == ("decode", 1, 0, True)
    assert by_name["decode_step"].attrs["decode_rows"] == 1
    assert by_name["engine.retire"].attrs == {"tokens": 1, "finished": 0}


def test_a_mixed_step_says_what_it_dispatched_and_admitted(fleet):
    with trace.tracing() as tracer:
        serve_some(fleet, 1, prompt=range(1, 20), new=2)
        first = {r.name: r.attrs for r in tracer.records if r.phase == "X"}
        tracer.reset()
        fleet.step()
        second = {r.name: r.attrs for r in tracer.records if r.phase == "X"}
    assert first["fleet.step"]["pending"] == 1
    assert first["fleet.route"] == {"routed": 1}
    assert first["engine.admit"] == {"admitted": 1, "waiting": 0,
                                     "released": 0}
    assert first["engine.step"] == {"dispatched": "mixed", "read": "none"}
    assert "mixed_step" not in first and "engine.retire" not in first
    # 19 tokens through a block of 4 rows of 8: one request, three rows
    d = first["engine.dispatch"]
    assert MIXED_PLAN | {"prefill_tokens"} <= set(d)
    assert (d["kind"], d["prefill_rows"], d["prefill_tokens"],
            d["decode_rows"], d["mixed_step_tokens"],
            d["prefill_rows_filled"], d["prefill_rows_extra"],
            d["prefill_rows_deferred"], d["overlapped"]) == (
                "mixed", 1, 19, 0, 19, 3, 2, 0, False)
    # the prompt went in one step: the next call dispatches its first
    # decode step and reads the mixed one
    assert second["engine.step"] == {"dispatched": "decode", "read": "mixed"}
    m = second["mixed_step"]
    assert MIXED_PLAN <= set(m)
    assert (m["decode_rows"], m["prefill_rows_filled"],
            m["prefill_rows_extra"]) == (0, 3, 2)
    assert second["engine.dispatch"]["overlapped"] is True


def test_a_serial_engine_names_its_reason_and_reads_what_it_dispatched(
        engine):
    fleet = Fleet.build(engine, n_replicas=1, n_slots=4, n_blocks=32,
                        block_size=4, prefill_chunk=8, **PLAIN_PATH,
                        nan_guard=True)
    with trace.tracing() as tracer:
        serve_some(fleet, 1)
        eng = next(r for r in tracer.records if r.name == "engine.step")
        names = [r.name for r in tracer.records if r.phase == "X"]
    assert eng.attrs == {"serial": "guard", "dispatched": "mixed",
                         "read": "mixed"}
    assert names.index("engine.dispatch") < names.index("mixed_step") \
        < names.index("engine.retire")


def test_a_capture_records_without_enable_and_the_profile_has_the_attributes(
        fleet, tmp_path):
    from jax.profiler import ProfileData

    serve_some(fleet, 2)
    assert len(trace.get_tracer()) == 0
    t0 = time.monotonic()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.enabled()          # recording, though not ``enable()``d
        fleet.step()
        fleet.step()
    finally:
        jax.profiler.stop_trace()
    t1 = time.monotonic()
    assert not trace.enabled()
    n = len(trace.get_tracer())
    fleet.step()
    assert len(trace.get_tracer()) == n        # the capture over: off again
    recorded = trace.get_tracer().between(t0, t1)
    assert [r.name for r in recorded if r.name == "fleet.step"] == \
        ["fleet.step"] * 2
    assert {"engine.dispatch", "engine.retire", "engine.observe"} <= {
        r.name for r in recorded}

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ATTRS or ev.name == "decode_step":
                    assert plane.name.startswith("/host:")
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    assert len(found["fleet.step"]) == 2
    for name, keys in ATTRS.items():
        assert keys <= set(found[name][0]), name
    assert found["engine.dispatch"][0]["kind"] == "decode"
    assert found["engine.dispatch"][0]["decode_rows"] == 1
    # given later by ``set()``: through ``set_metadata``
    assert found["engine.retire"][0]["tokens"] == 1
    assert found["decode_step"][0]["decode_rows"] == 1


def test_gc_pause_is_a_span_while_recording_and_the_hook_leaves_after():
    """The process-global tracer's (``gc_pauses``); an isolated instance
    holds what its owner recorded and nothing else."""
    assert trace.get_tracer().gc_pauses
    tracer, plain = trace.Tracer(), trace.Tracer()
    assert not plain.gc_pauses
    tracer.gc_pauses = True
    hooks = list(gc.callbacks)
    plain.enable()
    plain.instant("mark")
    assert gc.callbacks == hooks
    tracer.enable()
    with tracer.span("work"):
        gc.collect()
    tracer.instant("mark")
    pauses = [r for r in tracer.records if r.name == "gc_pause"]
    assert [r.name for r in plain.records] == ["mark"]
    assert pauses and pauses[-1].attrs["generation"] == 2
    assert "collected" in pauses[-1].attrs and pauses[-1].phase == "X"
    assert pauses[-1].depth == 1            # inside the span it interrupted
    assert pauses[-1].t_end >= pauses[-1].t_start
    assert len(gc.callbacks) == len(hooks) + 1
    tracer.disable()
    n = len(tracer)
    gc.collect()                # finds recording off: takes itself out
    assert gc.callbacks == hooks and len(tracer) == n
    tracer.enable()             # and comes back with the next record
    tracer.instant("again")
    assert len(gc.callbacks) == len(hooks) + 1
    tracer.disable()
    gc.collect()
    assert gc.callbacks == hooks


def test_between_gives_a_windows_records_oldest_first():
    tracer = trace.Tracer(capacity=8)
    tracer.enable()
    with tracer.span("outer"):          # closes last, began first
        with tracer.span("inner"):
            pass
    t_mid = time.monotonic()
    with tracer.span("late"):
        tracer.instant("mark")
    t_end = time.monotonic()
    tracer.disable()
    got = [r for r in tracer.between(0.0, t_end) if r.name != "gc_pause"]
    assert [r.name for r in got] == ["outer", "inner", "late", "mark"]
    assert [r.name for r in tracer.between(t_mid, t_end)
            if r.name != "gc_pause"] == ["late", "mark"]
    assert tracer.between(t_end, t_end + 1) == []
    # the half-open window: a record that begins at t1 is the next window's
    late = next(r for r in got if r.name == "late")
    assert late not in tracer.between(0.0, late.t_start)
    assert late in tracer.between(late.t_start, t_end)
    assert tracer.dropped == 0


def test_spans_read_the_clock_of_the_requests_stamps(fleet):
    """One clock: a span's stamps and ``Request.submit_t`` /
    ``first_token_t`` compare directly (``time.monotonic()``)."""
    t0 = time.monotonic()
    with trace.tracing() as tracer:
        rid = serve_some(fleet, 4)
        steps = [r for r in tracer.records if r.name == "fleet.step"]
        first_token = next(r for r in tracer.records
                           if r.name == "first_token")
    t1 = time.monotonic()
    req = fleet.request(rid)
    assert t0 <= req.submit_t <= steps[0].t_start <= steps[-1].t_end <= t1
    assert first_token.t_start == pytest.approx(req.first_token_t, abs=1e-3)
    assert not hasattr(steps[0], "wall_start")
    ev = tracer.chrome_events()
    ts = next(e["ts"] for e in ev if e["name"] == "fleet.step")
    assert ts == steps[0].t_start * 1e6


def test_fleet_request_is_the_handle_a_caller_keeps(fleet):
    rid = fleet.submit([1, 2, 3], 2)
    req = fleet.request(rid)
    assert req.req_id == rid and req.status == "pending" and not req.output
    fleet.run()
    assert fleet.request(rid) is req and req.status == "ok"
    assert len(req.output) == 2 and req.finish_t is not None
    with pytest.raises(KeyError):
        fleet.request("never-submitted")
