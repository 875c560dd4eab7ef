"""One step in flight (``BatchEngine.step``): a call dispatches step N+1 from
what the host knows by count and only then reads step N. Tiny sizes and the
gather path, so that a case costs seconds: what is tested is host logic and
the ``fed`` operand of the two compiled steps.

The oracle is the SAME loop flushed after every call (``step(); flush()``:
nothing is ever left in flight, which is the serial loop): the overlapped
loop has to serve its token streams to the bit.
"""

import copy

import jax
import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.models.config import (
    DeepseekV3Config,
    ExaoneMoeConfig,
    GraniteHybridConfig,
    ModelConfig,
    NemotronHConfig,
)
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.obs import trace as _trace
from triton_distributed_tpu.obs.efficiency import EfficiencyLedger
from triton_distributed_tpu.resilience import faults
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import batch_engine as _be_mod
from triton_distributed_tpu.serving.batch_engine import FLUSH_REASONS, BatchEngine
from triton_distributed_tpu.serving.fleet import Fleet

N_SLOTS, CHUNK, BLOCK = 4, 8, 4
N_BLOCKS = 18       # of 64 for full residency: ``churn(reuse=True)`` evicts
# dense, held experts over a latent pool, per-slot state, the pattern walk,
# window layers over a ring a slot
CLASSES = {
    "dense": lambda mesh: Engine(ModelConfig.from_name("tiny"), mesh=mesh,
                                 mode="xla", block_n=8),
    "held_experts": lambda mesh: Engine(DeepseekV3Config.tiny(), mesh=mesh,
                                        mode="dist"),
    "slot_state": lambda mesh: Engine(GraniteHybridConfig.tiny(), mesh=mesh,
                                      mode="dist"),
    "pattern_walk": lambda mesh: Engine(NemotronHConfig.tiny(), mesh=mesh,
                                        mode="dist"),
    "window_layers": lambda mesh: Engine(ExaoneMoeConfig.tiny(), mesh=mesh,
                                         mode="dist"),
}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)


@pytest.fixture(scope="module")
def engines(mesh):
    """``engines(name, sampled)``: one ``Engine`` a model class and sampling
    mode, built when first asked for (the sampled one shares the greedy
    one's parameters)."""
    made = {}

    def get(name, sampled=False):
        if (name, False) not in made:
            made[name, False] = CLASSES[name](mesh)
        if sampled and (name, True) not in made:
            eng = copy.copy(made[name, False])
            eng.temperature, eng.top_p = 0.8, 0.9
            made[name, True] = eng
        return made[name, sampled]
    return get


_DONORS: dict = {}


def batch_engine(engine, **kw):
    """A ``BatchEngine`` at the tests' geometry, with a prefill block of ONE
    row (``one_row_block``); engines of one ``Engine`` and geometry share
    their compiled steps, so a case costs a run, not a compile."""
    kw = {**dict(n_slots=N_SLOTS, n_blocks=N_BLOCKS, block_size=BLOCK,
                 prefill_chunk=CHUNK, seed=11, **PLAIN_PATH), **kw}
    key = (id(engine), kw["n_blocks"], kw.get("speculative", False))
    if key not in _DONORS:          # never served from, never wrapped
        _DONORS[key] = BatchEngine(engine, **kw)
    be = BatchEngine(engine, **kw)
    be.share_steps_from(_DONORS[key])
    return be


@pytest.fixture(autouse=True)
def one_row_block(monkeypatch):
    """More prompts prefilling than the block has rows, in every case."""
    monkeypatch.setattr(_be_mod, "MIXED_STEP_TOKEN_BUDGET", N_SLOTS + CHUNK)


def churn(vocab, *, reuse=True):
    """(prompt, max_new_tokens, the call before which it is submitted). A
    prompt that ends mid-chunk (11 = 8 + 3), one shorter than a chunk, one
    whose request ends by count at its first token, prompts of several
    chunks that wait for the block's one row, arrivals while a step is in
    flight; with ``reuse``, more requests than slots, and more tokens than
    the pool of ``N_BLOCKS`` holds: rows are evicted."""
    rng = np.random.default_rng(7)
    plan = [(11, 14, 0), (3, 21, 0), (9, 1, 0), (20, 9, 2)]
    if reuse:
        plan += [(5, 18, 0), (13, 12, 3), (17, 10, 5)]
    return [(rng.integers(0, vocab, size=n).tolist(), new, at)
            for n, new, at in plan]


def record_operands(be):
    """Every compiled step ``be`` dispatches from here on: its name and the
    host-made operands that place its rows (offsets, block tables, mask
    and, for the mixed step, the takes)."""
    calls = []
    for name, n in (("_decode_step", 3), ("_mixed_step", 4)):
        def call(*args, name=name, n=n, step=getattr(be, name)):
            calls.append((name, *(np.asarray(a).tolist()
                                  for a in args[3:3 + n])))
            return step(*args)
        setattr(be, name, call)
    return calls


def serve(be, script, *, flushed, max_calls=400):
    """Drive ``be`` through ``script``; returns the streams in the script's
    order and, for every call, how far the two step counters moved."""
    rids, moved = {}, []
    c = be.metrics.counters
    for call in range(max_calls):
        for k, (prompt, new, at) in enumerate(script):
            if at == call:
                rids[k] = be.submit(prompt, max_new_tokens=new)
        before = (c.get("decode_steps", 0), c.get("prefill_steps", 0))
        busy = be.step()
        if flushed:
            be.flush()
        moved.append((c.get("decode_steps", 0) - before[0],
                      c.get("prefill_steps", 0) - before[1]))
        if not busy and len(rids) == len(script):
            break
    out = be.finished
    assert set(out) == set(rids.values()) and not be.failed
    return [list(out[rids[k]].output) for k in range(len(script))], moved


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("name", list(CLASSES))
def test_the_overlapped_loop_serves_the_flushed_loops_streams(
        engines, name, sampled):
    """Across all the churn there is, greedy and sampled with one seed (the
    key is drawn once a step, in the same order): the streams to the bit,
    and more than that, the SAME STEPS: every step of the overlapped loop
    places the rows the flushed loop's step of that number places, because
    what the schedule depends on (a slot freed, blocks given back, an
    eviction) never waits for a token's value."""
    engine = engines(name, sampled)
    script = churn(engine.config.vocab_size)
    serial = batch_engine(engine)
    serial_steps = record_operands(serial)
    want, _ = serve(serial, script, flushed=True)
    be = batch_engine(engine)
    steps_placed = record_operands(be)
    got, moved = serve(be, script, flushed=False)
    assert got == want
    assert steps_placed == serial_steps
    assert [len(t) for t in got] == [new for _, new, _ in script]
    assert be.trace_counts == {"decode": 1, "prefill": 1}
    c = be.metrics.counters
    steps = c["decode_steps"] + c["prefill_steps"]
    flushes = be.stats_snapshot()["pipeline"]["flushes"]
    # Every step but the first (and the one after each flush) was
    # dispatched while the one before it was still unread.
    assert c["steps_overlapped"] == steps - sum(flushes.values())
    assert set(flushes) == {"idle", "preempt"} and flushes["idle"] == 1
    assert c["preemptions"] == serial.metrics.counters["preemptions"] > 0
    # A call moves at most one step's counters.
    assert all(d + p <= 1 for d, p in moved)
    be.pool.check_invariants()


def test_a_token_is_visible_in_the_call_after_the_one_that_dispatched_it(
        engines):
    """The contract of ``step()``, call by call, and what says so: the
    counters move with the step that is READ, the step's span is recorded
    there (``overlapped``, ``flush``), ``stats_snapshot()["pipeline"]``."""
    be = batch_engine(engines("dense"))
    dispatched = record_operands(be)
    rid = be.submit(list(range(1, 12)), max_new_tokens=3)    # 11 = 8 + 3
    req = be.scheduler.pending()[0]
    c = be.metrics.counters
    seen = []
    with _trace.tracing() as tracer:
        tracer.reset()
        while be.step():
            seen.append((len(dispatched), c.get("prefill_steps", 0),
                         c.get("decode_steps", 0), len(req.output)))
        spans = [(r.name, r.attrs["overlapped"], r.attrs.get("flush"))
                 for r in tracer.records
                 if r.name in ("decode_step", "mixed_step")]
    assert [name for name, *_ in dispatched] == [
        "_mixed_step", "_mixed_step", "_decode_step", "_decode_step"]
    # (steps dispatched, mixed steps read, decode steps read, tokens seen):
    # the second chunk's token comes up one call after its dispatch, the
    # call that reads a mixed step is the one that moves ``prefill_steps``,
    # and the last call has nothing to dispatch: it reads and returns True.
    assert seen == [(1, 0, 0, 0), (2, 1, 0, 0), (3, 2, 0, 1), (4, 2, 1, 2),
                    (4, 2, 2, 3)]
    assert spans == [("mixed_step", False, None), ("mixed_step", True, None),
                     ("decode_step", True, None),
                     ("decode_step", True, "idle")]
    assert be.finished[rid].output == req.output
    assert be.stats_snapshot()["pipeline"] == {
        "steps_overlapped": 3.0, "flushes": {"idle": 1.0}}


@pytest.mark.parametrize("reason", ["speculation", "fault_plan", "guard"])
def test_a_standing_condition_reads_every_step_where_it_was_dispatched(
        engines, reason):
    """Speculation, an installed fault plan, the NaN guard: the next plan
    needs the tokens' values (or the state a retry starts from), so no step
    is ever left in flight, and the streams are the plain engine's."""
    engine = engines("dense")
    script = churn(engine.config.vocab_size, reuse=True)
    want, _ = serve(batch_engine(engine), script, flushed=False)
    kw = {"speculation": {"speculative": True},
          "guard": {"nan_guard": True}}.get(reason, {})
    be = batch_engine(engine, **kw)
    if reason == "fault_plan":
        with faults.plan(faults.FaultPlan([], seed=0)):
            got, moved = serve(be, script, flushed=False)
    else:
        got, moved = serve(be, script, flushed=False)
    assert got == want
    c = be.metrics.counters
    assert c.get("steps_overlapped", 0) == 0
    assert be.stats_snapshot()["pipeline"]["flushes"] == {
        reason: c["decode_steps"] + c["prefill_steps"]}
    assert reason in FLUSH_REASONS
    assert all(d + p <= 1 for d, p in moved)


def test_a_condition_that_comes_up_between_two_calls_only_reads(engines):
    """A fault plan installed while a step is in flight: the next call reads
    that step and dispatches nothing, so that no call moves two steps."""
    be = batch_engine(engines("dense"))
    be.submit([5, 6, 7], max_new_tokens=4)
    assert be.step() and be._inflight is not None
    c = be.metrics.counters
    with faults.plan(faults.FaultPlan([], seed=0)):
        assert be.step() and be._inflight is None
        assert c["prefill_steps"] == 1 and "decode_steps" not in c
        assert be.step() and be._inflight is None
        assert c["decode_steps"] == 1
    out = be.run()
    assert len(next(iter(out.values()))) == 4
    assert be.stats_snapshot()["pipeline"]["flushes"]["fault_plan"] == 2


@pytest.mark.parametrize("entry", ["checkpoint", "retire", "drain", "finished"])
def test_a_caller_between_two_steps_finds_every_dispatched_token(
        engines, entry, tmp_path):
    """``Fleet.checkpoint`` / ``retire`` / a replica's ``drain`` / ``finished``
    taken with a step in flight read it first: no token is lost, and the
    streams end as the undisturbed fleet's."""
    engine = engines("dense")
    script = churn(engine.config.vocab_size, reuse=False)
    donor = batch_engine(engine)
    kw = dict(n_replicas=2, n_slots=N_SLOTS, n_blocks=N_BLOCKS,
              block_size=BLOCK, prefill_chunk=CHUNK, **PLAIN_PATH)

    def fleet():
        f = Fleet.build(engine, **kw)
        for rep in f.replicas:
            rep.engine.share_steps_from(donor)
        return f, [f.submit(p, new) for p, new, _ in script]

    calm, rids = fleet()
    want = calm.run(max_steps=400)
    f, rids2 = fleet()
    assert rids2 == rids
    for _ in range(4):
        f.step()
    busy = [rep for rep in f.replicas if rep.engine._inflight is not None]
    assert busy
    rep = busy[0]
    dispatched = sum(r.slot.in_flight for r in rep.engine._inflight.rows)
    reqs = [s.req for s in rep.engine._slots if s is not None]
    held = sum(len(req.output) for req in reqs)
    assert dispatched > 0
    if entry == "checkpoint":
        f.attach_journal(str(tmp_path / "journal"))
        f.checkpoint(str(tmp_path / "ckpt"))
    elif entry == "retire":
        f.retire(rep.idx)
    elif entry == "drain":
        for req in rep.engine.drain():
            f._requeue(req, "test drain")
    else:
        f.finished
    assert rep.engine._inflight is None
    assert sum(len(req.output) for req in reqs) == held + dispatched
    assert rep.engine.metrics.counters[
        "pipeline_flushes{reason=caller}"] == 1
    got = f.run(max_steps=400)
    assert got == want
    f.check_invariants()


def test_a_read_that_fails_loses_the_step_and_not_the_requests(
        engines, monkeypatch):
    """The device's error comes up where a step's tokens are read. The host
    takes back its count of that step and of the one dispatched behind it,
    so every row stands where its request's tokens say; here the pool is in
    fact sound, so stepping on serves the undisturbed streams."""
    engine = engines("dense")
    script = churn(engine.config.vocab_size, reuse=False)
    want, _ = serve(batch_engine(engine), script, flushed=False)
    be = batch_engine(engine)
    rids = [be.submit(p, new) for p, new, _ in script]
    for _ in range(3):
        be.step()
    assert be._inflight is not None
    before = {i: (s.offset, len(s.req.output))
              for i, s in enumerate(be._slots) if s is not None}
    device_get = jax.device_get
    calls = []

    def failing(x):
        calls.append(x)
        if len(calls) == 1:
            raise RuntimeError("device lost")
        return device_get(x)

    monkeypatch.setattr(jax, "device_get", failing)
    with pytest.raises(RuntimeError, match="device lost"):
        be.step()           # dispatches one more step, then reads: fails
    assert be._inflight is None
    for i, s in enumerate(be._slots):
        if s is None:
            continue
        # The step that was in flight before the call is taken back too: a
        # decoding row has written what its request's tokens say.
        assert s.in_flight == 0 and len(s.req.output) == before[i][1]
        assert s.offset <= before[i][0]
        if not s.prefilling:
            assert s.offset == len(s.req.prompt) + len(s.req.output) - 1
    assert any(s.offset < before[i][0]
               for i, s in enumerate(be._slots) if s is not None)
    out = be.run(max_steps=400)
    assert [out[r] for r in rids] == want
    be.pool.check_invariants()


def test_a_failed_read_requeues_a_request_that_had_left_its_slot(
        engines, monkeypatch):
    """A request that ended BY COUNT in the step in flight gives up its slot
    and blocks before the step is read. If that read fails, the request goes
    back to the queue with the tokens it has, and its re-prefill emits the
    one that was lost."""
    engine = engines("dense")
    prompt = [9, 8, 7]
    calm = batch_engine(engine)
    rid = calm.submit(prompt, max_new_tokens=2)
    want = calm.run()[rid]
    be = batch_engine(engine)
    rid = be.submit(prompt, max_new_tokens=2)
    assert be.step() and be.step()          # both tokens dispatched, one read
    req = be._slots[0].req
    assert len(req.output) == 1 and be._slots[0].ended

    def lost(x):
        raise RuntimeError("device lost")

    with monkeypatch.context() as m:
        m.setattr(jax, "device_get", lost)
        with pytest.raises(RuntimeError, match="device lost"):
            be.step()       # frees the slot, has no row to dispatch, reads
    assert be._slots[0] is None and be._inflight is None
    assert be.scheduler.pending() == [req] and req.n_preemptions == 1
    be.pool.check_invariants()
    assert be.run()[rid] == want and len(want) == 2


def test_the_ledgers_interval_of_an_overlapped_step_opens_at_the_last_end():
    """A step dispatched before the one before it ended could start only
    then: no bubble, no second count of the overlapped time."""
    clock = iter([]).__next__
    led = EfficiencyLedger(clock=clock)
    led.step_begin(1.0)
    led.step_end(flops=0, hbm_bytes=0, now=2.0)
    led.step_begin(1.5)                 # dispatched while the first ran
    att = led.step_end(flops=0, hbm_bytes=0, now=3.0)
    assert (att.t_start, att.interval_s, att.seconds["bubble"]) == (2.0, 1.0,
                                                                   0.0)
    led.step_begin(3.25)                # dispatched after a gap: a bubble
    att = led.step_end(flops=0, hbm_bytes=0, now=4.0)
    assert att.seconds["bubble"] == 0.25 and att.interval_s == 1.0
