"""Comm ledger: who moved how many bytes over which axis, and at what cost.

Every collective entry point in ``kernels/`` reports here when the ledger
is enabled: wire bytes (the analytical per-device byte count from
``runtime/perf_model.py`` — the same model that drives method dispatch),
call counts, the model's estimated latency, and — for host-level wrappers,
where a real wall clock exists — achieved latency. The straggler question
("which collective, on which rank, is slow") then reads straight off the
``achieved vs estimated`` ratio per (collective, axis) without attaching
XProf.

Two recording paths, because kernels run in two regimes:

- ``timed(fn, ...)`` wraps a HOST-level wrapper call (``all_gather(...)``
  etc.): runs ``fn``, blocks until ready, records wall time next to the
  estimate. Blocking is deliberate — the enabled ledger is a measurement
  mode; the disabled path never blocks, never computes bytes, and costs
  one attribute check.
- ``record_traced(...)`` marks a DEVICE-level entry point (``*_device``
  functions composed inside ``shard_map``/``jit``): it fires at TRACE
  time, so it counts what ONE execution of the traced program does, once
  for each time the program is traced — exactly what "is this kernel in
  the compiled program, and how many bytes does each execution move"
  needs. A call site in the body of a loop is traced once and runs every
  trip: the loop's owner says so with ``repeated(trips)`` and the record
  counts ``trips`` calls and their bytes. Records are flagged ``traced``
  so the two kinds never mix. ``gathering()`` hands the traced records of
  a block to its caller whether or not the ledger is enabled: how a
  compiled serving step keeps the count of its own collectives
  (``BatchEngine.stats_snapshot()["collectives"]``).

The ledger is process-global (like the tracer): collectives are called
from layers, engines, and benches that share no object graph.

Resilience hooks: the ``timed()`` host wrappers are ALSO the resilience
layer's instrumentation point for collectives (``resilience.install_hooks``
registers a fault-injection pre-call and a watchdog-deadline context via
``set_resilience_hooks``; ``active()`` tells the kernel call sites to route
through ``timed()`` whenever the ledger is enabled OR a hook is installed).
The hooks live here as plain module attributes so obs/ keeps zero imports
from resilience/ and the disabled path stays one attribute check.
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
import threading
import time

import jax


@dataclasses.dataclass
class LedgerEntry:
    """Aggregate for one (collective, method, axis, world) series."""

    collective: str
    method: str
    axis: str
    world: int
    calls: int = 0            # host-level executions
    traced_calls: int = 0     # device-level trace-time records
    bytes_total: float = 0.0  # analytical wire bytes, summed over calls
    est_s_total: float = 0.0  # perf_model estimated seconds, summed
    wall_s_total: float = 0.0 # achieved seconds (host-level calls only)
    wall_samples: int = 0
    # static facts of the series' call sites, as its last record stated
    # them (``paged_attn``: ``copy_bytes``, ``copies_per_tile``); they join
    # the aggregate's keys in ``as_dict``
    detail: dict = dataclasses.field(default_factory=dict)

    @property
    def key(self) -> str:
        return (f"{self.collective}[{self.method or 'auto'},"
                f"axis={self.axis},world={self.world}]")

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(d.pop("detail"))
        if self.wall_samples and self.est_s_total:
            # achieved / estimated: ~1 means the perf model is honest;
            # >>1 on one rank but not others names the straggler.
            d["achieved_over_est"] = round(
                (self.wall_s_total / self.wall_samples)
                / (self.est_s_total / max(self.calls + self.traced_calls, 1)),
                4)
        return d


@dataclasses.dataclass(frozen=True)
class TracedRecord:
    """One device-level call site, as ``gathering()`` hands it out."""

    collective: str
    method: str
    axis: str
    world: int
    calls: int      # executions of the site in one execution of the program
    nbytes: float   # wire bytes a device sends in those


class _TraceScope(threading.local):
    """What the traced records of this thread are multiplied by and who,
    beside the ledger, is handed them."""

    sinks: tuple = ()
    repeat: int = 1


_SCOPE = _TraceScope()


class CommLedger:
    def __init__(self):
        self.enabled = False
        self._entries: dict[tuple, LedgerEntry] = {}
        self._lock = threading.Lock()

    # -- state --------------------------------------------------------------

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def entries(self) -> list[LedgerEntry]:
        return list(self._entries.values())

    def get(self, collective: str) -> list[LedgerEntry]:
        return [e for e in self._entries.values()
                if e.collective == collective]

    def bytes_for(self, collective: str) -> float:
        return sum(e.bytes_total for e in self.get(collective))

    def snapshot(self, *, roofline: bool = True) -> dict[str, dict]:
        """``{series_key: aggregate dict}`` — JSON-ready. When any series
        carries achieved latency (wall samples), each entry is joined with
        its physical roofline bound (``obs/roofline.py``): per-entry
        ``roofline_bound`` / ``achieved_over_bound`` fields plus one
        ``roofline_summary`` aggregate key (series keys always contain
        ``[``, so the summary key can never collide)."""
        with self._lock:
            out = {e.key: e.as_dict() for e in self._entries.values()}
        if roofline and any(d.get("wall_samples") for d in out.values()):
            from triton_distributed_tpu.obs import roofline as _roofline

            recs = _roofline.attribute(out)
            for key, rec in recs.items():
                out[key]["roofline_bound"] = rec.bound
                if rec.achieved_over_bound is not None:
                    out[key]["achieved_over_bound"] = round(
                        rec.achieved_over_bound, 4)
            summ = _roofline.summary(recs)
            if summ:
                out["roofline_summary"] = summ
        return out

    # -- recording ----------------------------------------------------------

    def record(self, collective: str, *, axis: str, world: int,
               nbytes: float, method: str = "", est_s: float | None = None,
               wall_s: float | None = None, traced: bool = False,
               count: int = 1, detail: dict | None = None) -> None:
        """``count`` calls of ``nbytes`` (and ``est_s``) each. ``detail``:
        static facts of the call site, kept beside the series' sums."""
        if not self.enabled:
            return
        key = (collective, method, axis, world)
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                e = self._entries[key] = LedgerEntry(
                    collective=collective, method=method, axis=axis,
                    world=world)
            if traced:
                e.traced_calls += count
            else:
                e.calls += count
            e.bytes_total += float(nbytes) * count
            if est_s is not None:
                e.est_s_total += float(est_s) * count
            if wall_s is not None:
                e.wall_s_total += float(wall_s)
                e.wall_samples += 1
            if detail:
                e.detail.update(detail)

    def record_traced(self, collective: str, *, axis: str, world: int,
                      nbytes: float, method: str = "",
                      est_s: float | None = None,
                      detail: dict | None = None) -> None:
        """Trace-time record for device-level entry points (see module
        docstring): one call, or the enclosing ``repeated`` trips of it,
        to the ledger and to every open ``gathering()``."""
        count = _SCOPE.repeat
        for sink in _SCOPE.sinks:
            sink.append(TracedRecord(collective, method, axis, world, count,
                                     float(nbytes) * count))
        self.record(collective, axis=axis, world=world, nbytes=nbytes,
                    method=method, est_s=est_s, traced=True, count=count,
                    detail=detail)

    def timed(self, fn, collective: str, *, axis: str, world: int,
              nbytes: float, method: str = "",
              est_s: float | None = None):
        """Run ``fn()`` and record wall time (blocking on the result). If
        ``fn`` turns out to be running under a trace (its output holds
        tracers), falls back to a traced record — trace-time wall clocks
        measure compilation, not the collective.

        When resilience hooks are installed (``set_resilience_hooks``),
        the pre-call hook fires first (fault injection: may raise
        ``TransientFault`` or sleep) and the execution runs under the
        watchdog-deadline context — this is the ``comm.<collective>``
        fault/watchdog site."""
        if _PRE_CALL_HOOK is not None:
            _PRE_CALL_HOOK(collective, axis=axis, world=world)
        ctx = (_DEADLINE_HOOK(collective) if _DEADLINE_HOOK is not None
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ctx:
            out = fn()
            if any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree_util.tree_leaves(out)):
                self.record_traced(collective, axis=axis, world=world,
                                   nbytes=nbytes, method=method, est_s=est_s)
                return out
            # The deadline covers the blocking wait too — a hung collective
            # hangs HERE, not at dispatch.
            jax.block_until_ready(out)
        self.record(collective, axis=axis, world=world, nbytes=nbytes,
                    method=method, est_s=est_s,
                    wall_s=time.perf_counter() - t0)
        return out


_LEDGER = CommLedger()

# Resilience hooks (installed via set_resilience_hooks, normally by
# triton_distributed_tpu.resilience.install_hooks). Both default None: the
# hot path pays one module-attribute check.
_PRE_CALL_HOOK = None   # fn(collective, *, axis, world) — may raise / sleep
_DEADLINE_HOOK = None   # fn(collective) -> context manager


def set_resilience_hooks(*, pre_call=None, deadline=None) -> None:
    """Install (or clear, with None) the fault-injection pre-call and
    watchdog-deadline hooks applied inside every ``timed()`` wrapper."""
    global _PRE_CALL_HOOK, _DEADLINE_HOOK
    _PRE_CALL_HOOK = pre_call
    _DEADLINE_HOOK = deadline


def get_ledger() -> CommLedger:
    return _LEDGER


def enabled() -> bool:
    return _LEDGER.enabled


def active() -> bool:
    """Should collective call sites route through ``timed()``? True when
    the ledger records OR a resilience hook needs to observe the call."""
    return (_LEDGER.enabled or _PRE_CALL_HOOK is not None
            or _DEADLINE_HOOK is not None)


def recording() -> bool:
    """Has a traced record a taker (the ledger or an open ``gathering``)?
    Device-level entry points whose byte count costs something to work
    out ask before they do."""
    return _LEDGER.enabled or bool(_SCOPE.sinks)


@contextlib.contextmanager
def gathering():
    """Yields a list that receives a ``TracedRecord`` for every traced
    record made inside the block on this thread, ledger enabled or not."""
    records: list[TracedRecord] = []
    prior = _SCOPE.sinks
    _SCOPE.sinks = prior + (records,)
    try:
        yield records
    finally:
        _SCOPE.sinks = prior


@contextlib.contextmanager
def repeated(trips: int):
    """The block traces the body of a loop of ``trips`` trips: a traced
    record made inside it stands for that many calls."""
    prior = _SCOPE.repeat
    _SCOPE.repeat = prior * int(trips)
    try:
        yield
    finally:
        _SCOPE.repeat = prior


def enable() -> None:
    _LEDGER.enable()


def disable() -> None:
    _LEDGER.disable()


def reset() -> None:
    _LEDGER.reset()


def snapshot() -> dict[str, dict]:
    return _LEDGER.snapshot()


def wall_s_total() -> float:
    """Total achieved collective wall seconds across every series — the
    efficiency ledger diffs this around each serving step to bucket the
    step's comm time. Cheap enough to call per step (one lock, one sum
    over a handful of series)."""
    with _LEDGER._lock:
        return sum(e.wall_s_total for e in _LEDGER._entries.values())


def record(collective: str, **kw) -> None:
    _LEDGER.record(collective, **kw)


def record_traced(collective: str, **kw) -> None:
    _LEDGER.record_traced(collective, **kw)


def timed(fn, collective: str, **kw):
    return _LEDGER.timed(fn, collective, **kw)


@contextlib.contextmanager
def ledger(reset_first: bool = False):
    """Scoped enable (restores the prior enabled state)."""
    if reset_first:
        _LEDGER.reset()
    prior = _LEDGER.enabled
    _LEDGER.enable()
    try:
        yield _LEDGER
    finally:
        _LEDGER.enabled = prior


def selfcheck(mesh=None, axis: str = "tp") -> dict:
    """Byte-accounting cross-check: run one all-gather, one
    reduce-scatter, one all-reduce and one EP all-to-all through the
    instrumented host wrappers and compare the ledger's byte counters
    against the perf model's analytical wire-byte counts — the acceptance
    invariant for the ledger (recorded == analytic for every collective
    family).

    Where the backend cannot lower the Pallas collectives (a CPU host
    without the TPU interpreter), the call is replayed analytically through
    ``record()`` with the same wire-byte formula, so the check still
    verifies the ledger's accounting path end to end; ``*_mode`` reports
    which regime ran. The caller's ledger state (enabled flag AND
    accumulated entries) is saved and restored around the check.
    """
    # Lazy imports: kernels/ imports this module at its top level.
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.allgather import all_gather
    from triton_distributed_tpu.kernels.allreduce import (
        all_reduce,
        choose_all_reduce_method,
    )
    from triton_distributed_tpu.kernels.ep_all_to_all import (
        AllToAllContext,
        all_to_all,
    )
    from triton_distributed_tpu.kernels.reduce_scatter import reduce_scatter
    from triton_distributed_tpu.runtime import perf_model as pm
    from triton_distributed_tpu.runtime.mesh import make_mesh

    if mesh is None:
        world = len(jax.devices())
        mesh = make_mesh({axis: world}, devices=jax.devices()[:world],
                         set_default=False)
    world = mesh.shape[axis]

    # Every per-device input, output and staging buffer below is <= 8 KB
    # at world=8: under the Pallas interpreter a collective holding 16 KB
    # deadlocks (the ceiling in tests/conftest.py), and the check compares
    # recorded with expected bytes of whatever arrays it is given.
    x_ag = jnp.ones((world, 4, 128), jnp.float32)
    ag_expected = pm.wire_bytes_all_gather(x_ag.nbytes // world, world)
    x_rs = jnp.ones((world, world * 2, 128), jnp.float32)
    rs_expected = pm.wire_bytes_reduce_scatter(x_rs.nbytes // world, world)
    # AR over a (world, world*2, 128) stacked input: method mirrors the
    # wrapper's own dispatch so expected bytes == recorded bytes by
    # construction of the SAME (method, nbytes) pair.
    x_ar = jnp.ones((world, max(world, 2) * 2, 128), jnp.float32)
    ar_method = choose_all_reduce_method(
        world, x_ar.nbytes // world, x_ar.shape[1])
    ar_expected = pm.wire_bytes_all_reduce(
        x_ar.nbytes // world, world, ar_method.value)
    # EP a2a at a tiny aligned geometry: (world, world, cap, 16) f32.
    a2a_ctx = AllToAllContext(capacity=8, hidden=16, axis=axis,
                              chunk_rows=8)
    x_a2a = jnp.ones((world, world, 8, 16), jnp.float32)
    a2a_counts = jnp.full((world, world), 8, jnp.int32)
    a2a_expected = pm.wire_bytes_all_to_all(x_a2a.nbytes // world, world)

    prior_entries = dict(_LEDGER._entries)
    checks: dict[str, dict] = {}

    def host_bytes(led: CommLedger, collective: str) -> float:
        """Host-level (timed / replayed) bytes only. A host wrapper may
        ALSO fire a device-level trace-time record for the same traffic
        (a2a's dispatch entry point inside the stacked wrapper): counting
        both would double the bytes. Traced series stand in only when no
        host record exists for the collective at all."""
        entries = led.get(collective)
        host = [e for e in entries if e.calls > 0]
        return sum(e.bytes_total for e in (host or entries))

    def run_one(name: str, collective: str, fn, expected: float,
                method: str) -> None:
        before = copy.deepcopy(_LEDGER._entries)
        try:
            jax.block_until_ready(fn())
            mode = "executed"
        except Exception:  # noqa: BLE001 — no Pallas lowering here
            # Drop whatever the failed attempt recorded at trace time —
            # the analytical replay below is the whole record.
            _LEDGER._entries = before
            record(collective, axis=axis, world=world, nbytes=expected,
                   method=method or "analytical")
            mode = "analytical"
        checks[name] = {"collective": collective,
                        "expected": float(expected), "mode": mode}

    try:
        with ledger(reset_first=True) as led:
            run_one("ag", "all_gather",
                    lambda: all_gather(x_ag, mesh=mesh, axis=axis),
                    ag_expected, "")
            run_one("rs", "reduce_scatter",
                    lambda: reduce_scatter(x_rs, mesh=mesh, axis=axis),
                    rs_expected, "")
            run_one("ar", "all_reduce",
                    lambda: all_reduce(x_ar, mesh=mesh, axis=axis,
                                       method=ar_method),
                    ar_expected, ar_method.value)
            run_one("a2a", "ep_all_to_all",
                    lambda: all_to_all(x_a2a, a2a_counts, ctx=a2a_ctx,
                                       mesh=mesh),
                    a2a_expected, "stacked")
            for c in checks.values():
                c["bytes"] = host_bytes(led, c["collective"])
            entries = led.snapshot()
    finally:
        _LEDGER._entries = prior_entries
    out: dict = {"world": world, "entries": entries}
    for name, c in checks.items():
        out[f"{name}_bytes"] = c["bytes"]
        out[f"{name}_expected"] = c["expected"]
        out[f"{name}_mode"] = c["mode"]
    out["consistent"] = all(c["bytes"] == c["expected"]
                            for c in checks.values())
    return out
