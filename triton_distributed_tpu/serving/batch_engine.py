"""Continuous-batching engine: ONE compiled step, slot churn as data.

The serving-side driver over ``models/engine.Engine``'s model + mesh: a
fixed bank of ``n_slots`` sequence slots runs through TWO jitted programs —

  decode step  (n_slots, 1)-token ids      — one token for every slot
  mixed step   n_slots + P * prefill_chunk — a DECODE block of one token a
               token positions               slot beside a PREFILL block of
                                             ``P = prefill_rows`` rows of
                                             ``prefill_chunk`` prompt
                                             tokens, in the same iteration
                                             (Orca-style iteration-level
                                             batching). The host DEALS the
                                             block's rows (``_run_mixed``):
                                             one to each prompt, the P
                                             oldest admitted first and the
                                             rest deferred; then the rows
                                             still free to the same
                                             prompts, oldest first, as
                                             consecutive chunks of the one
                                             sequence: a prompt alone takes
                                             up to ``P * prefill_chunk``
                                             tokens a step

— whose operands (active-slot mask, per-slot offsets, block tables,
per-slot seq_lens, the dealt rows) are plain DATA. Requests arriving,
finishing, getting preempted or re-admitted never change a shape, so each
step compiles exactly once for the slot bank (``trace_counts`` proves it;
the tests assert on it). The reference engine gets this from CUDA-Graph
replay over a fixed batch; here XLA executable replay plays that role with
the dynamism pushed into masks — the TPU-idiomatic translation.

One step is kept IN FLIGHT: ``step()`` dispatches step N+1 from what the
host knows by count and only then reads step N's tokens, so the host's
work between two steps runs while the device computes (the section
"iteration" below; docs/serving.md has the contract). A token is visible
in the call after the one that dispatched it; ``flush()``, and everything
that reads or moves requests between two steps, reads the step in flight
first. Where the next plan needs the tokens' values (speculation, an
installed fault plan, the NaN guard, an eviction) the step is read before
anything is planned behind it — the same loop, nothing left in flight.

KV lives in the block-paged ``KVPool`` (vLLM-style), so HBM holds
sequences at their actual lengths; when the pool runs dry the scheduler
evicts by recompute (``serving/scheduler.py``) and the victim's re-prefill
reproduces its greedy continuation exactly.

Bit-exactness contract (tests/test_serving.py): under greedy sampling the
slot-batched run emits the SAME tokens as N independent single-sequence
``Engine`` runs — masked cache positions contribute exact zeros, every
per-row op is row-independent, and chunked prefill attends causally so
later-chunk keys never influence earlier logits.

Resilience (resilience/, docs/resilience.md): the engine is an error
boundary, not a crash amplifier. A failing request is QUARANTINED — moved
to ``failed`` with ``Request.status='failed'`` and an error string — while
the batch keeps running; transient step/allocator faults retry with
bounded backoff; NaN/Inf logits are caught by a finite-mask the steps
compile in unconditionally. All of it is SPMD-safe by construction:
failure handling is host-side slot churn over the same (mask, tables,
offsets) DATA the compiled step already consumes, so no rank ever takes a
divergent in-program branch and the step shapes never change. With no
``FaultPlan`` installed and no watchdog attached the hot path pays one
attribute check per site and emits bit-identical tokens.
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
import functools
import json
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.layers import nn
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.sampling import finite_logits_mask, sample_token
from triton_distributed_tpu.obs import comm_ledger as _comm
from triton_distributed_tpu.obs import trace as _trace
from triton_distributed_tpu.obs.blackbox import Blackbox
from triton_distributed_tpu.obs.efficiency import EfficiencyLedger
from triton_distributed_tpu.obs.incident import IncidentEngine
from triton_distributed_tpu.obs.journey import JourneyRecorder
from triton_distributed_tpu.obs.slo import (
    BREACH,
    STATE_LEVEL,
    SLOEngine,
    default_serving_slo,
)
from triton_distributed_tpu.obs.trace import TailSampler
from triton_distributed_tpu.resilience import faults as _faults
from triton_distributed_tpu.resilience import guards as _guards
from triton_distributed_tpu.serving.kv_pool import (
    KVPool,
    blocks_needed,
    row_tokens,
)
from triton_distributed_tpu.serving.metrics import Metrics
from triton_distributed_tpu.serving.prefix_cache import RadixPrefixCache
from triton_distributed_tpu.serving.scheduler import Request, Scheduler
from triton_distributed_tpu.serving.speculative import as_speculative

# Token positions a mixed step may carry: ``n_slots`` decode positions and
# as many whole prefill rows of ``prefill_chunk`` as fit beside them
# (``BatchEngine.prefill_rows``: 7 at 32 slots and chunks of 64). Both ends
# cost (read on a v5e at 96, 224, 288, 352 and 480 positions; PERF.md
# section 6, PR 31). Every row of the block, live or not, goes through the
# linear layers, which are compute-bound past about 240 positions (197
# TFLOP/s / 819 GB/s / 2 bytes): +1.8 ms on every decoding client's token
# gap a row for Qwen3-1.7B, +3.4 ms for the latent/expert block. And a
# lower block prefills a waiting population in more steps: 32 contexts of
# 35,889 tokens took 225 steps through 3 rows (6.6 s), 116 through 7 (4.2
# s), 51 through the dense (32, 64) block this replaced (3.5 s). 512 is the
# least at which that bulk case costs about what it did. Those counts
# are of one row a prompt. Since a prompt takes the free rows too (PR 38)
# the ordering between heights holds and the bulk case got cheaper: while
# more than P prompts wait each holds one row, and the last, long ones
# take the rows the finished leave (the same 32 contexts: 112 steps by
# one row each, 83 with the deal, counted on the host; 2.8 -> 2.1 s of
# the cell's set-up on the chip). What changed most is the case the
# block is sized AGAINST: one prompt beside 32 decoding rows used one row
# of seven; now it uses what it can fill.
MIXED_STEP_TOKEN_BUDGET = 512

# The trailing windows every stats snapshot reports ("last 10 s" for the
# live dashboard's now-view, "last 5 min" for trends) over these series.
_SNAPSHOT_WINDOWS = ((10.0, "10s"), (300.0, "5m"))
_SNAPSHOT_SERIES = ("ttft_s", "tbt_s", "queue_wait_s")


# Why a step is read with nothing dispatched behind it (the label of the
# ``pipeline_flushes`` counter): the three standing conditions of
# ``BatchEngine._serial_reason``, an eviction, a caller that reads or moves
# requests between two steps, and a step with no row left to follow it.
FLUSH_REASONS = ("speculation", "fault_plan", "guard", "preempt", "caller",
                 "idle")


class StepBuildError(RuntimeError):
    """A compiled step failed on its FIRST call — where it is traced,
    lowered, compiled (Mosaic/XLA) and first given device memory. That is
    a fault of the program or of the device's capacity, not of a replica:
    ``Fleet``'s replica error boundary re-raises it instead of
    quarantining the replica and failing its requests (docs/resilience.md).
    The original exception is ``__cause__``."""


def is_resource_error(exc: BaseException) -> bool:
    """True for the runtime's out-of-memory error (``RESOURCE_EXHAUSTED``),
    which is a capacity fault at any step, first or not."""
    return "RESOURCE_EXHAUSTED" in str(exc)


@dataclasses.dataclass
class _Slot:
    """Host bookkeeping for one occupied batch slot."""

    req: Request
    admit_seq: int
    ctx: list[int]          # prompt + pre-preemption output: what to prefill
    offset: int = 0         # tokens written into the pool so far
    last_tok: int = 0       # pending decode input (valid once offset>=len(ctx))
    last_token_t: float | None = None   # wall of previous emitted token (TBT)
    in_flight: int = 0      # tokens dispatched steps emit for this row that
                            # the host has not read (0 or 1 between calls)

    @property
    def prefilling(self) -> bool:
        return self.offset < len(self.ctx)

    @property
    def ended(self) -> bool:
        """Every token the request may take is dispatched (a request ends
        by count): the row takes no part in a further step."""
        return self.req.remaining_new <= self.in_flight


class _Row(typing.NamedTuple):
    """One row of a dispatched step."""

    idx: int        # its slot
    slot: _Slot
    take: int       # token positions it takes
    written: int    # of them, tokens its offset moves by at dispatch (a
                    # verify row's is acceptance's to move, once read)
    emits: bool     # the step's token of this row is the request's next
    first: bool     # ... and the first of this residency (prefill ends)


@dataclasses.dataclass(eq=False)
class _Step:
    """A dispatched compiled step whose tokens the host has not read."""

    span: str       # "decode_step" | "mixed_step": its trace span
    nxt: object     # on the device: a token a slot, then the step_stats
    finite: object  # on the device: the finite mask of its logits
    greedy: object  # on the device: every position's argmax | None
    rows: list      # of _Row: the rows that took part
    attrs: dict     # attributes of the span
    counts: dict    # counters that move when the step is read
    eff: tuple | None   # what the efficiency ledger is told of it
    verify: dict    # speculation: slot -> (draft tokens, row of ``greedy``)

    @property
    def kind(self) -> str:
        """"decode" | "mixed"."""
        return self.span.removesuffix("_step")


class BatchEngine:
    """Continuous-batching server over an ``Engine``'s model/params/mesh.

    ``n_slots``    fixed batch width (must divide by the TP world in
                   dist/xla modes — the hidden states are batch-sharded).
    ``n_blocks``   KV pool size; defaults to full residency for all slots
                   (no preemption pressure). Size it below
                   ``n_slots * ceil(max_seq_len/block_size)`` to oversubscribe.
    ``prefill_chunk`` width of the mixed step's prefill block: the tokens
                   of prompt ONE of its rows holds. The block's height
                   ``prefill_rows`` is derived: what fits beside the
                   decode block in ``MIXED_STEP_TOKEN_BUDGET`` positions
                   or, with ``speculative``, ``n_slots`` (every decode
                   row may be a verify row of several tokens). A prompt
                   takes one row a step and, where rows are free, as
                   many more as it can fill (``_run_mixed``); one row a
                   slot is the limit for a verify row and while
                   ``prefill_budget`` is below the chunk.
    ``admission_pressure`` fraction of the pool that must be free to admit
                   NEW requests while at least one slot is running (0.0 =
                   off). Backpressure trades queue wait for fewer
                   preemptions when the pool is oversubscribed; it never
                   pauses admission into an idle engine (no deadlock).
    ``retry``      ``RetryPolicy`` for transient step/allocator faults
                   (default: 3 retries, exponential backoff).
    ``nan_guard``  quarantine requests whose logits go non-finite even
                   with no fault plan installed. The finite mask itself is
                   ALWAYS compiled into the steps (SPMD safety — see
                   module docstring); this flag only enables the host-side
                   check of it.
    ``kv_dtype``   wire format of the KV pool: None (default) stores KV
                   in the model dtype; "int8"/"fp8" store quantized rows
                   plus per-(row, kv-head) f32 scales (``KVPool``: the
                   pool's state then has two scale arenas; the steps
                   take and return the state whole, whatever its
                   format). Quantization happens at append time inside
                   the step; dequantization happens inside the fused
                   kernel's VMEM staging (or on the gathered view in
                   gather mode), so pool HBM traffic shrinks by the
                   dtype ratio. Same two traces, same shapes —
                   ``trace_counts`` stays {1,1}.
    ``paged_attn`` "fused" (default): every step shape — decode, chunked
                   prefill, ragged mixed — walks the block table inside
                   the Pallas kernel, one pass over the pool bytes.
                   "gather": the materialized-view reference path
                   (``paged_gather_kv``), the escape hatch the fused kernel
                   is verified token-identical against. Baked into the
                   compiled steps at construction.
    ``prefix_cache`` attach a ``RadixPrefixCache`` (default True): finished
                   requests donate their KV blocks to a radix tree over
                   token prefixes, and admissions that share a cached
                   prefix adopt those blocks and start chunked prefill at
                   the match point. Pure host-side data — a hit changes
                   the (offsets, block_tables) operands, never a shape —
                   so ``trace_counts`` stays {1,1} and greedy output stays
                   bit-identical to a cold pool (the KV a request would
                   have computed IS the cached KV, token for token).
                   ``engine.prefix_cache.enabled = False`` toggles it off
                   at runtime without touching compiled state. A model
                   some of whose layers keep a fixed-size state a slot
                   (``config.slot_state_shapes``) or a window of rows a
                   slot (``config.n_window_layers``) gets none, whatever
                   is asked (``KVPool.prefix_cacheable``): blocks hold
                   rows, not the state or the window at their boundary,
                   so every admission of such a model prefills from
                   offset 0 (docs/serving.md).

    Always-on observability (bounded, defaults ON; what it costs a step
    on the chip is not measured):
    ``windowed_metrics`` feed every counter/histogram into trailing-window
                   rings so ``stats_snapshot()`` and the SLO engine can
                   answer "p99 over the last 10 s / 5 min".
    ``blackbox``   flight recorder of structured lifecycle events
                   (admit/preempt/finish/quarantine/fault/SLO); True =
                   default capacity, an int = that capacity, False = off.
    ``tail_sampling`` per-request trace sampling that always keeps
                   slow/errored requests plus a deterministic head-sampled
                   fraction; pass a configured ``TailSampler`` or False.
    ``attach_slo()`` adds the OK/WARN/BREACH state machine on top; a
                   BREACH fires the attached watchdog's snapshot path.
    ``step()``     dispatches one compiled step and reads the one the call
                   before dispatched (module docstring): tokens, finished
                   requests and the step counters appear a call after the
                   dispatch. ``flush()`` reads the step in flight;
                   ``run()``, ``drain()``, ``finished`` and ``failed``
                   call it. No option: ``speculative``, ``nan_guard`` and
                   an installed fault plan read every step at once, as
                   ``stats_snapshot()["pipeline"]`` counts.
    ``speculative`` draft-then-verify decoding (serving/speculative.py):
                   True = n-gram drafter + default adaptive-k controller,
                   or pass a ``Drafter`` / a ``Speculative`` plan. The
                   drafter proposes up to k tokens per decode slot, the
                   ONE compiled mixed step verifies them as a ragged row
                   (``q_lens = 1 + proposed`` — pure seq_lens data, zero
                   retraces), host-side longest-prefix acceptance emits
                   the accepted drafts plus the model's own bonus token,
                   and ``KVPool.truncate`` rolls back the rejected
                   suffix. Greedy output stays bit-identical to the
                   non-speculative engine (the bonus token IS what
                   one-at-a-time decode would have emitted), so
                   speculation requires ``temperature == 0.0``.
    """

    # Driven-continuity parameters for the incident engine's efficiency-
    # trio signals: a tick gap beyond _INC_GAP_S (or an idle step) marks
    # the engine not-continuously-driven, and the trio stays suppressed
    # until _INC_WINDOW_S of uninterrupted busy ticks refill the rolling
    # window (matches the 10 s windowed reads in _incident_tick).
    _INC_GAP_S = 0.5
    _INC_WINDOW_S = 10.0

    def __init__(self, engine: Engine, *, n_slots: int = 8,
                 n_blocks: int | None = None, block_size: int = 16,
                 prefill_chunk: int = 32, max_seq_len: int | None = None,
                 kv_dtype=None,
                 seed: int = 0, admission_pressure: float = 0.0,
                 retry: _guards.RetryPolicy | None = None,
                 nan_guard: bool = False, paged_attn: str = "fused",
                 prefix_cache: bool = True, windowed_metrics: bool = True,
                 blackbox: bool | int = True,
                 tail_sampling: bool | TailSampler = True,
                 journey: bool | JourneyRecorder = True,
                 efficiency: bool | EfficiencyLedger = True,
                 incidents: bool | IncidentEngine = True,
                 speculative=False):
        if paged_attn not in ("fused", "gather"):
            raise ValueError(
                f"paged_attn must be 'fused' or 'gather', got {paged_attn!r}")
        self.paged_attn = paged_attn
        self.spec = as_speculative(speculative)
        if self.spec is not None and engine.temperature != 0.0:
            raise ValueError(
                "speculative decoding requires greedy sampling "
                f"(temperature == 0.0, got {engine.temperature}): the "
                "longest-prefix acceptance rule is only lossless under "
                "argmax")
        self.engine = engine
        world = engine.mesh.shape[engine.model.axis]
        if engine.decode_mode in ("dist", "xla") and n_slots % world:
            raise ValueError(f"n_slots {n_slots} not divisible by TP world "
                             f"{world} (required in dist/xla modes)")
        self.n_slots = n_slots
        self.prefill_chunk = prefill_chunk
        # Rows of the mixed step's prefill block (module docstring).
        self.prefill_rows = n_slots if self.spec is not None else min(
            n_slots, max(1, (MIXED_STEP_TOKEN_BUDGET - n_slots)
                         // prefill_chunk))
        # Runtime chunked-prefill token budget: how much of the compiled
        # ``prefill_chunk`` ids width a mixed step may actually consume per
        # prefill-block row. The adaptive controller (serving/controller.py)
        # moves this as pure per-step data — ``seq_lens`` narrows, the ids
        # shape never changes, so the compiled mixed step is untouched.
        # Below the chunk's width it also holds a prompt to ONE row a step.
        self.prefill_budget = prefill_chunk
        max_seq_len = max_seq_len or engine.max_length
        if n_blocks is None:
            n_blocks = n_slots * blocks_needed(
                max_seq_len, block_size, row_tokens(engine.config))
        self.pool = KVPool(engine.config, n_blocks=n_blocks,
                           block_size=block_size, max_seq_len=max_seq_len,
                           mesh=engine.mesh, axis=engine.model.axis,
                           kv_dtype=kv_dtype, n_slots=n_slots,
                           max_take=self.prefill_rows * prefill_chunk)
        self.scheduler = Scheduler()
        self.metrics = Metrics(windowed=windowed_metrics)
        if blackbox:
            cap = blackbox if isinstance(blackbox, int) \
                and not isinstance(blackbox, bool) else 1024
            self.blackbox = Blackbox(capacity=cap)
        else:
            self.blackbox = None
        # The scheduler reports its own decisions (admit batches) into the
        # same flight recorder — pure data, no import cycle.
        self.scheduler.event_sink = (self.blackbox.record
                                     if self.blackbox is not None else None)
        if isinstance(tail_sampling, TailSampler):
            self.sampler = tail_sampling
        else:
            self.sampler = TailSampler(seed=seed) if tail_sampling else None
        # Request-journey recorder (obs/journey.py) — always-on causal
        # timelines + latency attribution. A Fleet replaces this with ONE
        # shared recorder across its replicas so a cross-replica requeue
        # stays a single journey.
        if isinstance(journey, JourneyRecorder):
            self.journey = journey
        else:
            self.journey = JourneyRecorder() if journey else None
        # Efficiency ledger (obs/efficiency.py): decomposes every step's
        # wall interval into compute/hbm/comm/stall/bubble fractions and
        # meters per-tenant cost. Pure host-side arithmetic on counters the
        # step already produces — it never touches compiled state, so the
        # bench --serve --efficiency arm can gate bit-identical outputs and
        # trace_counts {1,1} with the ledger on.
        if isinstance(efficiency, EfficiencyLedger):
            self.efficiency = efficiency
        elif efficiency:
            self.efficiency = EfficiencyLedger()
        else:
            self.efficiency = None
        # Incident engine (obs/incident.py): deterministic online anomaly
        # detectors over the live signal set, with cross-layer triage into
        # a ranked suspect list when one trips. Step-paced (its observe
        # ordinal is the clock) and host-side only — same trace, same
        # incidents, trace_counts untouched.
        if isinstance(incidents, IncidentEngine):
            self.incidents = incidents
        elif incidents:
            self.incidents = IncidentEngine()
        else:
            self.incidents = None
        # Bounded SLO transition log the incident triage reads (cursor-
        # indexed, so a plain append-only list — transitions are rare).
        self._slo_transition_log: list[dict] = []
        # Driven-continuity tracking for the efficiency-trio signals: the
        # tick before the first, after an idle step, or after an external
        # pause marks the engine not-continuously-driven (see
        # _incident_tick).
        self._inc_last_tick: float | None = None
        self._inc_idle_mark = 0.0
        # Dtype widths feeding step_hbm_bytes: activations/weights run in
        # the model dtype (tiny test configs f32; real configs bf16); the
        # KV pool may be narrower (kv_dtype="int8"/"fp8"), in which case
        # the per-row scale arenas are billed too (kv_scales=True).
        self._eff_itemsize = int(jnp.dtype(engine.config.dtype).itemsize)
        self._eff_kv_itemsize = int(self.pool.kv_dtype.itemsize)
        # Optional zero-arg callable returning a kprobe ``stall_summary``
        # dict; when probes are wired it refines the ledger's stall bucket
        # into dma_wait / sem_spin detail (never reclassifies).
        self.eff_stall_source = None
        self._slo = None
        self._slo_eval_interval_s = 1.0
        self._slo_next_eval = 0.0
        self._controller = None
        self._stats_stream = None
        self._stats_interval_s = 1.0
        self._stats_next_emit = 0.0
        # A model with per-slot state gets NO prefix cache, asked for or
        # not: a cached block holds rows, not the state at its boundary, so
        # a request that adopted blocks would start past tokens its state
        # has never seen. Every such admission starts at offset 0, where
        # the layer that keeps the state starts it from zero. Nor does a
        # model with window layers: a cached block does not carry their
        # last rows at its boundary (``KVPool.prefix_cacheable``), and an
        # evicted request's ring is another's by the time it comes back,
        # so admission and re-admission alike prefill from offset 0.
        self.prefix_cache = (RadixPrefixCache(self.pool,
                                              metrics=self.metrics)
                             if prefix_cache and self.pool.prefix_cacheable
                             else None)
        self.trace_counts = {"decode": 0, "prefill": 0}
        # What the collectives of one execution of each compiled step
        # move, written when the step is traced (``_build_steps``).
        self.collectives = {kind: {"collective_calls": 0, "ici_bytes": 0,
                                   "by_collective": {}}
                            for kind in ("decode", "prefill")}
        # Fault sites ("engine.decode"/"engine.prefill") whose jitted step
        # has returned at least once; until then a failure of the call is a
        # StepBuildError (see ``_call_step``).
        self._steps_built: set[str] = set()
        self._slots: list[_Slot | None] = [None] * n_slots
        self._admit_seq = 0
        self._req_counter = 0
        self._finished: dict[object, Request] = {}
        self._failed: dict[object, Request] = {}
        self._key = jax.random.PRNGKey(seed)
        # resilience state
        self.admission_pressure = admission_pressure
        self.retry = _guards.RetryPolicy() if retry is None else retry
        self.nan_guard = nan_guard
        self._watchdog = None
        self._heartbeat = None
        self._step_deadline_s = None
        # The always-present logit-corruption operand: zeros on every
        # normal step; a fault directive swaps in a row of NaN. One cached
        # device array, so the disabled path never re-uploads.
        self._corrupt0 = jnp.zeros((n_slots,), jnp.float32)
        # Per-step draft proposals, slot index -> token list; rebuilt by
        # ``step()`` every iteration (never carried across steps).
        self._proposals: dict[int, list[int]] = {}
        # The dispatched step whose tokens the host has not read (at most
        # one), and the newest step's token vector on the device: the
        # operand a decode row's input comes from while its token is in
        # flight. Zeros, placed as a step's output is, until a step ran.
        self._inflight: _Step | None = None
        self._prev0 = self._prev = jax.device_put(
            np.zeros((n_slots + len(engine.model.step_stats),), np.int32),
            jax.sharding.NamedSharding(engine.mesh,
                                       jax.sharding.PartitionSpec()))
        # Write-ahead journal (resilience/checkpoint.py), attached by
        # ``Fleet.attach_journal``: emit/finish/fail records flow through
        # ``_journal`` below. None = journaling off (zero overhead).
        self.journal = None
        if self.incidents is not None:
            self._wire_incident_sources(self.incidents)
        self._build_steps()

    # -- compiled steps -----------------------------------------------------

    def _build_steps(self):
        eng = self.engine
        V = eng.config.vocab_size
        # With speculation the ONE mixed step also emits the all-position
        # argmax continuation (``greedy``) — baked into the single trace,
        # so verify steps, chunked prefill, and plain mixed iterations all
        # share it and trace_counts stays {1,1}.
        kw = dict(paged_attn=self.paged_attn, state_specs=self.pool.specs)
        sm_dec = eng._make_sm(eng.decode_mode, paged="decode", **kw)
        sm_pre = eng._make_sm(eng.prefill_mode, paged="prefill",
                              spec_verify=self.spec is not None, **kw)
        temperature, top_p = eng.temperature, eng.top_p
        trace_counts = self.trace_counts
        collectives = self.collectives

        def counted(kind, sm, *operands):
            """``sm(*operands)``, with the comm ledger's trace-time
            records of it (``perf_model``'s wire bytes, from the
            device-level entry points of the kernels) summed by collective
            and kept as step ``kind``'s: a side effect of the trace, like
            ``trace_counts``. Calls an execution of the step, and bytes a
            chip sends in them; a mesh of one records none."""
            with _comm.gathering() as records:
                out = sm(*operands)
            by_collective: dict = {}
            for r in records:
                if r.world > 1:
                    c = by_collective.setdefault(
                        r.collective, {"collective_calls": 0, "ici_bytes": 0})
                    c["collective_calls"] += r.calls
                    c["ici_bytes"] += int(r.nbytes)
            collectives[kind] = {
                "collective_calls": sum(c["collective_calls"]
                                        for c in by_collective.values()),
                "ici_bytes": sum(c["ici_bytes"]
                                 for c in by_collective.values()),
                "by_collective": by_collective}
            return out

        # Both steps take the pool's state (``KVPool.state``, whatever its
        # format) as their ONE donated operand and return the fixed record
        # ``(nxt, finite, greedy | None, state)``.
        #
        # ``corrupt`` (n_slots,) f32 is zeros on the healthy path: adding it
        # to the logits is an exact no-op for sampling, and swapping NaN
        # into one row on the host is how fault injection poisons a slot
        # WITHOUT a second compiled variant. ``finite`` is the matching
        # always-compiled guard (models/sampling.finite_logits_mask): every
        # rank computes it every step, only the host decides what to do.
        # NaN injected at the last position only poisons ``nxt``; a REAL
        # non-finite at an interior verify position propagates through
        # causal attention to the last position, so the row-level
        # ``finite`` mask covers ``greedy`` too.
        #
        # A model with ``step_stats`` (device-side counts of the step, a
        # few int32) has them appended to ``nxt``, so they reach the host
        # in the transfer that brings the tokens and cost no further sync
        # (``_take_stats`` splits them off).

        # ``fed = (prev, from_prev)``: the token vector the step before
        # returned, still on the device, and a host-made mask of the
        # decode rows whose input token is in it and not yet on the host
        # (``step()``); every other row's token is the host's ``tok``.

        def feed(tok, fed):
            prev, from_prev = fed
            return jnp.where(from_prev, prev[:tok.shape[0]], tok)

        def sample(logits, aux, corrupt, key):
            logits = logits + corrupt[:, None]
            finite = finite_logits_mask(logits)
            nxt = sample_token(logits, key, temperature=temperature,
                               top_p=top_p)
            if "stats" in aux:
                nxt = jnp.concatenate([nxt, aux["stats"]])
            return nxt, finite, aux.get("greedy")

        @functools.partial(jax.jit, donate_argnums=(2,))
        def decode_step(params, tok, state, offsets, block_tables,
                        slot_mask, corrupt, key, fed):
            # Trace-time side effect: counts COMPILATIONS, not calls — the
            # one-compile-across-churn guarantee the tests assert on.
            trace_counts["decode"] += 1
            ids = jnp.clip(feed(tok, fed), 0, V - 1)[:, None]
            logits, aux, state = counted("decode", sm_dec, params, ids,
                                         state, offsets, block_tables,
                                         slot_mask)
            return *sample(logits, aux, corrupt, key), state

        @functools.partial(jax.jit, donate_argnums=(2,))
        def mixed_step(params, ids, state, offsets, block_tables, slot_mask,
                       seq_lens, corrupt, key, fed):
            # ``ids`` is the triple (tok (n_slots,), chunk (prefill_rows,
            # prefill_chunk), dealt (prefill_rows, 3)): the decode block,
            # the prefill block, and whose each of its rows is, as
            # ``_run_mixed`` dealt them (``nn.paged_token_blocks``).
            trace_counts["prefill"] += 1
            tok, chunk, dealt = ids
            ids = (jnp.clip(feed(tok, fed), 0, V - 1),
                   jnp.clip(chunk, 0, V - 1), dealt)
            logits, aux, state = counted("prefill", sm_pre, params, ids,
                                         state, offsets, block_tables,
                                         slot_mask, seq_lens)
            return *sample(logits, aux, corrupt, key), state

        self._decode_step = decode_step
        self._mixed_step = mixed_step

    def share_steps_from(self, other: "BatchEngine") -> None:
        """Adopt ``other``'s compiled step callables (elastic spawn,
        ``Fleet.spawn``): both engines wrap the SAME model ``Engine``, so
        the jitted closures — keyed on operand shapes, which identical
        construction parameters make identical — are reusable as-is, and
        a spawned replica serves its first token with zero retraces.

        ``trace_counts`` is shared as the SAME dict object: the closures
        captured it at trace time, so per-replica counts read {1,1} on
        every sharer and the per-replica retrace formula
        (decode+prefill-2) sums to zero fleet-wide. Our own never-called
        closures from ``_build_steps`` are dropped untraced (jax.jit is
        lazy — no compilation happened for them)."""
        if other.engine is not self.engine:
            raise ValueError("share_steps_from requires the same model "
                             "Engine (one-model fleet design)")
        def pool_format(be):
            # Structure, shapes and dtypes of the donated operand.
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                be.pool.state)

        same = (self.n_slots == other.n_slots
                and self.prefill_chunk == other.prefill_chunk
                and self.prefill_rows == other.prefill_rows
                and self.paged_attn == other.paged_attn
                and pool_format(self) == pool_format(other)
                and (self.spec is None) == (other.spec is None))
        if not same:
            raise ValueError("share_steps_from requires identical step "
                             "geometry (n_slots/prefill_chunk/paged_attn/"
                             "pool format/speculation)")
        self._decode_step = other._decode_step
        self._mixed_step = other._mixed_step
        self.trace_counts = other.trace_counts
        self.collectives = other.collectives
        self._steps_built = other._steps_built

    def _next_key(self):
        if self.engine.temperature == 0.0:
            return None        # greedy: sample_token never touches the key
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- resilience plumbing ------------------------------------------------

    @property
    def _guarding(self) -> bool:
        return _faults._PLAN is not None or self.nan_guard

    def attach_watchdog(self, wd, *, step_deadline_s: float | None = None,
                        heartbeat_interval_s: float | None = None,
                        monitor: bool = False):
        """Wire a ``resilience.Watchdog`` into the serving loop: every
        compiled-step dispatch runs under ``deadline('serving_step',
        step_deadline_s)``, each completed step beats a heartbeat, and the
        watchdog's breach snapshots pull ``resilience_snapshot()`` (metrics
        + the in-flight request table). Returns ``wd``."""
        wd.snapshot_provider = self.resilience_snapshot
        self._watchdog = wd
        self._step_deadline_s = step_deadline_s
        if heartbeat_interval_s is not None:
            self._heartbeat = wd.heartbeat(
                "serving_step", interval_s=heartbeat_interval_s,
                monitor=monitor)
        return wd

    def attach_slo(self, objectives=None, *,
                   eval_interval_s: float = 1.0) -> SLOEngine:
        """Attach the OK/WARN/BREACH state machine: ``objectives`` (default
        ``obs.slo.default_serving_slo()``) are evaluated every
        ``eval_interval_s`` seconds of serving-loop time, piggybacked on
        ``step()`` — no threads. Transitions land in metrics
        (``slo_state{objective=}`` gauges, ``slo_transitions`` counters),
        the blackbox, and the tracer; a transition INTO BREACH increments
        ``slo_breaches`` and fires the attached watchdog's ``snapshot``
        (reason ``slo-breach:<objective>``) so an SLO violation produces
        the full forensic bundle. Requires windowed metrics."""
        if not self.metrics.windowed:
            raise ValueError("attach_slo needs windowed metrics — construct "
                             "BatchEngine(windowed_metrics=True)")
        if objectives is None:
            objectives = default_serving_slo()
        self._slo = SLOEngine(objectives, self.metrics,
                              on_transition=self._on_slo_transition)
        self._slo_eval_interval_s = float(eval_interval_s)
        self._slo_next_eval = 0.0
        return self._slo

    @property
    def slo(self) -> SLOEngine | None:
        return self._slo

    def attach_controller(self, controller=None, **kwargs):
        """Attach the adaptive control plane (serving/controller.py),
        piggybacked on ``step()`` the way ``attach_slo`` is: every step the
        controller observes (SLO level, queue, row mix, pool headroom) and
        moves its knobs — ``prefill_budget``, ``admission_pressure``,
        cache reclaim — as pure per-step data (zero retraces). Pass a
        pre-built ``Controller`` or kwargs for one; returns it. Fleet
        deployments should attach at ``Fleet`` scope instead (one
        controller per plant)."""
        from triton_distributed_tpu.serving.controller import Controller
        if controller is None:
            controller = Controller(engine=self, **kwargs)
        self._controller = controller
        return controller

    @property
    def controller(self):
        return self._controller

    def _on_slo_transition(self, obj, old: str, new: str, detail: dict):
        self.metrics.inc("slo_transitions",
                         labels={"objective": obj.name, "to": new})
        self.metrics.set_gauge("slo_state", STATE_LEVEL[new],
                               labels={"objective": obj.name})
        if self.blackbox is not None:
            self.blackbox.record("slo", objective=obj.name, old=old,
                                 new=new, fast=detail["fast"]["value"],
                                 slow=detail["slow"]["value"])
        _trace.instant("slo_transition", objective=obj.name, old=old,
                       new=new)
        if self.journey is not None:
            self.journey.global_event("slo", objective=obj.name, old=old,
                                      new=new)
        self._slo_transition_log.append(
            {"objective": obj.name, "old": old, "new": new})
        if new == BREACH:
            self.metrics.inc("slo_breaches")
            if self._watchdog is not None:
                self._watchdog.snapshot(
                    f"slo-breach:{obj.name}",
                    extra={"slo_detail": detail})
            if self.incidents is not None:
                # A breach IS an incident — open it immediately (the SLO
                # engine already burned its windows getting here) wrapping
                # a compact summary of the same forensic bundle the
                # watchdog snapshot carries.
                self.incidents.on_slo_breach(
                    obj.name, detail, forensic=self.resilience_snapshot())

    def stream_stats(self, path: str, *, interval_s: float = 1.0) -> None:
        """Append one ``stats_snapshot()`` JSON line to ``path`` every
        ``interval_s`` seconds of serving-loop time (piggybacked on
        ``step()``) — the feed ``tools/serve_top.py --stats-jsonl``
        tails. Pass ``path=None`` to stop."""
        self._stats_stream = path
        self._stats_interval_s = float(interval_s)
        self._stats_next_emit = 0.0

    def _obs_tick(self):
        """Per-step observability housekeeping: SLO evaluation and the
        stats stream, each on its own interval. One monotonic read and two
        comparisons when neither is due."""
        if self._slo is None and self._stats_stream is None:
            return
        now = time.monotonic()
        if self._slo is not None and now >= self._slo_next_eval:
            self._slo_next_eval = now + self._slo_eval_interval_s
            self._slo.evaluate(now)
        if self._stats_stream is not None and now >= self._stats_next_emit:
            self._stats_next_emit = now + self._stats_interval_s
            with open(self._stats_stream, "a") as f:
                f.write(json.dumps(self.stats_snapshot(), default=str)
                        + "\n")

    def _wire_incident_sources(self, inc: IncidentEngine) -> None:
        """Hand the incident engine its cross-layer evidence feeds as
        zero-arg callables. Everything resolves through ``self`` lazily —
        the controller and watchdog attach after construction, and the
        fault plane is a context-scoped module global — and everything is
        polled only when an incident actually trips (triage time), never
        per step."""
        inc.fault_log_source = lambda: (
            p.log if (p := _faults.get_plan()) is not None else ())
        if self.blackbox is not None:
            inc.blackbox_source = lambda: (
                self.blackbox.n_recorded,
                self.blackbox.dump(last=64)["events"])
        inc.controller_source = lambda: (
            self._controller.action_log
            if self._controller is not None else ())
        inc.slo_source = lambda: self._slo_transition_log
        if self.efficiency is not None:
            inc.efficiency_source = lambda: (
                self.efficiency.stats()["worst_bubble"])
        if self.journey is not None:
            inc.journey_source = lambda: (
                self.journey.stats().get("slowest", ()))
        inc.comm_source = lambda: (
            _comm.snapshot() if _comm.enabled() else {})

    def _incident_tick(self, busy: bool = True) -> None:
        """Feed the incident engine one step's signal bundle. Absent
        subsystems simply never feed their signal — the detectors skip
        missing keys. Windowed reads stay cheap (bucket-count merges, no
        sample storage); the bench --serve --incidents arm gates the total
        under 5% of step time.

        The efficiency trio (mfu/mbu/bubble_frac) is fed only after the
        engine has been CONTINUOUSLY driven for a full window: the ledger
        bills any external pause (idle polling, a caller that stopped
        stepping, bench interleaving) to the next step's bubble, and the
        rolling window then reads ~the gap fraction for a further 10 s —
        a driving-pattern artifact, not a host pathology. Genuine host
        stalls accumulate as many sub-threshold per-step gaps and still
        feed through; the sample-based latency quantiles and the failure
        counters are immune and stay always-on."""
        inc = self.incidents
        if inc is None:
            return
        now = time.monotonic()
        prev = self._inc_last_tick
        self._inc_last_tick = now
        if not busy or prev is None or now - prev > self._INC_GAP_S:
            self._inc_idle_mark = now
        driven = now - self._inc_idle_mark >= self._INC_WINDOW_S
        sig: dict = {}
        if self.metrics.windowed:
            for series, name in (("tbt_s", "tbt_p99_s"),
                                 ("queue_wait_s", "queue_wait_p99_s")):
                ws = self.metrics.window_stats(series, 10.0)
                if ws is not None and ws.count:
                    sig[name] = ws.quantile(99)
            ws = self.metrics.window_stats("spec_accept_ratio", 10.0)
            if ws is not None and ws.count:
                sig["accept_rate"] = ws.mean
        eff = self.efficiency
        if eff is not None and eff.steps and driven:
            mfu, mbu = eff.mfu(10.0), eff.mbu(10.0)
            if mfu or mbu:      # window has accounted steps
                sig["mfu"] = mfu
                sig["mbu"] = mbu
                sig["bubble_frac"] = eff.bubble_frac(10.0)
        if _comm.enabled():
            snap = _comm.snapshot()
            ratios = [row["achieved_over_est"] for row in snap.values()
                      if row.get("achieved_over_est") is not None]
            if ratios:
                sig["achieved_over_est"] = max(ratios)
        sig["requests_failed"] = self.metrics.counters.get(
            "requests_failed", 0.0)
        inc.observe(sig)

    def _window_summary(self) -> dict:
        """Trailing-window latency stats over the snapshot windows (empty
        when the registry isn't windowed)."""
        if not self.metrics.windowed:
            return {}
        out: dict = {}
        for w_s, label in _SNAPSHOT_WINDOWS:
            d = {}
            for name in _SNAPSHOT_SERIES:
                w = self.metrics.window(name, w_s)
                if w:
                    d[name] = w
            out[label] = d
        return out

    def stats_snapshot(self) -> dict:
        """One JSON-able frame of live serving state — what ``serve_top``
        renders and ``stream_stats`` emits: occupancy, pool, throughput
        counters, trailing-window percentiles, SLO verdicts, and the
        bounded-telemetry drop counters."""
        m = self.metrics.as_dict()
        tracer_dropped = _trace.dropped_spans()
        self.metrics.set_gauge("trace_dropped_spans", tracer_dropped)
        snap = {
            "t": round(time.monotonic(), 3),
            "wall_time": round(time.time(), 3),
            "slots": {
                "active": sum(s is not None for s in self._slots),
                "total": self.n_slots,
            },
            "queue_depth": len(self.scheduler),
            "pool": {"n_blocks": self.pool.n_blocks,
                     "n_free": self.pool.n_free,
                     "n_used": self.pool.n_used,
                     "n_cached": self.pool.n_cached,
                     "n_reclaimable": self.pool.n_reclaimable,
                     "slot_state_bytes": self.pool.slot_state_bytes,
                     "window_bytes": self.pool.window_bytes},
            "counters": {k: m.get(k, 0.0) for k in (
                "requests_admitted", "requests_completed",
                "requests_failed", "tokens_generated", "preemptions",
                "admission_backpressure", "slo_breaches")},
            "windows": self._window_summary(),
            "trace_dropped_spans": tracer_dropped,
            # Compiles of each step, and the arithmetic each shape of the
            # fused paged-attention call took (static a call site, so
            # recorded when the shape is traced).
            "trace_counts": dict(self.trace_counts),
            "paged_arithmetic": nn.fused_paged_arithmetic(),
            # What one execution of each step moves over the mesh, by
            # collective (``_build_steps``): zero on a mesh of one.
            "collectives": copy.deepcopy(self.collectives),
            # Steps dispatched while the step before was still unread,
            # and the times a step was read with nothing dispatched
            # behind it, by what made it so.
            "pipeline": {
                "steps_overlapped": m.get("steps_overlapped", 0.0),
                "flushes": {r: m[k] for r in FLUSH_REASONS if (
                    k := f"pipeline_flushes{{reason={r}}}") in m},
            },
            # The mixed step's prefill block: its compiled height, the
            # steps that ran it, their live rows, those of them that were
            # a slot's second or later (the deal's second pass), and the
            # rows that waited for a place.
            "prefill_block": {"rows": self.prefill_rows, **{
                k: m.get(k, 0.0) for k in (
                    "prefill_steps", "prefill_rows_filled",
                    "prefill_rows_extra", "prefill_rows_deferred",
                    "mixed_step_tokens")}},
        }
        # A pool whose rows stand for several tokens each says how many,
        # and the rows its live sequences hold (whole chunks written).
        if self.pool.row_tokens != 1:
            snap["pool"].update(
                row_tokens=self.pool.row_tokens,
                summary_rows_held=sum(
                    s.offset // self.pool.row_tokens
                    for s in self._slots if s is not None))
        # A model whose layers are of several kinds says how many of each
        # (what the ``step_stats`` counts of a step are sums over).
        kinds = getattr(self.engine.model, "layer_counts", None)
        if kinds:
            snap["layers"] = dict(kinds)
        # ... and one with routed experts what they are made of: the score
        # form, the expert form, where the router reads.
        forms = getattr(self.engine.model, "moe_forms", None)
        if forms:
            snap["moe"] = dict(forms)
        lookups = m.get("prefix_lookups", 0.0)
        if lookups:
            snap["prefix_hit_rate"] = round(
                m.get("prefix_hits", 0.0) / lookups, 4)
        if self._slo is not None:
            snap["slo"] = {"states": self._slo.verdicts(),
                           "breaches": self._slo.n_breaches}
        if self._controller is not None:
            snap["controller"] = self._controller.stats()
        if self.blackbox is not None:
            snap["blackbox"] = {"len": len(self.blackbox),
                                "recorded": self.blackbox.n_recorded,
                                "dropped": self.blackbox.n_dropped}
        if self.sampler is not None:
            snap["sampler"] = self.sampler.stats()
        if self.journey is not None:
            snap["journey"] = self.journey.stats()
        if self.efficiency is not None:
            snap["efficiency"] = self.efficiency.stats()
        if self.incidents is not None:
            snap["incidents"] = self.incidents.stats()
        if self.spec is not None:
            blk = {"drafter": self.spec.name,
                   **self.spec.controller.stats()}
            if self.metrics.windowed:
                # Windowed acceptance quality + accepted-token goodput
                # (rides the PR 10 rings): what serve_top's spec pane and
                # the SLO-side "is speculation still paying?" read want.
                w = self.metrics.window("spec_accept_ratio", 10.0)
                if w:
                    blk["accept_10s"] = w
                blk["accepted_tps_10s"] = round(
                    self.metrics.window_counter("spec_accepted_tokens",
                                                10.0) / 10.0, 3)
            snap["spec"] = blk
        return snap

    def resilience_snapshot(self) -> dict:
        """Diagnostic snapshot: metrics, pool/queue stats, the in-flight
        request table, and (when the always-on telemetry is enabled) the
        forensic bundle an SLO/watchdog breach needs — the blackbox event
        ring, trailing-window percentiles, SLO summary, and the sampled
        traces of the offending (slow/errored) requests."""
        plan = _faults.get_plan()
        out = {
            "in_flight": [
                {"slot": i, "req_id": s.req.req_id,
                 "phase": "prefill" if s.prefilling else "decode",
                 "offset": s.offset, "ctx_len": len(s.ctx),
                 "generated": len(s.req.output),
                 "priority": s.req.priority,
                 "n_preemptions": s.req.n_preemptions}
                for i, s in enumerate(self._slots) if s is not None],
            "queue_depth": len(self.scheduler),
            "pool": {"n_blocks": self.pool.n_blocks,
                     "n_free": self.pool.n_free,
                     "n_used": self.pool.n_used,
                     "n_cached": self.pool.n_cached,
                     "n_reclaimable": self.pool.n_reclaimable},
            "requests": {"completed": len(self._finished),
                         "failed": len(self._failed)},
            "faults_fired": plan.n_fired if plan is not None else 0,
            "metrics": self.metrics.as_dict(),
        }
        windows = self._window_summary()
        if windows:
            out["windows"] = windows
        if self._slo is not None:
            out["slo"] = self._slo.summary()
        if self.blackbox is not None:
            out["blackbox"] = self.blackbox.dump(last=256)
        if self.sampler is not None:
            out["sampler"] = self.sampler.stats()
            out["sampled_traces"] = [rt.as_dict() for rt in
                                     list(self.sampler.kept)[-8:]]
        if self.journey is not None:
            out["journey"] = self.journey.dump()
        if self.efficiency is not None:
            out["efficiency"] = self.efficiency.dump()
        if self.incidents is not None:
            out["incidents"] = self.incidents.dump()
        return out

    def perfdb_sample(self) -> dict:
        """Flat metric dict for the perf flight recorder (obs/perfdb.py):
        the serving-side tracked numbers — TTFT/TBT/e2e percentiles in ms,
        token/request counters, preemptions, retraces. Callers append this
        as one PerfDB run (``scripts/serve_smoke.py --perfdb``, bench's
        serve arms) so ``tools/perf_gate.py`` can gate on serving latency
        the same way it gates on kernel time."""
        m = self.metrics.as_dict()
        out: dict = {}
        for hist in ("ttft_s", "tbt_s", "e2e_latency_s", "queue_wait_s"):
            for stat in ("p50", "p95"):
                k = f"{hist}_{stat}"
                if k in m:
                    out[f"{hist[:-2]}_{stat}_ms"] = round(
                        float(m[k]) * 1e3, 3)
        for k in ("tokens_generated", "requests_completed",
                  "requests_failed", "preemptions", "step_retries"):
            if k in m:
                out[k] = float(m[k])
        out["retraces"] = max(0.0, float(self.trace_counts["decode"]
                                         + self.trace_counts["prefill"] - 2))
        if self.spec is not None:
            out.update(self.spec.controller.perfdb_sample())
            for k in ("spec_proposed_tokens", "spec_accepted_tokens",
                      "spec_verify_rows", "spec_rollback_tokens",
                      "spec_rollback_blocks", "spec_drafts_dropped"):
                if k in m:
                    out[k] = float(m[k])
        if self.journey is not None:
            out.update(self.journey.perfdb_sample())
        if self._controller is not None:
            out.update(self._controller.perfdb_sample())
        if self.efficiency is not None and self.efficiency.steps:
            out.update(self.efficiency.perfdb_sample())
        if self.incidents is not None:
            out.update(self.incidents.perfdb_sample())
        # Pool fragmentation (KVPool.fragmentation): lets block-size sweeps
        # in the run DB separate allocator shredding from kernel effects.
        frag = self.pool.fragmentation()
        out["pool_free_blocks"] = float(frag["free_blocks"])
        out["pool_largest_free_run"] = float(frag["largest_free_run"])
        out["pool_frag_frac"] = float(frag["frag_frac"])
        out["pool_cached_blocks"] = float(frag["cached_blocks"])
        # Prefix-cache effectiveness: hit rate over adoption-time lookups
        # and the fraction of admitted prompt tokens served from cache.
        lookups = m.get("prefix_lookups", 0.0)
        if lookups:
            out["prefix_hit_rate"] = float(
                m.get("prefix_hits", 0.0)) / float(lookups)
        ct = m.get("prefix_cached_tokens", 0.0)
        ut = m.get("prefix_uncached_tokens", 0.0)
        if ct + ut:
            out["prefix_cached_token_frac"] = float(ct) / float(ct + ut)
        # Autotune-search shrinkage this process (configs the resource
        # analyzer rejected before timing — e.g. the paged-tile pruner).
        try:
            from triton_distributed_tpu.runtime.autotuner import (
                pruned_configs_total,
            )

            out["pruned_configs"] = float(pruned_configs_total())
        except Exception:
            pass
        return out

    def _call_step(self, site: str, fn):
        """Dispatch one compiled step through the fault plane + retry.

        ``fn(corrupt)`` runs the jitted step with the given corruption
        operand. With no plan installed this is a direct call with the
        cached zero operand (one attribute check). With a plan, each
        attempt re-fires the ``site`` BEFORE touching the jitted function —
        so a raised ``TransientFault`` never consumes the donated KV
        buffers and the retry re-runs against intact state. (Real
        device-side failures are out of retry's scope for exactly that
        donation reason.)"""
        if site not in self._steps_built:
            # First call of this jitted step: tracing, lowering, the
            # Mosaic/XLA compile and the first device allocation all
            # happen inside it, and none of them is a replica fault.
            # Injected faults fire in ``attempt`` BEFORE ``fn`` and are
            # not wrapped.
            inner = fn

            def fn(corrupt):
                try:
                    out = inner(corrupt)
                except Exception as e:
                    raise StepBuildError(
                        f"{site}: first call of the compiled step failed "
                        f"({type(e).__name__}: {e})") from e
                self._steps_built.add(site)
                return out

        if _faults._PLAN is None:
            return fn(self._corrupt0)

        def attempt():
            corrupt = self._corrupt0
            directive = _faults.fire(site)   # may raise / sleep
            if directive is not None and directive[0] == "nan":
                row = directive[1] % self.n_slots
                arr = np.zeros((self.n_slots,), np.float32)
                arr[row] = np.nan
                corrupt = jnp.asarray(arr)
                self.metrics.inc("faults_nan_injected")
                _trace.instant("fault_nan", site=site, row=row)
            return fn(corrupt)

        def on_retry(attempt_i, exc):
            self.metrics.inc("faults_injected")
            self.metrics.inc("step_retries")
            _trace.instant("fault_retry", site=site, attempt=attempt_i,
                           error=str(exc))
            if self.blackbox is not None:
                self.blackbox.record("fault", site=site,
                                     attempt=attempt_i, error=str(exc))
            if self.journey is not None:
                self.journey.global_event("fault", site=site,
                                          attempt=attempt_i,
                                          error=str(exc))

        def on_recovery(seconds):
            self.metrics.inc("step_recoveries")
            self.metrics.observe("recovery_s", seconds)

        return self.retry.run(attempt, on_retry=on_retry,
                              on_recovery=on_recovery)

    def _ensure_blocks(self, seq_id, n_tokens: int, *, match=None) -> bool:
        """``pool.ensure`` through the retry policy (the ``pool.ensure``
        fault site fires inside ``KVPool.ensure`` itself). ``match`` (a
        ``PrefixMatch`` from ``_cache_match``) routes adopted cache blocks
        into the new table. Raises ``TransientFault`` only after the retry
        budget is spent."""
        adopt = match.blocks if match is not None else ()
        cow = match.cow_src if match is not None else None

        def ensure():
            return self.pool.ensure(seq_id, n_tokens, adopt=adopt,
                                    cow_src=cow)

        if _faults._PLAN is None:
            return ensure()

        def on_retry(attempt_i, exc):
            self.metrics.inc("faults_injected")
            self.metrics.inc("alloc_retries")
            _trace.instant("fault_retry", site="pool.ensure",
                           attempt=attempt_i, error=str(exc))

        def on_recovery(seconds):
            self.metrics.inc("alloc_recoveries")
            self.metrics.observe("recovery_s", seconds)

        return self.retry.run(ensure, on_retry=on_retry,
                              on_recovery=on_recovery)

    # -- prefix cache plumbing ----------------------------------------------

    def _probe_match_len(self, req: Request) -> int:
        """Side-effect-free cached-prefix probe for the scheduler's
        admission budget. A faulted lookup reads as 0 cached tokens — the
        budget just turns conservative."""
        try:
            return self.prefix_cache.match_len(
                req.prompt + req.output,
                max_len=max(req.context_len - 1, 0))
        except _faults.TransientFault as e:
            self.metrics.inc("faults_injected")
            self.metrics.inc("prefix_lookup_faults")
            _trace.instant("fault_cache_lookup", phase="probe", error=str(e))
            return 0

    def _cache_match(self, ctx: list[int]):
        """Adoption-time lookup (the one that counts): longest cached
        prefix of ``ctx``, capped one token short so the admission still
        recomputes a token and produces first-token logits. A faulted
        lookup degrades to a cold miss — correct output, zero hit, no
        refcount ever touched (the fault site fires before the cache reads
        anything)."""
        if self.prefix_cache is None or not self.prefix_cache.enabled:
            return None
        try:
            return self.prefix_cache.match(ctx, max_len=len(ctx) - 1)
        except _faults.TransientFault as e:
            self.metrics.inc("faults_injected")
            self.metrics.inc("prefix_lookup_faults")
            _trace.instant("fault_cache_lookup", phase="match", error=str(e))
            return None

    # -- request lifecycle --------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *, priority: int = 0,
               req_id=None, tenant: str | None = None) -> object:
        """Queue one request; returns its id (used as the pool seq id).
        ``tenant`` is the billing identity for the efficiency ledger's
        per-tenant cost table (untagged requests bill to "default")."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new_tokens>=1")
        total = len(prompt) + max_new_tokens
        if total > self.pool.max_seq_len:
            raise ValueError(f"prompt+max_new_tokens ({total}) exceeds pool "
                             f"max_seq_len ({self.pool.max_seq_len})")
        if self.pool.blocks_for(total) > self.pool.n_blocks:
            raise ValueError(f"request needs {self.pool.blocks_for(total)} "
                             f"blocks; pool has {self.pool.n_blocks} total")
        if req_id is None:
            req_id = f"req-{self._req_counter}"
        self._req_counter += 1
        req = Request(req_id=req_id, prompt=prompt,
                      max_new_tokens=max_new_tokens, priority=priority,
                      submit_t=time.monotonic(), tenant=tenant)
        self.scheduler.submit(req)
        _trace.async_begin("request", req_id, prompt_len=len(prompt),
                           max_new_tokens=max_new_tokens)
        if self.sampler is not None:
            self.sampler.begin(req_id, prompt_len=len(prompt),
                               max_new_tokens=max_new_tokens)
        if self.journey is not None:
            # Direct engine submit: the opening wait is the scheduler
            # queue (a fleet submit opens in "route" instead — fleet.py).
            req.journey = self.journey.begin(
                req_id, phase="queue", prompt_len=len(prompt),
                **({"tenant": tenant} if tenant else {}))
        return req_id

    def adopt(self, req: Request) -> object:
        """Enqueue an EXISTING ``Request`` object — the fleet's placement
        and requeue endpoint (``serving/fleet.py``). Unlike ``submit``,
        the Request survives the move: its id, accumulated ``output``,
        preemption count, and arrival order all carry over, so a requeue
        after a replica drain is eviction-by-recompute at fleet scope —
        the new replica re-prefills prompt+output and greedy decoding
        continues bit-identically. Tracing/async request intervals are the
        CALLER's job (the fleet opens them once at first submit; the
        process-global tracer matches this engine's ``async_end``)."""
        total = req.context_len + max(req.remaining_new, 1)
        if total > self.pool.max_seq_len:
            raise ValueError(f"request context ({total}) exceeds pool "
                             f"max_seq_len ({self.pool.max_seq_len})")
        if self.pool.blocks_for(total) > self.pool.n_blocks:
            raise ValueError(f"request needs {self.pool.blocks_for(total)} "
                             f"blocks; pool has {self.pool.n_blocks} total")
        if req.submit_t is None:
            req.submit_t = time.monotonic()
        self.scheduler.submit(req)
        if self.sampler is not None:
            self.sampler.begin(req.req_id, prompt_len=len(req.prompt),
                               max_new_tokens=req.max_new_tokens,
                               adopted=True)
        if self.journey is not None:
            # Fleet placements arrive with a live context (the route hop
            # was recorded fleet-side); a standalone adopt opens fresh.
            if getattr(req, "journey", None) is None:
                req.journey = self.journey.begin(req.req_id, phase="queue",
                                                 adopted=True)
            else:
                self.journey.event(req.req_id, "adopt")
        return req.req_id

    def drain(self, reason: str = "drain") -> list[Request]:
        """Pull EVERY request out of this engine — occupied slots via the
        eviction-by-recompute path (blocks released, generated output kept
        on the Request for re-prefill elsewhere) plus the whole waiting
        queue — and return them, oldest arrival first. The fleet calls
        this on a quarantined replica; the engine is left empty (pool
        invariants intact) and can be stepped or probed safely afterwards.
        Requests stay ``status='pending'`` — draining is displacement, not
        failure. A step in flight is read first; where the device fails
        under that read its tokens are lost as a failed step's are, and
        the requests leave with what they have."""
        try:
            self.flush()
        except Exception as e:  # noqa: BLE001 — a failed replica is drained
            self.metrics.inc("drain_flush_failures")
            _trace.instant("drain_flush_failed", reason=reason,
                           error=f"{type(e).__name__}: {e}")
        out: list[Request] = []
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            self.pool.release(s.req.req_id)
            s.req.n_preemptions += 1
            self._slots[i] = None
            if self.spec is not None:
                self.spec.drafter.release(s.req.req_id)
            self.metrics.inc("preemptions")
            self.metrics.inc("drained_requests")
            _trace.instant("drain", req=s.req.req_id, slot=i,
                           progress=s.offset, reason=reason)
            if self.blackbox is not None:
                self.blackbox.record("drain", req=s.req.req_id, slot=i,
                                     progress=s.offset, reason=reason)
            if self.sampler is not None:
                self.sampler.event(s.req.req_id, "drain", slot=i,
                                   reason=reason)
            if self.journey is not None:
                self.journey.hop(s.req.req_id, "drain", reason=reason,
                                 progress=s.offset)
            out.append(s.req)
        while len(self.scheduler):
            req = self.scheduler.pop()
            self.metrics.inc("drained_requests")
            if self.journey is not None:
                # Queue-drained requests hop too: their wait moves from
                # this replica's queue to the fleet requeue bucket.
                self.journey.hop(req.req_id, "drain", reason=reason,
                                 progress=0)
            out.append(req)
        out.sort(key=lambda r: (r.arrival_seq
                                if r.arrival_seq is not None else 0))
        return out

    @property
    def heartbeat(self):
        """The serving-loop ``Heartbeat`` attached via ``attach_watchdog``
        (None when no heartbeat is configured). The fleet health machine
        polls ``heartbeat.stale()`` through this."""
        return self._heartbeat

    def _admit(self):
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return
        # Cache-resident-but-unreferenced blocks are one eviction away from
        # the free list, so budgets and backpressure count them as
        # available — otherwise a warm cache would read as a full pool and
        # park admission forever.
        avail = self.pool.n_free + self.pool.n_reclaimable
        if (self.admission_pressure > 0.0
                and len(free) < self.n_slots       # engine not idle
                and len(self.scheduler)
                and avail / self.pool.n_blocks
                    < self.admission_pressure):
            # Backpressure: let the running residents drain before adding
            # contenders that would immediately trigger eviction churn.
            # Never applied to an idle engine, so progress is guaranteed.
            self.metrics.inc("admission_backpressure")
            _trace.instant("backpressure", waiting=len(self.scheduler),
                           pool_free=self.pool.n_free)
            if self.blackbox is not None:
                self.blackbox.record("backpressure",
                                     waiting=len(self.scheduler),
                                     pool_free=self.pool.n_free)
            return
        if _faults._PLAN is not None:
            try:
                _faults.fire("sched.admit")
            except _faults.TransientFault as e:
                # Admission is naturally idempotent: nothing was popped
                # yet, so "degrade" = skip this round and try next step.
                self.metrics.inc("faults_injected")
                self.metrics.inc("admissions_deferred")
                _trace.instant("fault_admit", error=str(e))
                return
        caching = (self.prefix_cache is not None
                   and self.prefix_cache.enabled)
        admitted = self.scheduler.admit(
            free_slots=len(free), free_blocks=avail,
            blocks_for=self.pool,
            match_len=self._probe_match_len if caching else None)
        for req in admitted:
            ctx = req.prompt + req.output
            # Match immediately before ensure — the budget probe above was
            # advisory (an earlier ensure's reclaim may have evicted what
            # it saw), but nothing can evict between this match and the
            # ensure that pins/adopts its blocks.
            m = self._cache_match(ctx) if caching else None
            if m is not None and m.match_len == 0:
                m = None
            try:
                ok = self._ensure_blocks(req.req_id, len(ctx) + 1, match=m)
            except _faults.TransientFault:
                # Allocator faulted past the retry budget: requeue rather
                # than fail the request — admission hasn't touched a slot.
                self.scheduler.requeue(req)
                self.metrics.inc("admissions_deferred")
                _trace.instant("admit_deferred", req=req.req_id)
                continue
            if not ok:
                # The probe over-promised (probe-time match shrank, or
                # reclaim came up short). Nothing was allocated; put the
                # request back at its FIFO position and retry next step.
                self.scheduler.requeue(req)
                self.metrics.inc("admissions_deferred")
                _trace.instant("admit_deferred", req=req.req_id)
                continue
            matched = m.match_len if m is not None else 0
            self._slots[free.pop(0)] = _Slot(req=req,
                                             admit_seq=self._admit_seq,
                                             ctx=ctx, offset=matched)
            self._admit_seq += 1
            if self.spec is not None:
                # Rebuild the drafter's tables from the REQUEST's token
                # history — never from surviving drafter state — so a
                # preempted/requeued/fleet-migrated request proposes
                # exactly what it would have on its original timeline.
                self.spec.drafter.adopt(req.req_id, ctx)
            self.metrics.inc("requests_admitted")
            if caching:
                # Hit accounting lives HERE, not in the cache: only an
                # adoption that actually landed in a table counts.
                if matched:
                    self.metrics.inc("prefix_hits")
                    if m.cow_src is not None:
                        self.metrics.inc("prefix_cow_adoptions")
                self.metrics.inc("prefix_cached_tokens", matched)
                self.metrics.inc("prefix_uncached_tokens",
                                 len(ctx) - matched)
            if req.n_preemptions == 0:
                # First admission only: re-admissions after preemption would
                # double-count the scheduler wait.
                self.metrics.observe("queue_wait_s",
                                     time.monotonic() - req.submit_t)
            _trace.instant("admit", req=req.req_id, ctx_len=len(ctx),
                           cached=matched, readmit=req.n_preemptions > 0)
            if self.blackbox is not None:
                self.blackbox.record("admit", req=req.req_id,
                                     ctx_len=len(ctx), cached=matched,
                                     readmit=req.n_preemptions > 0)
            if self.sampler is not None:
                self.sampler.event(req.req_id, "admit", ctx_len=len(ctx),
                                   cached=matched,
                                   readmit=req.n_preemptions > 0)
            if self.journey is not None:
                self.journey.event(req.req_id, "admit", ctx_len=len(ctx),
                                   cached=matched,
                                   readmit=req.n_preemptions > 0)
            self._journal("admit", req_id=req.req_id, ctx_len=len(ctx))

    def _preempt(self, idx: int):
        # An eviction requeues its victim with the tokens it has: the one
        # in flight has to be among them. (Who is evicted does not depend
        # on it: ``select_victim`` reads priorities and admission order.)
        self.flush("preempt")
        s = self._slots[idx]
        self.pool.release(s.req.req_id)
        s.req.n_preemptions += 1
        self.scheduler.requeue(s.req)
        self._slots[idx] = None
        if self.spec is not None:
            # Drop drafter tables (re-adoption rebuilds them from the
            # request's history); the controller KEEPS its acceptance
            # window — it still predicts the recompute replay.
            self.spec.drafter.release(s.req.req_id)
        self.metrics.inc("preemptions")
        _trace.instant("preempt", req=s.req.req_id, slot=idx,
                       progress=s.offset)
        if self.blackbox is not None:
            self.blackbox.record("preempt", req=s.req.req_id, slot=idx,
                                 progress=s.offset)
        if self.sampler is not None:
            self.sampler.event(s.req.req_id, "preempt", slot=idx,
                               progress=s.offset)
        if self.journey is not None:
            self.journey.hop(s.req.req_id, "preempt", progress=s.offset)

    def _ensure_or_preempt(self, idx: int) -> bool:
        """Grow slot ``idx``'s table for its next token write, evicting
        victims (possibly ``idx`` itself) until the allocation fits.
        Victim selection honors the scheduler's aging cap; when EVERY
        candidate has aged out the cap is overridden (liveness beats
        fairness — the pool is full and somebody must yield)."""
        s = self._slots[idx]
        while True:
            try:
                if self._ensure_blocks(s.req.req_id, s.offset + 1):
                    return True
            except _faults.TransientFault:
                # Allocator faulted past the retry budget mid-decode:
                # degrade by preempting THIS slot (eviction-by-recompute
                # loses no output) instead of crashing the batch.
                self.metrics.inc("degraded_preemptions")
                _trace.instant("degraded_preempt", req=s.req.req_id,
                               slot=idx)
                self._preempt(idx)
                return False
            running = [(j, t.req, t.admit_seq)
                       for j, t in enumerate(self._slots) if t is not None]
            victim = Scheduler.select_victim(
                running, preemption_cap=self.scheduler.preemption_cap)
            if victim is None:
                victim = Scheduler.select_victim(running)
                assert victim is not None, "no evictable slot but pool full"
                self.metrics.inc("aging_overridden")
            self._preempt(victim)
            if victim == idx:
                return False

    def _journal(self, kind: str, **fields) -> None:
        """Best-effort journal append: emit/finish/fail/admit records are
        RECOVERABLE by determinism (a lost emit re-decodes to the same
        token on replay; a lost finish re-finishes), so a journal fault
        here degrades to a metric instead of failing the step. Only
        ``submit`` records demand durability — the fleet writes those
        itself, before registering the request."""
        if self.journal is None:
            return
        try:
            self.journal.append(kind, **fields)
        except _faults.TransientFault:
            self.metrics.inc("journal_faults")

    def _release_slot(self, idx: int):
        """The half of a finish that the SCHEDULE depends on: the row's
        blocks go to the prefix cache and back to the pool, the slot is
        free. It needs no token's value (a request ends by count, and the
        last token is never written back), so ``step()`` does it for a row
        whose last token is still in flight, before it admits: admission
        and eviction then see what they would see had the step been read.
        On the device the step in flight runs before any later one (the
        donated state chains them), so a block may be handed on at once."""
        s = self._slots[idx]
        if self.prefix_cache is not None and self.prefix_cache.enabled:
            # Donate this sequence's KV to the radix tree BEFORE release:
            # pool positions 0..offset-1 hold the KV of the full token
            # stream's first ``offset`` tokens (the final emitted token is
            # never written back). Insert promotes those blocks to cached;
            # the release below then drops them to resident-only.
            toks = (s.req.prompt + s.req.output)[:s.offset]
            self.prefix_cache.insert(s.req.req_id, toks)
        self.pool.release(s.req.req_id)
        self._slots[idx] = None

    def _finish(self, idx: int):
        s = self._slots[idx]
        self._release_slot(idx)
        self._complete(s)

    def _complete(self, s: _Slot):
        """The other half: the request, with its last token on it, is
        finished for everyone who asks."""
        s.req.finish_t = time.monotonic()
        s.req.status = "ok"
        self._finished[s.req.req_id] = s.req
        if self.spec is not None:
            self.spec.drafter.release(s.req.req_id)
            self.spec.controller.forget(s.req.req_id)
        self.metrics.inc("requests_completed")
        e2e = s.req.finish_t - s.req.submit_t
        self.metrics.observe("e2e_latency_s", e2e)
        _trace.async_end("request", s.req.req_id,
                         tokens=len(s.req.output),
                         preemptions=s.req.n_preemptions)
        if self.blackbox is not None:
            self.blackbox.record("finish", req=s.req.req_id,
                                 tokens=len(s.req.output),
                                 preemptions=s.req.n_preemptions,
                                 e2e_s=round(e2e, 6))
        kept = False
        if self.sampler is not None:
            kept = self.sampler.finish(s.req.req_id, latency_s=e2e,
                                       tokens=len(s.req.output))
        if self.journey is not None:
            # The TailSampler verdict decides full-detail retention; the
            # recorder force-keeps failed/displaced journeys on its own.
            self.journey.finish(s.req.req_id, status="ok", keep=kept)
        self._journal("finish", req_id=s.req.req_id,
                      n_tokens=len(s.req.output))

    def _quarantine(self, idx: int, reason: str):
        """Fail ONE request without failing the batch: release its blocks,
        empty its slot, park it in ``failed`` with an error status. Pure
        host-side slot churn — the next step's (mask, tables, offsets)
        simply exclude the row, same as a finish, so nothing about the
        compiled program or the surviving rows changes. Deliberately NO
        ``prefix_cache.insert`` here: a quarantined sequence's KV is
        suspect (NaN-poisoned logits, faulted steps) and must never become
        shareable. ``release`` raises before mutating on an unknown seq,
        so refcounts survive even a double-quarantine."""
        s = self._slots[idx]
        req = s.req
        req.status = "failed"
        req.error = reason
        req.finish_t = time.monotonic()
        self.pool.release(req.req_id)
        self._slots[idx] = None
        self._failed[req.req_id] = req
        if self.spec is not None:
            self.spec.drafter.release(req.req_id)
            self.spec.controller.forget(req.req_id)
        self.metrics.inc("requests_failed")
        _trace.instant("quarantine", req=req.req_id, slot=idx,
                       reason=reason)
        _trace.async_end("request", req.req_id, tokens=len(req.output),
                         failed=True, error=reason)
        if self.blackbox is not None:
            self.blackbox.record("quarantine", req=req.req_id, slot=idx,
                                 reason=reason)
        if self.sampler is not None:
            self.sampler.finish(req.req_id, error=reason,
                                tokens=len(req.output))
        if self.journey is not None:
            self.journey.finish(req.req_id, status="failed", error=reason,
                                keep=True)
        self._journal("fail", req_id=req.req_id, error=reason)

    def _record_token(self, s: _Slot, tok: int):
        self._journal("emit", req_id=s.req.req_id, tok=int(tok))
        s.req.output.append(tok)
        s.last_tok = tok
        if self.spec is not None:
            self.spec.drafter.observe(s.req.req_id, tok)
        self.metrics.inc("tokens_generated")
        now = time.monotonic()
        gap = None
        if s.req.first_token_t is None:
            s.req.first_token_t = now
            gap = now - s.req.submit_t
            self.metrics.observe("ttft_s", gap)
            _trace.instant("first_token", req=s.req.req_id)
            if self.sampler is not None:
                self.sampler.event(s.req.req_id, "first_token",
                                   ttft_s=round(gap, 6))
        elif s.last_token_t is not None:
            # Inter-token latency within one residency; the slot-local
            # timestamp resets on preemption so the requeue gap lands in
            # queue_wait/preemption accounting, not TBT.
            gap = now - s.last_token_t
            self.metrics.observe("tbt_s", gap)
        s.last_token_t = now
        # Tail-keep a straggler THE MOMENT one token blows the slow
        # threshold: a breach snapshot taken while it is still in flight
        # already contains its trace.
        if (self.sampler is not None and self.sampler.slow_s is not None
                and gap is not None and gap > self.sampler.slow_s):
            self.sampler.mark_slow(s.req.req_id, slow_gap_s=round(gap, 6))

    # -- speculative drafting -----------------------------------------------

    def _draft(self) -> dict[int, list[int]]:
        """Ask the drafter for up to k tokens per DECODE slot (prefilling
        slots have nothing to speculate on). The width cap per slot:
          controller k   acceptance-adaptive, clamped by the serving
                         controller's ``spec_k_cap`` SLO knob;
          remaining-1    a verify step emits at most proposed+1 tokens,
                         so never propose past the request's budget;
          chunk-1        the mixed step's compiled ids width holds
                         ``last_tok`` plus the proposals."""
        ctl = self.spec.controller
        drafter = self.spec.drafter
        out: dict[int, list[int]] = {}
        for i, s in enumerate(self._slots):
            if s is None or s.prefilling:
                continue
            cap = min(ctl.k_for(s.req.req_id), s.req.remaining_new - 1,
                      self.prefill_chunk - 1)
            if cap <= 0:
                continue
            props = drafter.propose(s.req.req_id, cap)
            if props:
                out[i] = [int(t) for t in props[:cap]]
        return out

    # -- iteration ----------------------------------------------------------
    # One step in flight. A call of ``step()`` plans and DISPATCHES step
    # N+1 from what the host knows by count (offsets, takes, the tokens a
    # request may still take, the block a row needs next), and only then
    # READS step N: its tokens, the journal's records, the finishes, the
    # counters. The Python between two steps so runs while the device
    # works. Where the next plan needs the VALUES of the tokens
    # (``_serial_reason``; an eviction) the step is read before anything
    # is planned behind it: the same loop with nothing left in flight.

    def _serial_reason(self) -> str | None:
        """Why a step must be read before the next is planned, as long as
        the condition holds: acceptance decides a verify row's offset; a
        fault plan's retry must find the state it started from; the guard
        quarantines a row before it takes another token."""
        if self.spec is not None:
            return "speculation"
        if _faults._PLAN is not None:
            return "fault_plan"
        if self.nan_guard:
            return "guard"
        return None

    def flush(self, reason: str = "caller") -> None:
        """Read the step in flight, if there is one: afterwards every
        dispatched token is on its request and every finished request in
        ``finished``. What reads or moves requests between two steps
        (``drain``, ``finished``, ``failed``, the end of ``run()``,
        ``Fleet.checkpoint``) calls it first."""
        st, self._inflight = self._inflight, None
        if st is not None:
            self.metrics.inc("pipeline_flushes", labels={"reason": reason})
            self._retire(st, flush=reason)

    def _moved(self, before: dict, **names) -> dict:
        """How far counters moved since ``before`` (a copy of
        ``metrics.counters``), as ``attribute=counter``: what a trace span
        says of its phase, with no second count kept beside the counter."""
        c = self.metrics.counters
        return {attr: int(c.get(name, 0.0) - before.get(name, 0.0))
                for attr, name in names.items()}

    def step(self) -> bool:
        """One scheduler iteration: admit, dispatch one compiled step,
        then read the step dispatched by the call before (the one just
        dispatched, where ``_serial_reason`` holds). A token is therefore
        visible in the call AFTER the one that dispatched it. Returns
        False when there is nothing to do (idle).

        While the tracer records (``obs.trace``: enabled, or a profiler
        capture live) the call is the span ``engine.step`` and its phases
        are ``engine.admit``, ``engine.blocks``, ``engine.observe``,
        ``engine.dispatch``, the wait for the tokens (``decode_step`` /
        ``mixed_step``) and ``engine.retire`` (``sp`` and ``phase`` below
        are None when it does not, and nothing is counted for them)."""
        with _trace.span("engine.step") as sp:
            return self._step(sp)

    def _step(self, sp) -> bool:
        serial = self._serial_reason()
        if sp is not None and serial:
            sp.set(serial=serial)
        if serial and self._inflight is not None:
            # The condition came up between two calls: this call only
            # reads, so that no call ever moves two steps' counters.
            if sp is not None:
                sp.set(dispatched="none", read=self._inflight.kind)
            self.flush(serial)
            return True
        with _trace.span("engine.admit") as phase:
            c0 = phase and dict(self.metrics.counters)
            released = 0
            if self._inflight is not None:
                # A request whose last token is in flight has ended BY
                # COUNT: its slot and blocks are free for this call's
                # admission, as they would be had the step been read
                # (``_release_slot``).
                for r in self._inflight.rows:
                    if r.slot.ended and self._slots[r.idx] is r.slot:
                        self._release_slot(r.idx)
                        released += 1
            self._admit()
            if phase is not None:
                phase.set(waiting=len(self.scheduler), released=released,
                          **self._moved(c0, admitted="requests_admitted"))
        self._proposals = self._draft() if self.spec is not None else {}
        # Decode rows write one token this step — make room first (prefill
        # rows were fully funded at admission). A slot with draft
        # proposals needs blocks for all of them up front; speculation
        # NEVER preempts a neighbor for that — if the wider allocation
        # doesn't fit, the proposal is dropped and the slot falls back to
        # the plain one-token path.
        with _trace.span("engine.blocks") as phase:
            c0 = phase and dict(self.metrics.counters)
            for i in range(self.n_slots):
                s = self._slots[i]
                if s is None or s.prefilling:
                    continue
                props = self._proposals.get(i)
                if props:
                    try:
                        ok = self._ensure_blocks(
                            s.req.req_id, s.offset + 1 + len(props))
                    except _faults.TransientFault:
                        ok = False
                    if ok:
                        continue
                    del self._proposals[i]
                    self.metrics.inc("spec_drafts_dropped")
                self._ensure_or_preempt(i)
            if phase is not None:
                phase.set(**self._moved(
                    c0, preempted="preemptions",
                    drafts_dropped="spec_drafts_dropped"))
        live = [i for i, s in enumerate(self._slots) if s is not None]
        with _trace.span("engine.observe"):
            self.metrics.set_gauge("queue_depth", len(self.scheduler))
            self.metrics.set_gauge("active_slots", len(live))
            self.metrics.set_gauge("pool_free_blocks", self.pool.n_free)
            self.metrics.set_gauge("pool_reclaimable_blocks",
                                   self.pool.n_reclaimable)
            self.metrics.set_gauge("pool_occupancy",
                                   self.pool.n_used / self.pool.n_blocks)
            # SLO evaluation + stats stream run even on idle iterations —
            # an engine starved by a fault is exactly when the SLO must
            # keep evaluating. Same for the incident detectors: a stall
            # shows up as signals going quiet, not as a step that runs.
            self._obs_tick()
            self._incident_tick(busy=bool(live))
            if self._controller is not None:
                self._controller.on_step()
        if not live and self._inflight is None:
            return False
        # Draft proposals ride the mixed step (ragged verify rows need
        # seq_lens); a step with neither prefill rows nor proposals uses
        # the cheaper (n_slots, 1) decode step unchanged.
        self._proposals = {i: p for i, p in self._proposals.items()
                           if self._slots[i] is not None}
        run = (self._run_mixed
               if (any(self._slots[i].prefilling for i in live)
                   or self._proposals)
               else self._run_decode)

        deadline = (contextlib.nullcontext() if self._watchdog is None
                    else self._watchdog.deadline("serving_step",
                                                 self._step_deadline_s))
        with deadline:
            before, st = self._inflight, None
            if not live:
                self.flush("idle")      # nothing to dispatch behind it
            else:
                with _trace.span("engine.dispatch") as phase:
                    self._inflight = st = run(live)
                    if phase is not None:
                        phase.set(
                            kind=st.kind, overlapped=st.attrs["overlapped"],
                            prefill_rows=st.attrs.get("prefill_rows", 0),
                            collective_calls=st.attrs["collective_calls"],
                            ici_bytes=st.attrs["ici_bytes"],
                            **{name: n for name, n in st.counts.items()
                               if not name.endswith("_steps")})
                if before is not None:
                    self.metrics.inc("steps_overlapped")
                    self._retire(before)
                elif serial:
                    self.flush(serial)
            if sp is not None:
                read = before or (st if serial else None)
                sp.set(dispatched=st.kind if st else "none",
                       read=read.kind if read else "none")
        if self._heartbeat is not None:
            self._heartbeat.beat()
        return True

    def _operands(self, live):
        """Offsets, block tables and mask of the occupied slots."""
        sids = [None] * self.n_slots
        offsets = np.zeros((self.n_slots,), np.int32)
        mask = np.zeros((self.n_slots,), bool)
        for i in live:
            s = self._slots[i]
            sids[i], offsets[i], mask[i] = s.req.req_id, s.offset, True
        tables = self.pool.padded_tables(sids)
        return (jnp.asarray(offsets), jnp.asarray(tables),
                jnp.asarray(mask))

    def _guard_rows(self, finite, rows) -> None:
        """Host half of the NaN/Inf guard: quarantine every row of
        ``rows`` (the slots that took tokens in the step) whose logits
        failed the compiled finite check. Costs a device transfer, so it
        only runs while guarding (fault plan installed or
        ``nan_guard=True``) — the mask itself is computed every step."""
        active = [i for i in rows if self._slots[i] is not None]
        for i in _guards.bad_rows(np.asarray(finite), active):
            self._quarantine(i, "non-finite logits (NaN/Inf guard)")

    # -- efficiency-ledger hooks --------------------------------------------
    # The ledger's interval of a step opens where the device could start
    # it (its dispatch, or the end of the step before it where that came
    # later: ``step_end`` clamps) and closes where its tokens are on the
    # host. Host work that ran while an earlier step was on the device is
    # in no interval's BUBBLE; what is, is the time the device waited.

    def _eff_begin(self, rows) -> tuple | None:
        """At dispatch: the ledger's clock, the comm ledger's wall, the
        (new_tokens, kv_len) pairs the step computes, its tokens and the
        token positions each tenant is billed."""
        if self.efficiency is None:
            return None
        pairs, tenants = [], {}
        for r in rows:
            # kv_len at the step's end: the row attends its whole context
            # up to and including the tokens just written.
            pairs.append((r.take, r.slot.offset + r.take))
            t = r.slot.req.tenant or "default"
            tenants[t] = tenants.get(t, 0) + r.take
        # Tokens of the step: a prompt's, and one for each decoding row.
        tokens = sum(r.take if r.slot.prefilling else 1 for r in rows)
        return (self.efficiency.clock(),
                _comm.wall_s_total() if _comm.enabled() else 0.0,
                pairs, tokens, tenants)

    def _eff_end(self, eff: tuple | None) -> None:
        """Account one step that has been read: model its FLOPs / HBM
        bytes from the rows it computed, diff the comm ledger, bill the
        tenants."""
        if eff is None:
            return
        t0, comm0, pairs, tokens, tenants = eff
        comm_s = ((_comm.wall_s_total() - comm0)
                  if _comm.enabled() else 0.0)
        model = self.engine.model
        stall = self.eff_stall_source() if self.eff_stall_source else None
        self.efficiency.step_begin(t0)
        self.efficiency.step_end(
            flops=model.step_flops(pairs),
            hbm_bytes=model.step_hbm_bytes(
                pairs, block_size=self.pool.block_size,
                itemsize=self._eff_itemsize, method=self.paged_attn,
                kv_itemsize=self._eff_kv_itemsize,
                kv_scales=self.pool.kv_quant),
            comm_s=comm_s, tokens=tokens, tenants=tenants,
            stall_summary=stall)

    def _take_stats(self, nxt, span):
        """Split the model's ``step_stats`` off the end of the step's
        token vector, add them to the counters of the same names and give
        them to the step's trace span (None: tracing is off) as
        attributes."""
        if nxt.shape[0] == self.n_slots:
            return nxt
        stats = {name: int(n) for name, n in zip(
            self.engine.model.step_stats, nxt[self.n_slots:])}
        for name, n in stats.items():
            self.metrics.inc(name, n)
        if span is not None:
            span.set(**stats)
        return nxt[:self.n_slots]

    def _dispatch(self, site: str, step, span: str, ids, live, rows,
                  counts, *extra, verify=(), **attrs) -> _Step:
        """What the two runners share: the slot operands, the call of the
        compiled ``step`` through ``_call_step`` with the pool's state as
        its donated operand, the state written back, the copy of the
        tokens to the host started, and the host's count of each row
        moved on. Returns the step, unread."""
        offsets, tables, mask = self._operands(live)
        # A decode row whose newest token is still in flight takes it
        # from the step before, on the device.
        from_prev = np.zeros((self.n_slots,), bool)
        for r in rows:
            from_prev[r.idx] = r.slot.in_flight
        state = self.pool.state
        key = self._next_key()   # drawn ONCE — retries replay the same key
        eff = self._eff_begin(rows)
        nxt, finite, greedy, state = self._call_step(
            site, lambda corrupt: step(
                self.engine.params, ids, state, offsets, tables, mask,
                *extra, corrupt, key, (self._prev, jnp.asarray(from_prev))))
        self.pool.state = state
        self._prev = nxt
        nxt.copy_to_host_async()
        for r in rows:
            r.slot.offset += r.written
            r.slot.in_flight += r.emits
        moved = self.collectives[
            "decode" if span == "decode_step" else "prefill"]
        return _Step(span=span, nxt=nxt, finite=finite, greedy=greedy,
                     rows=rows, counts=counts, eff=eff,
                     verify=dict(verify), attrs={
                         **attrs, "active": len(live),
                         "decode_rows": counts["decode_rows"],
                         "collective_calls": moved["collective_calls"],
                         "ici_bytes": moved["ici_bytes"],
                         "overlapped": self._inflight is not None})

    def _run_decode(self, live) -> _Step:
        tok = np.zeros((self.n_slots,), np.int32)
        rows = []
        for i in live:
            s = self._slots[i]
            tok[i] = 0 if s.in_flight else s.last_tok
            rows.append(_Row(i, s, 1, 1, True, False))
        return self._dispatch(
            "engine.decode", self._decode_step, "decode_step",
            jnp.asarray(tok), live, rows,
            {"decode_steps": 1, "decode_rows": len(rows)})

    def _run_mixed(self, live) -> _Step:
        L, P = self.prefill_chunk, self.prefill_rows
        proposals = self._proposals
        # The controller's runtime budget narrows a prefilling row's take
        # without touching the compiled (P, L) block: ids stays
        # zero-padded, seq_lens carries the smaller take.
        budget = min(max(int(self.prefill_budget), 1), L)
        wants: dict[int, list[int]] = {}    # slot -> this step's new tokens
        for i in live:
            s = self._slots[i]
            if s.prefilling:
                wants[i] = s.ctx[s.offset:s.offset + budget]
            else:
                # Decode row, possibly a speculative verify row: the ids
                # are [last_tok, d_1..d_p] and seq_lens = 1+p — churn in
                # draft width is pure operand data, same compiled step.
                wants[i] = [0 if s.in_flight else s.last_tok,
                            *proposals.get(i, ())]
        # A row of ONE token (a decode row; a prompt's last token) rides
        # the decode block. A longer one needs rows of the prefill block,
        # which are dealt in two passes. First every such slot gets ONE
        # row, the P oldest admitted; the rest take nothing this step and
        # go next. Then the rows still free go to the prompts that hold
        # one, oldest first, each as many further whole chunks as it can
        # fill: so no request takes less than its one row, and a prompt
        # alone takes up to P * L tokens a step. A slot's rows lie one
        # after another, every one but the last full, which is what lets
        # the second start where the first ends: attention appends every
        # row's keys before any row is read, a layer with per-slot state
        # chains the rows (``layers.mamba2``). One row a slot stays the
        # limit where that layout cannot be kept: a verify row, a narrowed
        # budget (a row cut short that is not the slot's last).
        many = sorted((i for i, t in wants.items() if len(t) > 1),
                      key=lambda i: self._slots[i].admit_seq)
        for i in many[P:]:
            del wants[i]
        served = many[:P]
        free = P - len(served)
        if budget == L:
            for i in served:
                s = self._slots[i]
                more = min(free, -(-(len(s.ctx) - s.offset) // L) - 1)
                if s.prefilling and more > 0:
                    free -= more
                    wants[i] = s.ctx[s.offset:s.offset + (1 + more) * L]
        # The block as the device takes it: a slot's rows one after
        # another, oldest admitted first, each named by (slot, cache length
        # before the row, live tokens); a dead row names no slot.
        tok = np.zeros((self.n_slots,), np.int32)
        chunk = np.zeros((P, L), np.int32)
        dealt = np.tile(np.int32([-1, 0, 0]), (P, 1))
        block_row, k = {}, 0
        for i in served:
            t, block_row[i] = wants[i], k
            chunk.reshape(-1)[k * L:k * L + len(t)] = t
            for at in range(0, len(t), L):
                dealt[k] = i, self._slots[i].offset + at, min(L, len(t) - at)
                k += 1
        seq_lens = np.zeros((self.n_slots,), np.int32)
        rows = []
        pre_toks = dec_rows = 0
        for i, t in wants.items():
            seq_lens[i] = len(t)
            if i not in block_row:
                tok[i] = t[0]
            s = self._slots[i]
            if not s.prefilling:
                dec_rows += 1
                # A verify row's offset is acceptance's to move.
                rows.append(_Row(i, s, len(t), 0 if i in proposals else 1,
                                 True, False))
                continue
            pre_toks += len(t)
            last = s.offset + len(t) >= len(s.ctx)
            rows.append(_Row(i, s, len(t), len(t), last, last))
            if self.journey is not None:
                # Chunk consumption keyed by the budget in force, so
                # controller narrowing shows up per request.
                self.journey.event(s.req.req_id, "prefill_chunk",
                                   tokens=len(t), budget=budget)
        # Per-step work accounting (prompt tokens actually consumed vs
        # 1-token decode rows riding the mixed step) — what the adaptive
        # bench's deterministic cost model and serve_top's rate lines
        # read; how full the step was (live tokens of its n_slots + P * L
        # positions; live rows of the block, and those of them that are a
        # slot's second or later) and how often the block was: a
        # prefilling row that wanted tokens and got none.
        block = {"mixed_step_tokens": int(seq_lens.sum()),
                 "prefill_rows_filled": k,
                 "prefill_rows_extra": k - len(served),
                 "prefill_rows_deferred": len(many) - len(served)}
        counts = {"prefill_steps": 1, "prefill_tokens": pre_toks,
                  "decode_rows": dec_rows, **block}
        return self._dispatch(
            "engine.prefill", self._mixed_step, "mixed_step",
            (jnp.asarray(tok), jnp.asarray(chunk), jnp.asarray(dealt)),
            live, rows, counts, jnp.asarray(seq_lens),
            verify={i: (p, block_row[i]) for i, p in proposals.items()},
            prefill_rows=len(served), spec_rows=len(proposals), **block)

    def _retire(self, st: _Step, flush: str | None = None) -> None:
        """Read a dispatched step: its tokens (with the ``step_stats``
        that ride them) under the step's trace span, then everything that
        follows from their values or is counted a step: the efficiency
        ledger, the counters, the guard, the tokens on their requests (the
        journal's records, ``ttft_s`` / ``tbt_s``), the finishes."""
        attrs = {**st.attrs, "flush": flush} if flush else st.attrs
        with _trace.span(st.span, **attrs) as sp:
            try:
                nxt, greedy = jax.device_get((st.nxt, st.greedy))
            except Exception:
                self._unwind(st)
                raise
            nxt = self._take_stats(nxt, sp)
        with _trace.span("engine.retire") as phase:
            c0 = phase and dict(self.metrics.counters)
            self._eff_end(st.eff)
            for name, n in st.counts.items():
                self.metrics.inc(name, n)
            if self._guarding:
                self._guard_rows(st.finite, [r.idx for r in st.rows])
            for i, s, _, _, emits, first in st.rows:
                if s.req.status == "failed":
                    continue        # quarantined by the guard just above
                s.in_flight -= emits
                if i in st.verify:
                    props, k = st.verify[i]
                    self._accept_row(i, s, props, greedy[k], int(nxt[i]))
                    continue
                if not emits:
                    continue        # still mid-prompt; logits row is interim
                if first and self.journey is not None:
                    # This residency's prefill just completed: the journey
                    # phase flips to decode at the first emitted token.
                    self.journey.event(s.req.req_id, "decode_start")
                self._record_token(s, int(nxt[i]))
                if s.req.remaining_new == 0:
                    if self._slots[i] is s:     # else ``step()`` freed it
                        self._release_slot(i)
                    self._complete(s)
            if phase is not None:
                phase.set(**self._moved(c0, tokens="tokens_generated",
                                        finished="requests_completed"))

    def _unwind(self, st: _Step) -> None:
        """The device failed under a dispatched step (the error comes up
        where its tokens are read). Its tokens are lost, and so are those
        of a step dispatched behind it, whose operands were this one's
        results: take back the host's count of both, so that every row
        stands where its request's tokens say, as after a failed step of
        the flushed loop. A request that ended in the failed step and has
        given up its slot already goes back to the queue (its re-prefill
        emits the token that was lost)."""
        behind, self._inflight = self._inflight, None
        self._prev = self._prev0
        for step in (st,) if behind is None else (st, behind):
            for r in step.rows:
                r.slot.offset -= r.written
                r.slot.in_flight -= r.emits
                if self._slots[r.idx] is not r.slot \
                        and r.slot.req.status == "pending" and r.emits:
                    r.slot.req.n_preemptions += 1
                    self.scheduler.requeue(r.slot.req)
                    self.metrics.inc("preemptions")

    def _accept_row(self, idx: int, s: _Slot, props: list[int],
                    greedy_row, nxt_i: int) -> None:
        """Host-side longest-prefix acceptance for one verify row.

        The row consumed ``[last_tok, d_1..d_p]``; ``greedy_row[j]`` is
        the model's argmax continuation after position j — exactly the
        token one-at-a-time greedy decode would emit next. Accept the
        longest prefix with ``d_{j+1} == greedy_row[j]``, emit it plus
        the BONUS token ``greedy_row[m]`` (so every verify step advances
        >= 1 token and the emitted stream is bit-identical to the
        non-speculative engine's — full acceptance takes the bonus from
        ``nxt_i``, the canonical last-position sampling path), then roll
        the kv frontier back over the rejected suffix: ``offset`` simply
        advances by m+1 instead of p+1 — the stale rows past it are
        DMA-skipped by seq_lens and overwritten by the next step — and
        ``KVPool.truncate`` returns now-empty tail blocks."""
        p = len(props)
        m = 0
        while m < p and int(greedy_row[m]) == props[m]:
            m += 1
        rid = s.req.req_id
        s.offset += m + 1
        self.metrics.inc("spec_verify_rows")
        self.metrics.inc("spec_proposed_tokens", p)
        self.metrics.inc("spec_accepted_tokens", m)
        self.metrics.observe("spec_accept_ratio", m / p)
        self.spec.controller.record(rid, p, m)
        if m < p:
            freed = self.pool.truncate(rid, s.offset)
            self.metrics.inc("spec_rollback_tokens", p - m)
            if freed:
                self.metrics.inc("spec_rollback_blocks", freed)
        for t in props[:m]:
            self._record_token(s, t)
        self._record_token(s, nxt_i if m == p else int(greedy_row[m]))
        if s.req.remaining_new == 0:
            self._finish(idx)

    # -- driver -------------------------------------------------------------

    def run(self, max_steps: int | None = None) -> dict:
        """Step until idle (or ``max_steps``); returns
        ``{req_id: [generated token ids]}`` for every SUCCESSFUL request.
        Quarantined requests are in ``failed`` (status + error string) —
        a chaos run completes instead of crashing."""
        steps = 0
        idle = 0
        while max_steps is None or steps < max_steps:
            if self.step():
                idle = 0
            elif not len(self.scheduler):
                break
            else:
                # Nothing active but requests still queued: admission was
                # deferred (injected sched.admit fault). Spin the scheduler
                # again — bounded, so a pathological plan (p=1.0 error on
                # admission forever) fails loudly instead of hanging.
                idle += 1
                if idle > 1000:
                    raise RuntimeError(
                        "admission made no progress for 1000 consecutive "
                        "idle steps (fault plan blocking all admission?)")
            steps += 1
        return {rid: list(req.output) for rid, req in self.finished.items()}

    @property
    def finished(self) -> dict:
        """Finished requests, those of a step still in flight among them
        (it is read first)."""
        self.flush()
        return dict(self._finished)

    @property
    def failed(self) -> dict:
        """Quarantined requests: ``{req_id: Request}`` with
        ``status='failed'`` and ``error`` set."""
        self.flush()
        return dict(self._failed)
