"""Contextual autotuner: thunk-level timing with a cross-process config vote.

TPU-native analog of the reference's ``python/triton_dist/autotuner.py``
(``ContextualAutoTuner`` :43, ``@contextual_autotune(is_dist=True)`` :97,
docs/autotuner.md): because overlap ops are multi-kernel and side-effectful,
the unit of tuning is a whole THUNK (everything the op launches), not one
kernel; and because every process must run the same config (SPMD — a
mismatched block size deadlocks a collective), per-process timings are
combined across processes and every process picks the argmin of the SAME
summed vector (the reference all-reduces timings for exactly this reason).

Timing methodology: every dispatch carries a per-call host cost that is
not the kernel's, so a naive wall-clock of one short call measures the
host, not the kernel. ``perf_thunk`` times a jitted ``lax.fori_loop`` of
the op with a forced data dependence (the bench.py methodology): constant
overhead cancels in the short/long slope.

Choices are cached in-process and on disk (keyed by op name + shapes +
mesh fingerprint), so engine startup skips re-tuning — set
``TDT_AUTOTUNE_CACHE=/path.json`` to relocate, ``TDT_AUTOTUNE=0`` to
disable tuning entirely (first config wins).
"""

from __future__ import annotations

import functools
import hashlib
import json
import logging
import os
import statistics
import time
import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from triton_distributed_tpu.runtime.platform import cache_dir

_memory_cache: dict[str, Any] = {}

_log = logging.getLogger(__name__)
# (tuner name, config repr) pairs whose build failure was already logged.
_logged_build_failures: set = set()

# Per-tuner-name counts of configs statically rejected by the resource
# analyzer (the ``pruner=`` hook) before any compile/timing. bench.py's
# perfdb samples and serving's ``perfdb_sample()`` read these so
# autotune-search shrinkage is visible in the run DB.
_pruned_counts: dict[str, int] = {}

# Lazily-built obs.metrics registry for the pruned-config counter
# (``autotune_pruned_configs{tuner=<name>}``) — lazy so importing the
# autotuner never drags in the obs layer.
_metrics = None


def metrics():
    """The autotuner's obs.metrics.Metrics registry (created on first use)."""
    global _metrics
    if _metrics is None:
        from triton_distributed_tpu.obs.metrics import Metrics

        _metrics = Metrics()
    return _metrics


def pruned_counts() -> dict[str, int]:
    """Copy of the per-tuner pruned-config counts since process start."""
    return dict(_pruned_counts)


def pruned_configs_total() -> int:
    """Total configs statically pruned across all tuners this process."""
    return sum(_pruned_counts.values())


def _note_pruned(name: str, n: int) -> None:
    _pruned_counts[name] = _pruned_counts.get(name, 0) + n
    try:
        metrics().inc("autotune_pruned_configs", n,
                      labels={"tuner": name})
    except Exception:
        pass  # metrics are best-effort; pruning accounting must not raise


def _device_kind() -> str:
    """Kind string of device 0 ("TPU v5e", "cpu", ...) for the cache key.
    Module-level so tests can monkeypatch it to simulate hardware kinds
    without real devices; failure degrades to "unknown" rather than
    breaking tuning."""
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def _cache_path() -> str:
    """``TDT_AUTOTUNE_CACHE`` if set, else the in-checkout
    ``.cache/autotune.json`` (winners change what gets compiled, so they
    live beside the compile cache — ``runtime.platform.cache_dir``)."""
    return os.environ.get("TDT_AUTOTUNE_CACHE") or cache_dir("autotune.json")


def _load_disk_cache() -> dict:
    try:
        with open(_cache_path()) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _store_disk_cache(key: str, value) -> None:
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        cache = _load_disk_cache()
        cache[key] = value
        with open(path, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
    except OSError:
        pass  # unwritable cache dir: tuning still works, just not persisted


def clear_cache(disk: bool = False) -> None:
    _memory_cache.clear()
    _blocks_memo.clear()
    if disk:
        try:
            os.remove(_cache_path())
        except OSError:
            pass


def perf_thunk(thunk: Callable[[], Any], *, iters: tuple[int, int] = (8, 24),
               calls: int = 3) -> float:
    """Median per-iteration ms of ``thunk`` via the short/long slope
    (dispatch overhead cancels). ``thunk`` must return jax array(s); it is
    re-invoked ``iters`` times per measurement inside host loops — for ops
    already amortized in-jit, pass ``iters=(1, 2)``."""
    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = thunk()
        _force_completion(out)
        return (time.perf_counter() - t0) * 1e3

    short, long_ = iters
    run(short)  # compile + warm
    samples = []
    for _ in range(calls):
        s = run(short)
        l = run(long_)
        samples.append(max((l - s) / (long_ - short), 1e-6))
    return statistics.median(samples)


def _vote_across_processes(timings: Sequence[float],
                           tie_tol: float = 0.125) -> tuple[int, bool]:
    """Every process picks the winner from the SAME summed timing vector
    (the reference's cross-rank all-reduce of timings, autotuner.py:97).

    The winner is not the raw argmin: candidates within ``tie_tol`` of the
    fastest are a statistical tie on a chip with ±10-20%% run-to-run noise,
    and raw argmin then flip-flops between them across runs (observed: 3
    different "winners" in 5 fresh tunes at tol 3%% — the band must cover
    the chip's real noise floor: the cohort-normalized estimator still
    shows ~12%% run-to-run spread on the co-tenant chip, hence 12.5%%; a
    candidate must beat that spread to displace a preference-ordered
    earlier one). The EARLIEST candidate inside
    the tie band wins — candidate lists order known-good configs first, so
    noise collapses to a deterministic, preference-ordered choice while a
    genuinely faster candidate (by more than the band) still wins.

    Returns ``(best_index, valid)``; ``valid`` is False when the summed
    vector is all-inf (every candidate failed or was pure jitter on every
    process) — also a COLLECTIVE fact, so every process takes the same
    branch. A single process must never decide 'all failed' locally and
    skip the allgather: that hangs the processes still voting."""
    t = jnp.asarray(timings, jnp.float32)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        t = multihost_utils.process_allgather(t).sum(axis=0)
    if not bool(jnp.isfinite(t).any()):
        return int(jnp.argmin(t)), False
    best = float(jnp.min(t))
    for i, ti in enumerate([float(x) for x in t]):
        if ti <= best * (1.0 + tie_tol):
            return i, True
    return int(jnp.argmin(t)), True  # unreachable; defensive


class ContextualAutotuner:
    """Times ``make_thunk(config)`` for every candidate config and returns
    the globally-agreed winner; caches by ``key`` in memory and on disk."""

    def __init__(self, name: str, configs: Sequence[Any], *,
                 iters: tuple[int, int] = (8, 24), calls: int = 3,
                 timer: Callable[[Callable], float] | None = None,
                 multi_timer: Callable[[Sequence[Callable]],
                                       Sequence[float]] | None = None,
                 pruner: Callable[[Any], Sequence[Any]] | None = None):
        if not configs:
            raise ValueError("need at least one config")
        self.name = name
        self.configs = list(configs)
        self.iters = iters
        self.calls = calls
        # Static feasibility analyzer: ``pruner(config) -> findings``. A
        # non-empty findings list rejects the config BEFORE any compile or
        # timing (make_thunk is never called for it) — the
        # analysis.resources config-pruner hook. The pruner must be
        # DETERMINISTIC across processes (pure static analysis of the
        # config) or SPMD processes would time different candidate sets;
        # an exception inside it never prunes (analyzer bugs degrade to
        # "time everything", not "tune nothing").
        self.pruner = pruner
        # Custom ms-estimator for one candidate (overrides perf_thunk) —
        # used where the thunk shape allows better amortization than
        # host-looped dispatches (see slope_timer).
        self.timer = timer
        # Joint estimator for ALL candidates at once (overrides both):
        # candidates sampled round-robin in one harness so drift lands on
        # every candidate equally and cancels from the ranking — the
        # bench.py interleaved-pair methodology (VERDICT r3 weak #4: timing
        # candidates sequentially let drift decide the winner).
        self.multi_timer = multi_timer

    # Bumped whenever the timing methodology changes: cached winners are
    # only comparable within one methodology (ilq2 = interleaved round-robin
    # + plausibility gate + cohort-normalized medians; old entries must not
    # survive the switch — they were ranked under uncancelled drift).
    _METHODOLOGY = "ilq2"

    def _key(self, context_key: str) -> str:
        # The cached value is an INDEX into self.configs: the key must pin
        # the candidate list, or editing it would silently remap stale
        # cached indices onto different configs. The device kind and jax
        # version are part of the key because the disk cache file outlives
        # both: a winner tuned on v5e is not a winner on v6e, and a jax
        # upgrade can change what a config compiles to.
        digest = hashlib.sha256(
            repr(self.configs).encode()).hexdigest()[:10]
        return (f"{self.name}|{context_key}|{digest}|{self._METHODOLOGY}"
                f"|{_device_kind()}|jax{jax.__version__}")

    def _log_build_failure(self, i: int, exc: Exception) -> None:
        key = (self.name, repr(self.configs[i]))
        if key not in _logged_build_failures:
            _logged_build_failures.add(key)
            _log.warning("autotune %s: candidate %r failed to build and "
                         "loses: %s: %s", self.name, self.configs[i],
                         type(exc).__name__, exc)

    def _raise_if_none_built(self, build_errors: dict, tried: list,
                             context_key: str) -> None:
        """Local decision, safe under SPMD: every process builds the same
        candidates from the same shapes, so all of them raise together."""
        if tried and len(build_errors) == len(tried):
            first = build_errors[tried[0]]
            raise RuntimeError(
                f"autotune {self.name} [{context_key}]: all {len(tried)} "
                f"candidate configs failed to build; first "
                f"({self.configs[tried[0]]!r}): "
                f"{type(first).__name__}: {first}") from first

    def peek(self, context_key: str):
        """The cached winner for this context, or None — NEVER times or
        writes; safe under an active jax trace. In MULTI-process runs only
        the memory cache is consulted: it is written strictly after a
        collective decision, so it is process-consistent — whereas the disk
        cache is per-host, and a trace-time read of it could bake DIFFERENT
        configs into different hosts' jaxprs of one SPMD program (the
        divergence tune()'s allgather consensus exists to prevent)."""
        key = self._key(context_key)
        if key in _memory_cache:
            return self.configs[_memory_cache[key]]
        if jax.process_count() == 1:
            disk = _load_disk_cache()
            if key in disk and 0 <= disk[key] < len(self.configs):
                return self.configs[disk[key]]
        return None

    def tune(self, make_thunk: Callable[[Any], Callable[[], Any]],
             context_key: str):
        """Return the winning config for this context (cached).

        The cache decision itself is COLLECTIVE in multi-process runs: the
        disk cache is per-host and TDT_AUTOTUNE per-process, so hosts can
        disagree on cache state — a cache-hit process skipping the vote while
        a cache-miss process blocks in ``process_allgather`` hangs the job,
        and divergent cached winners deadlock collectives (SPMD). Every
        process first allgathers its (hit, index) pair; the cached winner is
        used only if ALL processes agree, otherwise everyone re-tunes.
        Memory-cache entries are exempt from the consensus round: they are
        only ever written after a collective decision (consensus or vote
        below), so they are process-consistent by construction — and the
        early return keeps repeat calls of tuned ops collective-free."""
        key = self._key(context_key)
        if key in _memory_cache:
            return self.configs[_memory_cache[key]]
        cached = None
        disk = _load_disk_cache()
        if key in disk and 0 <= disk[key] < len(self.configs):
            cached = disk[key]
        env_off = os.environ.get("TDT_AUTOTUNE", "1") == "0"
        if env_off and cached is None:
            cached = 0
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            pair = jnp.asarray(
                [1 if cached is not None else 0,
                 cached if cached is not None else -1,
                 1 if env_off else 0], jnp.int32)
            pairs = multihost_utils.process_allgather(pair)
            all_hit = bool(pairs[:, 0].min() == 1)
            agree = bool((pairs[:, 1] == pairs[0, 1]).all())
            any_env_off = bool(pairs[:, 2].max() == 1)
            if all_hit and agree:
                cached = int(pairs[0, 1])
            elif any_env_off:
                # Tuning disabled on >=1 process: EVERY process must make the
                # same participation decision (a lone env_off process taking
                # config 0 while others enter the timing vote deadlocks), so
                # consensus failure resolves to config 0 globally.
                cached = 0
            else:
                cached = None
        if cached is not None:
            _memory_cache[key] = cached
            return self.configs[cached]

        # Static pruning pass: analyzer-rejected configs are excluded from
        # the competition before anything compiles — make_thunk is never
        # called for them and they carry inf into the timing vote. The
        # prune decision is deterministic static analysis, so every SPMD
        # process computes the same set and the collective vote stays
        # aligned. If the analyzer rejects EVERY candidate it is
        # distrusted wholesale (warn + time everything) rather than left
        # to crash the tune.
        pruned: set[int] = set()
        if self.pruner is not None:
            for i, cfg in enumerate(self.configs):
                try:
                    findings = self.pruner(cfg)
                except Exception:
                    findings = None  # analyzer failure never prunes
                if findings:
                    pruned.add(i)
            if len(pruned) == len(self.configs):
                warnings.warn(
                    f"autotune {self.name}: resource pruner rejected all "
                    f"{len(self.configs)} candidate configs — ignoring the "
                    f"pruner and timing everything (its model is likely "
                    f"wrong for this context)")
                pruned = set()
            if pruned:
                _note_pruned(self.name, len(pruned))

        # A candidate that fails to BUILD (trace, lower, Mosaic/XLA compile)
        # loses, and its exception text goes to the log once. When every
        # candidate that was tried fails to build there is nothing to
        # choose from: raise the first failure instead of handing the
        # caller config 0, which would fail the same way later with the
        # cause gone.
        build_errors: dict[int, Exception] = {}
        tried = [i for i in range(len(self.configs)) if i not in pruned]

        def build(i):
            try:
                return make_thunk(self.configs[i])
            except Exception as e:  # noqa: BLE001 — candidate loses
                build_errors[i] = e
                self._log_build_failure(i, e)
                return None

        if self.multi_timer is not None:
            thunks = [None if i in pruned else build(i)
                      for i in range(len(self.configs))]
            self._raise_if_none_built(build_errors, tried, context_key)
            timings = list(self.multi_timer(thunks))
        else:
            timings = []
            for i in range(len(self.configs)):
                thunk = None if i in pruned else build(i)
                if thunk is None:
                    timings.append(float("inf"))  # pruned or unbuildable
                    continue
                try:
                    if self.timer is not None:
                        timings.append(self.timer(thunk))
                    else:
                        timings.append(perf_thunk(thunk, iters=self.iters,
                                                  calls=self.calls))
                except Exception as e:  # noqa: BLE001 — candidate loses
                    # perf_thunk's first call is where a lazily-jitted
                    # thunk compiles, so this is a build failure too.
                    build_errors[i] = e
                    self._log_build_failure(i, e)
                    timings.append(float("inf"))
            self._raise_if_none_built(build_errors, tried, context_key)
        best, valid = _vote_across_processes(timings)
        if not valid:
            # Every candidate failed/jittered out on every process — a
            # transient (e.g. sustained host noise turning all slopes
            # negative). Use config 0 UNCACHED so a later call re-tunes,
            # rather than crashing the caller or pinning a noise verdict.
            warnings.warn(f"autotune {key}: no candidate produced a valid "
                          f"timing; using config 0 uncached")
            return self.configs[0]
        _memory_cache[key] = best
        _store_disk_cache(key, best)
        return self.configs[best]


def contextual_autotune(configs: Sequence[Any], *, name: str | None = None,
                        key_fn: Callable[..., str] | None = None,
                        iters: tuple[int, int] = (8, 24)):
    """Decorator form (reference ``@contextual_autotune``, autotuner.py:97):
    wraps ``fn(config, *args, **kw)``; on first call per context the
    candidates are timed as whole thunks over the live arguments, then the
    cached winner is used.

    ``key_fn(*args, **kw) -> str`` scopes the cache (default: the
    shapes/dtypes of array arguments)."""
    def default_key(*args, **kw):
        parts = [f"{tuple(a.shape)}:{a.dtype}" for a in args
                 if hasattr(a, "shape")]
        return ",".join(parts)

    def deco(fn):
        tuner = ContextualAutotuner(name or fn.__name__, configs,
                                    iters=iters)

        @functools.wraps(fn)
        def wrapper(*args, **kw):
            ctx = (key_fn or default_key)(*args, **kw)
            cfg = tuner.tune(
                lambda c: (lambda: fn(c, *args, **kw)), ctx)
            return fn(cfg, *args, **kw)

        wrapper.tuner = tuner
        return wrapper

    return deco


# ---------------------------------------------------------------------------
# Stock tuners for the flagship ops
# ---------------------------------------------------------------------------

# Candidate blocks: on-chip sweep winners (tools/sweep_matmul.py) + safe
# fallbacks covering small/ragged shapes.
MATMUL_BLOCK_CANDIDATES: tuple[tuple[int, int, int], ...] = (
    (1024, 640, 1024),
    (1024, 512, 1024),
    (512, 1024, 1024),
    (512, 512, 1024),
    (512, 640, 512),
    (256, 1024, 512),
    (512, 256, 512),
    # Full-K single-pass blockings (1<<30 caps to K): no K revisiting, one
    # accumulator fill per (i, j) tile — legal since ag_gemm_single_chip
    # sizes vmem_limit_bytes to the working set (the fused-step winner's
    # shape applied to the plain matmul).
    (512, 640, 1 << 30),
    (1024, 640, 1 << 30),
    (2048, 640, 1 << 30),
)


def _force_completion(out) -> None:
    """Block until ``out`` is actually computed, by host-reading one
    element: a scalar device->host read cannot return before the program
    that produces it has finished."""
    leaf = jax.tree.leaves(out)[0]
    float(leaf.reshape(-1)[0])


# Same trip counts as bench.py: a 64-iteration delta puts tens of ms of
# real signal behind each slope, well above host-side dispatch jitter; a
# cold tune therefore costs (32 + 96) x rounds timed calls per candidate.
_TUNE_SHORT, _TUNE_LONG = 32, 96


def _trace_state_clean() -> bool:
    """True when no jax trace is active (timing thunks may run). The check
    lives in jax's private core module; if a jax upgrade moves it, fail
    toward "tracing" — the no-tune fallback is always correct (just
    untuned), while timing under a trace returns tracers and crashes."""
    try:
        from jax._src.core import trace_state_clean
    except Exception:
        return False
    return trace_state_clean()


def slope_timer(loop, *, rounds: int = 7):
    """Per-iteration ms of ``loop(n)`` — a jitted fori_loop whose trip count
    is a RUNTIME argument, so short and long runs share ONE executable and
    one dispatch each; the dispatch offset subtracts out of the slope.

    Two failure modes this design retired: host-looped separate dispatches
    (per-call host jitter never cancels), and static-trip-count loops (two
    executables per candidate, so switching between them lands in the
    slope). Negative-slope samples are jitter artifacts and are dropped —
    clamping them small would hand the argmin to the noisiest candidate; a
    candidate with no valid sample ranks last."""
    def run(n):
        t0 = time.perf_counter()
        out = loop(n)
        _force_completion(out)
        return (time.perf_counter() - t0) * 1e3

    run(_TUNE_SHORT)
    run(_TUNE_LONG)  # warm
    samples = [
        (run(_TUNE_LONG) - run(_TUNE_SHORT)) / (_TUNE_LONG - _TUNE_SHORT)
        for _ in range(rounds)
    ]
    pos = sorted(x for x in samples if x > 1e-5)
    if not pos:
        return float("inf")
    return pos[len(pos) // 2]


def interleaved_slope_timer(loops, *, rounds: int = 13, ms_bounds=None):
    """Per-iteration ms for a LIST of ``loop(n)`` thunks, sampled
    round-robin (loop0, loop1, ... per round) so clock/thermal drift hits
    every candidate equally and cancels from the RANKING — the bench.py
    paired-slope methodology moved into the tuner (VERDICT r3 weak #4: the
    sequential ``slope_timer`` path let drift land unevenly across
    candidates and the winner flip-flopped run to run).

    Per round each loop contributes one short/long slope (two dispatches of
    ONE executable — the dispatch offset subtracts out). ``ms_bounds``
    (lo, hi) is the physical-plausibility gate and matters as much as the
    interleaving: host dispatch jitter is TWO-sided, so without the
    gate a lucky-low impossible sample (a "0.13 ms" 4096x5120x3200 matmul
    — 1000 TF/s on a 197 TF/s chip) anchors the quartile and noise elects
    the winner. Callers that know the op's FLOPs derive the bounds from
    the perf-model peak (see ``_tune_matmul_blocks``); without bounds only
    non-positive slopes are dropped. The estimate is COHORT-NORMALIZED:
    each plausible slope is divided by its round's cohort median (all
    candidates in a round share the same drift, so it cancels from the
    ranking), the per-candidate median ratio is taken across rounds, and
    the result is scaled back to ms by the grand median. ``None`` entries
    (build-failed candidates) and loops with no valid sample return
    inf."""
    def run(loop, n):
        t0 = time.perf_counter()
        out = loop(n)
        _force_completion(out)
        return (time.perf_counter() - t0) * 1e3

    # A candidate that RAISES at any point (transient device error,
    # runtime OOM — compile failures were already caught at build time) is
    # dropped to inf, never allowed to abort the whole tune: the old
    # sequential path wrapped each timer call in try/except and this path
    # must degrade the same way.
    live = []
    for i, lp in enumerate(loops):
        if lp is None:
            continue
        try:
            run(lp, _TUNE_SHORT)
            run(lp, _TUNE_LONG)  # warm + absorb executable-switch stalls
            live.append((i, lp))
        except Exception:
            pass
    dead: set[int] = set()
    per_round: list[dict[int, float]] = []
    for _ in range(rounds):
        rd: dict[int, float] = {}
        for i, lp in live:
            if i in dead:
                continue
            try:
                s = run(lp, _TUNE_SHORT)
                l = run(lp, _TUNE_LONG)
            except Exception:
                dead.add(i)
                continue
            slope = (l - s) / (_TUNE_LONG - _TUNE_SHORT)
            ok = slope > 1e-5
            if ms_bounds is not None:
                ok = ms_bounds[0] <= slope <= ms_bounds[1]
            if ok:
                rd[i] = slope
        if rd:
            per_round.append(rd)

    # Cohort-normalized aggregation: within one round every candidate ran
    # under the SAME drift/contention, so dividing by the round's cohort
    # median cancels it from the RANKING entirely; the median of a
    # candidate's normalized ratios across rounds is then far lower
    # variance than any absolute-time estimate. Scaled back to ms by the
    # grand cohort median so callers still see real-unit times. Only
    # rounds where >=2 candidates survived the gate carry ranking signal
    # (a singleton round pins its lone survivor's ratio to exactly 1.0 —
    # uninformative, and it dilutes real differences). Candidates seen
    # only in singleton rounds rank inf when other candidates carry
    # normalized estimates (mixing estimators misranks under drift,
    # ADVICE r4 #3); when NO round had two survivors, all candidates fall
    # back to absolute medians together — one estimator either way.
    if live and not per_round:
        # No candidate produced a single valid sample (ADVICE r4 #3): this
        # looks exactly like "no winner" downstream (the tune silently
        # never commits) — make it loud, naming every possible cause: the
        # plausibility gate (over-tight ms_bounds / the non-positive-slope
        # floor when ms_bounds is None) or all candidates dying mid-rounds.
        cause = (f"plausibility gate ms_bounds={ms_bounds}"
                 if ms_bounds is not None else
                 "non-positive-slope gate (ms_bounds=None)")
        n_died = sum(1 for i, _ in live if i in dead)
        warnings.warn(
            f"interleaved_slope_timer: no valid sample from any of "
            f"{len(live)} live candidates over {rounds} rounds "
            f"({n_died} raised and died mid-rounds; the rest were "
            f"rejected by the {cause}) — no result will commit; if "
            f"bounds-gated, the bound may be too tight for this op "
            f"(overhead-dominated small shape?)", stacklevel=2)
    ranked = [rd for rd in per_round if len(rd) >= 2]
    grand = statistics.median(
        v for rd in ranked for v in rd.values()) if ranked else None
    out: list[float] = []
    for i in range(len(loops)):
        if i in dead:
            out.append(float("inf"))
            continue
        ratios = [v / statistics.median(rd.values())
                  for rd in ranked if (v := rd.get(i)) is not None]
        if ratios:
            out.append(statistics.median(ratios) * grand)
            continue
        if ranked:
            # Mixing estimators misranks (ADVICE r4 #3): when OTHER
            # candidates carry cohort-normalized estimates, a candidate
            # seen only in singleton rounds has no drift-comparable
            # signal — rank it out rather than compare its raw absolute
            # median against rescaled ratios under drift.
            out.append(float("inf"))
            continue
        # No multi-survivor round anywhere: every candidate is on the same
        # (absolute-median) estimator, so the comparison stays consistent.
        absolute = [v for rd in per_round
                    if (v := rd.get(i)) is not None]
        out.append(statistics.median(absolute) if absolute
                   else float("inf"))
    return out


def _tune_matmul_blocks(name: str, candidates, body_of, m: int, k: int,
                        n: int, dtype_str: str):
    """Shared (m, k, n) block-tuning harness: per candidate, ONE jitted
    dynamic-trip fori_loop of ``body_of(cfg)(acc, a, b)`` (forced dependence
    through acc defeats hoisting; runtime trip count = one executable, no
    switch stalls) slope-timed by ``slope_timer``; contextual-autotuner
    cached.

    Timing thunks cannot run under an active jax trace (an inner jit
    INLINES into the outer trace and returns tracers, not timings) — when
    called while tracing, a cached winner is used if one exists, else the
    first feasible candidate is returned UNCACHED so a later eager call can
    tune for real.

    Returns ``(cfg, committed)``: ``committed`` is False for the
    trace-fallback and the all-candidates-failed path — CALLERS MUST NOT
    MEMOIZE an uncommitted result (a plain lru_cache here once pinned the
    untuned fallback for the process lifetime)."""
    from triton_distributed_tpu.runtime import perf_model as _pm
    from triton_distributed_tpu.runtime.platform import on_tpu

    # Physical plausibility bounds for the slope gate: nothing computes
    # 2mkn FLOPs faster than the chip's bf16 peak (+2% tolerance), and a
    # sample 20x slower than peak is a co-tenant burst, not a candidate.
    # Real-TPU only: on other backends the v5e fallback figures would
    # reject every honest sample.
    bounds = None
    if on_tpu():
        flops = 2.0 * m * k * n
        peak = _pm.detect_hardware().peak_bf16_flops * 1.02
        ms_lo = flops / peak * 1e3
        # The FLOOR is dtype-independent physics (nothing beats the bf16
        # peak); the CEILING must account for wider dtypes running the MXU
        # multi-pass (f32 ~6x slower than bf16) or honest slow samples
        # would gate out as "bursts" and the tune would never commit.
        derate = {4: 6, 8: 13}.get(jnp.dtype(dtype_str).itemsize, 1)
        bounds = (ms_lo, 20 * ms_lo * derate)
    tuner = ContextualAutotuner(
        name, list(candidates),
        multi_timer=functools.partial(interleaved_slope_timer,
                                      ms_bounds=bounds))
    context_key = (f"{m}x{k}x{n}:{dtype_str}:"
                   f"{jax.devices()[0].device_kind}")
    if not _trace_state_clean():
        cached = tuner.peek(context_key)
        if cached is not None:
            return cached, True
        # ADVICE r3 #2: a jitted caller reaching this path bakes the
        # untuned config into its cached executable PERMANENTLY — a later
        # eager tune cannot retroactively fix already-compiled programs.
        # Warn once per shape so the fix (warm the tuned_* wrapper eagerly
        # before the first jit trace, as bench.py does) is discoverable.
        warn_key = ("trace_fallback", name, m, k, n, dtype_str)
        if warn_key not in _warned_trace_fallback:
            _warned_trace_fallback.add(warn_key)
            warnings.warn(
                f"autotune {name} {m}x{k}x{n}: called under an active jax "
                f"trace with no cached winner — the untuned default config "
                f"is being baked into the enclosing jit program. Call the "
                f"tuned_* wrapper eagerly once (outside jit) before the "
                f"first traced use to tune for real.", stacklevel=3)
        return list(candidates)[0], False
    dtype = jnp.dtype(dtype_str)
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k), dtype)
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, n), dtype)

    def make_thunk(cfg):
        body = body_of(cfg)

        @jax.jit
        def loop(a, b, n_iter):
            return jax.lax.fori_loop(
                0, n_iter, lambda _, acc: body(acc, a, b),
                jnp.zeros((m, n), jnp.float32))

        # Compile check before timing (also the executable every timed call
        # reuses — n_iter is a runtime arg).
        loop(a, b, jnp.int32(2)).block_until_ready()
        return lambda n_iter: loop(a, b, jnp.int32(n_iter))

    cfg = tuner.tune(make_thunk, context_key)
    # The no-valid-timing path returns config 0 without writing the tuner
    # cache; mirror that commit decision to the caller's memo.
    return cfg, tuner._key(context_key) in _memory_cache


# One warning per (tuner, shape) for the trace-time no-cache fallback.
_warned_trace_fallback: set = set()


# Per-shape memo for the tuned_* wrappers. NOT functools.lru_cache: only
# COMMITTED results may be memoized (an uncommitted trace-time fallback must
# be re-asked so a later eager call tunes for real).
_blocks_memo: dict = {}


def _memoized_blocks(memo_key, compute):
    if memo_key in _blocks_memo:
        return _blocks_memo[memo_key]
    result, committed = compute()
    if committed:
        _blocks_memo[memo_key] = result
    return result


def tuned_matmul_blocks(m: int, k: int, n: int, dtype_str: str = "bfloat16"):
    """On-chip tune of the single-chip matmul blocks at (m, k, n) — the
    consumer GEMM of ag_gemm / gemm_rs (block_n doubles as the overlap
    kernels' N tile). Returns (bm, bn, bk), or None when no candidate
    divides the shape (callers use the auto-block path, which delegates
    ragged shapes to XLA); cached in memory and on disk."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        ag_gemm_single_chip,
    )

    feasible = [c for c in MATMUL_BLOCK_CANDIDATES
                if m % min(c[0], m) == 0 and n % min(c[1], n) == 0
                and k % min(c[2], k) == 0]
    if not feasible:
        # No candidate divides this shape (ragged dims): None tells the
        # caller to use the auto-block path, which delegates to XLA's
        # emitter — forcing a non-dividing block as EXPLICIT would raise.
        return None

    def body_of(cfg):
        bm, bn, bk = (min(cfg[0], m), min(cfg[1], n), min(cfg[2], k))

        def body(acc, a, b):
            # Epsilon, not *0: a folded dep lets XLA hoist the matmul out
            # of the timing loop entirely (observed in a bench harness).
            bb = b + (acc[0, 0] * 1e-24).astype(b.dtype)
            return acc + ag_gemm_single_chip(
                a, bb, block_m=bm, block_n=bn, block_k=bk
            ).astype(jnp.float32)
        return body

    def compute():
        cfg, committed = _tune_matmul_blocks(
            "matmul_blocks", feasible, body_of, m, k, n, dtype_str)
        return (min(cfg[0], m), min(cfg[1], n), min(cfg[2], k)), committed

    return _memoized_blocks(("matmul", m, k, n, dtype_str), compute)


# Fused accumulate-step candidates ((bm, bn, bk); bk=None = full K single
# pass). Full-K (512, 640) is the on-chip winner at the bench shape
# (0.707 ms vs XLA 0.725, 4096x5120x3200 bf16); the rest cover revisiting
# variants and smaller shapes.
FUSED_STEP_CANDIDATES: tuple[tuple[int, int, int | None], ...] = (
    (512, 640, None),
    # Larger block_m cuts whole-B re-reads: B is re-fetched once per m/bm
    # grid row (the A block's index is constant across the inner j steps, so
    # Mosaic's pipeline skips its re-fetch). At the bench shape bm=2048
    # drops HBM traffic from ~408MB to ~212MB per step.
    (1024, 640, None),
    (2048, 640, None),
    (1024, 640, 2560),
    (512, 640, 2560),
    (1024, 640, 1024),
    (256, 640, None),
)


def tuned_fused_step_blocks(m: int, k: int, n: int,
                            dtype_str: str = "bfloat16"):
    """On-chip tune of ``fused_matmul_step`` blocks at (m, k, n):
    returns (bm, bn, bk|None); cached in memory and on disk."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        fused_matmul_step,
    )

    def body_of(cfg):
        bm, bn, bk = cfg

        def body(acc, a, b):
            s = (acc[0, 0] * 1e-24).astype(jnp.float32)
            return fused_matmul_step(acc, a, b, s, block_m=bm, block_n=bn,
                                     block_k=bk)
        return body

    def compute():
        return _tune_matmul_blocks("fused_step_blocks",
                                   FUSED_STEP_CANDIDATES, body_of, m, k, n,
                                   dtype_str)

    return _memoized_blocks(("fused", m, k, n, dtype_str), compute)
