"""Efficiency ledger: where every serving wall-second went, and who used it.

The ROADMAP's north star ("as fast as the hardware allows") is unverifiable
without a live answer to *what fraction of peak are we sustaining, and where
does the rest of the time go*. This module is that accounting substrate: an
always-on, bounded-memory ledger that decomposes every ``BatchEngine.step()``
wall interval into fractions that telescope to exactly 1.0:

  compute   modeled MXU seconds — ``perf_model.step_flops`` over the step's
            (new_tokens, kv_len) rows, divided by ``peak_bf16_flops``.
  hbm       modeled HBM seconds — ``perf_model.step_hbm_bytes`` (weight
            stream + ``paged_attn_bytes`` pool traffic) over ``hbm_bw``.
  comm      achieved collective wall seconds this step (the comm ledger's
            ``wall_s_total`` delta; zero when the ledger is disabled).
  stall     the in-step residual: device time not accounted by the models
            above (DMA waits, sem spins, launch overhead, Python dispatch).
            When a kprobe ``stall_summary`` is supplied it is split into
            dma_wait / sem_spin / other detail — refinement, never a
            reclassification.
  bubble    the HOST gap between consecutive steps: scheduler, controller,
            router, journey-recorder, token post-processing — everything
            the device spent idle waiting for the host.

The allocation is sequential-clamped (compute, then hbm, then comm eat the
step wall; stall is the remainder; bubble is the measured gap), so the five
seconds always sum to the interval and the fractions sum to 1.0 — the
``bench.py --serve --efficiency`` arm asserts |sum - 1| <= 1e-6 per step.

From the same feed the ledger derives live windowed MFU / MBU /
``bubble_frac`` (constant-memory ``obs.window.WindowRing`` counters),
attributes step resources to tenants (token-weighted FLOP-seconds and
HBM-byte-seconds, billed on the replica where the work actually ran — so
fleet kill+requeue conserves totals by construction), and keeps a bounded
worst-bubble ring for blackbox correlation (``tools/fleet_efficiency.py``).

Memory is constant in steps and requests: fixed window rings, a bounded
recent-step deque, a top-k worst-bubble list, and a capped tenant table
(overflow bills to ``~overflow``). Pure host-side data — feeding the ledger
never touches compiled state, so ``trace_counts`` stays {1,1} and greedy
output stays bit-identical with the ledger on.
"""

from __future__ import annotations

import dataclasses
import time

from triton_distributed_tpu.obs.window import WindowRing

# Attribution buckets, in allocation order (see module docstring).
BUCKETS = ("compute", "hbm", "comm", "stall", "bubble")
# |sum(fracs) - 1| tolerance the bench arm and tests assert per step.
FRAC_TOL = 1e-6
# Trailing windows every stats frame reports (matches the engine's
# snapshot windows: "now" view and trend view).
_WINDOWS = ((10.0, "10s"), (300.0, "5m"))
# Default windowed-query span for the headline mfu()/mbu()/bubble_frac().
_DEFAULT_WINDOW_S = 60.0


@dataclasses.dataclass
class StepAttribution:
    """One step's accounted interval: seconds per bucket plus the fractions
    of the full interval (gap + step wall), telescoping to exactly 1.0."""

    step: int
    t_start: float
    t_end: float
    interval_s: float          # bubble + wall
    wall_s: float              # dispatch-to-sync step time
    seconds: dict              # {bucket: s}, sums to interval_s
    fracs: dict                # {bucket: frac}, sums to 1.0 (FRAC_TOL)
    flops: float
    hbm_bytes: float
    comm_s: float
    tokens: int
    stall_detail: dict | None = None   # kprobe split of the stall bucket

    @property
    def frac_sum(self) -> float:
        return sum(self.fracs.values())

    def as_dict(self) -> dict:
        return {
            "step": self.step,
            "t_start": round(self.t_start, 6),
            "t_end": round(self.t_end, 6),
            "interval_s": round(self.interval_s, 9),
            "wall_s": round(self.wall_s, 9),
            "seconds": {k: round(v, 9) for k, v in self.seconds.items()},
            "fracs": {k: round(v, 9) for k, v in self.fracs.items()},
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "tokens": self.tokens,
            **({"stall_detail": self.stall_detail}
               if self.stall_detail else {}),
        }


@dataclasses.dataclass
class TenantAccount:
    """Accumulated cost of one tenant: tokens plus token-weighted shares of
    the modeled resources (FLOP-seconds = seconds of device compute the
    tenant's tokens consumed; likewise HBM seconds)."""

    tenant: str
    tokens: int = 0
    steps: int = 0
    flop_s: float = 0.0
    hbm_s: float = 0.0
    flops: float = 0.0
    hbm_bytes: float = 0.0
    wall_s: float = 0.0        # token-weighted share of accounted interval

    def as_dict(self) -> dict:
        return {"tenant": self.tenant, "tokens": self.tokens,
                "steps": self.steps, "flop_s": round(self.flop_s, 9),
                "hbm_s": round(self.hbm_s, 9), "flops": self.flops,
                "hbm_bytes": self.hbm_bytes,
                "wall_s": round(self.wall_s, 9)}


class EfficiencyLedger:
    """Per-engine efficiency accounting (one per ``BatchEngine``).

    ``peak_flops`` / ``hbm_bw``  hardware ceilings (flops/s, bytes/s);
                   default from ``perf_model.detect_hardware()``.
    ``clock``      injectable time source (tests drive a virtual step
                   clock; same pattern as ``WindowRing`` / journey).
    ``keep_steps`` bounded deque of recent ``StepAttribution``s — what the
                   bench arm's frac-sum assertion and the forensic report
                   read; memory cap, not history.
    ``worst_k``    how many worst-bubble steps to retain.
    ``max_tenants`` tenant-table cap; tenants past the cap bill to the
                   ``~overflow`` row so totals stay conserved.
    """

    OVERFLOW_TENANT = "~overflow"

    def __init__(self, *, peak_flops: float | None = None,
                 hbm_bw: float | None = None, clock=time.monotonic,
                 keep_steps: int = 128, worst_k: int = 8,
                 max_tenants: int = 64, bucket_s: float = 0.25,
                 n_buckets: int = 1440):
        if peak_flops is None or hbm_bw is None:
            # Lazy import: perf_model pulls in jax; the ledger itself must
            # stay importable anywhere obs/ is.
            from triton_distributed_tpu.runtime import perf_model as pm
            hw = pm.detect_hardware()
            peak_flops = peak_flops or hw.peak_bf16_flops
            hbm_bw = hbm_bw or hw.hbm_bw
        if peak_flops <= 0 or hbm_bw <= 0:
            raise ValueError("peak_flops and hbm_bw must be positive")
        self.peak_flops = float(peak_flops)
        self.hbm_bw = float(hbm_bw)
        self.clock = clock
        self.keep_steps = int(keep_steps)
        self.worst_k = int(worst_k)
        self.max_tenants = int(max_tenants)
        ring = dict(bucket_s=bucket_s, n_buckets=n_buckets, bounds=None,
                    clock=clock)
        self._w_flops = WindowRing(**ring)
        self._w_bytes = WindowRing(**ring)
        self._w_interval = WindowRing(**ring)
        self._w_bubble = WindowRing(**ring)
        self._recent: list[StepAttribution] = []
        self._worst: list[dict] = []
        self._tenants: dict[str, TenantAccount] = {}
        self._tot_seconds = dict.fromkeys(BUCKETS, 0.0)
        self._tot_flops = 0.0
        self._tot_bytes = 0.0
        self._tot_interval = 0.0
        self._tot_wall = 0.0
        self._tokens = 0
        self._steps = 0
        self._frac_sum_violations = 0
        self._t_start: float | None = None
        self._last_end: float | None = None

    # -- per-step feed -------------------------------------------------------

    def step_begin(self, now: float | None = None) -> float:
        """Mark the start of one compiled-step dispatch. Host time since
        the previous ``step_end`` becomes that step's bubble."""
        now = self.clock() if now is None else now
        self._t_start = now
        return now

    def step_end(self, *, flops: float, hbm_bytes: float,
                 comm_s: float = 0.0, tokens: int = 0,
                 tenants: dict | None = None,
                 stall_summary: dict | None = None,
                 now: float | None = None) -> StepAttribution:
        """Account one completed step. ``tenants`` maps tenant name to the
        token positions it consumed this step; the step's resources are
        split token-weighted across them."""
        now = self.clock() if now is None else now
        t_start = now if self._t_start is None else self._t_start
        self._t_start = None
        if self._last_end is not None:
            # A step dispatched before the one before it ended (the
            # serving loop keeps one in flight) could start only then:
            # its interval opens there, with no bubble.
            t_start = max(t_start, self._last_end)
        bubble_s = (t_start - self._last_end
                    if self._last_end is not None else 0.0)
        wall_s = max(0.0, now - t_start)
        self._last_end = now
        interval = bubble_s + wall_s
        flops = max(0.0, float(flops))
        hbm_bytes = max(0.0, float(hbm_bytes))
        # Sequential-clamped allocation inside the step wall: the modeled
        # terms can never over-account the measured wall, and the pieces
        # sum to the interval EXACTLY by construction.
        compute_s = min(wall_s, flops / self.peak_flops)
        hbm_s = min(wall_s - compute_s, hbm_bytes / self.hbm_bw)
        comm_acct = min(wall_s - compute_s - hbm_s, max(0.0, float(comm_s)))
        stall_s = wall_s - compute_s - hbm_s - comm_acct
        seconds = {"compute": compute_s, "hbm": hbm_s, "comm": comm_acct,
                   "stall": stall_s, "bubble": bubble_s}
        if interval > 0:
            fracs = {k: v / interval for k, v in seconds.items()}
            # Absorb the float rounding residue into the largest bucket so
            # the telescoping-to-1.0 contract is exact, not approximate.
            err = 1.0 - sum(fracs.values())
            fracs[max(fracs, key=fracs.get)] += err
        else:
            # Degenerate zero-length interval (frozen virtual clock):
            # nothing to attribute; bill the unit to stall for stability.
            fracs = dict.fromkeys(BUCKETS, 0.0)
            fracs["stall"] = 1.0
        detail = None
        if stall_summary and stall_s > 0:
            dma = float(stall_summary.get("pct_dma_wait", 0.0)) / 100.0
            sem = float(stall_summary.get("pct_sem_spin", 0.0)) / 100.0
            dma, sem = max(0.0, dma), max(0.0, sem)
            scale = min(1.0, dma + sem)
            if dma + sem > 1.0:
                dma, sem = dma / (dma + sem), sem / (dma + sem)
            detail = {"dma_wait_s": round(stall_s * dma, 9),
                      "sem_spin_s": round(stall_s * sem, 9),
                      "other_s": round(stall_s * (1.0 - min(1.0, dma + sem)
                                                  if scale <= 1.0 else 0.0),
                                       9)}
        att = StepAttribution(
            step=self._steps, t_start=t_start, t_end=now,
            interval_s=interval, wall_s=wall_s, seconds=seconds,
            fracs=fracs, flops=flops, hbm_bytes=hbm_bytes,
            comm_s=comm_acct, tokens=int(tokens), stall_detail=detail)
        if abs(att.frac_sum - 1.0) > FRAC_TOL:
            self._frac_sum_violations += 1
        self._recent.append(att)
        if len(self._recent) > self.keep_steps:
            del self._recent[0]
        if bubble_s > 0:
            self._note_worst(att, bubble_s)
        self._w_flops.observe(flops, now)
        self._w_bytes.observe(hbm_bytes, now)
        self._w_interval.observe(interval, now)
        self._w_bubble.observe(bubble_s, now)
        for k, v in seconds.items():
            self._tot_seconds[k] += v
        self._tot_flops += flops
        self._tot_bytes += hbm_bytes
        self._tot_interval += interval
        self._tot_wall += wall_s
        self._tokens += int(tokens)
        self._steps += 1
        if tenants:
            self._bill_tenants(tenants, att)
        return att

    def _note_worst(self, att: StepAttribution, bubble_s: float) -> None:
        row = {"step": att.step, "bubble_s": round(bubble_s, 9),
               "interval_s": round(att.interval_s, 9),
               # The GAP interval [t0, t1] — what blackbox events (same
               # monotonic clock domain) correlate against.
               "t0": round(att.t_start - bubble_s, 6),
               "t1": round(att.t_start, 6)}
        self._worst.append(row)
        self._worst.sort(key=lambda r: -r["bubble_s"])
        del self._worst[self.worst_k:]

    def _bill_tenants(self, tenants: dict, att: StepAttribution) -> None:
        total_toks = sum(tenants.values())
        if total_toks <= 0:
            return
        for name, ntok in tenants.items():
            name = str(name)
            acct = self._tenants.get(name)
            if acct is None:
                if len(self._tenants) >= self.max_tenants:
                    name = self.OVERFLOW_TENANT
                    acct = self._tenants.get(name)
                if acct is None:
                    acct = self._tenants[name] = TenantAccount(tenant=name)
            share = ntok / total_toks
            acct.tokens += int(ntok)
            acct.steps += 1
            acct.flop_s += share * att.seconds["compute"]
            acct.hbm_s += share * att.seconds["hbm"]
            acct.flops += share * att.flops
            acct.hbm_bytes += share * att.hbm_bytes
            acct.wall_s += share * att.interval_s

    # -- derived views -------------------------------------------------------

    def mfu(self, window_s: float = _DEFAULT_WINDOW_S,
            now: float | None = None) -> float:
        """Windowed model-FLOP utilization: modeled FLOPs over the window's
        accounted intervals, against peak. Computed over ACCOUNTED seconds
        (not raw wall time), so short runs and virtual clocks read true."""
        t = self._w_interval.sum(window_s, now)
        if t <= 0:
            return 0.0
        return self._w_flops.sum(window_s, now) / (self.peak_flops * t)

    def mbu(self, window_s: float = _DEFAULT_WINDOW_S,
            now: float | None = None) -> float:
        """Windowed memory-bandwidth utilization (modeled HBM bytes over
        the window vs the pipe)."""
        t = self._w_interval.sum(window_s, now)
        if t <= 0:
            return 0.0
        return self._w_bytes.sum(window_s, now) / (self.hbm_bw * t)

    def bubble_frac(self, window_s: float = _DEFAULT_WINDOW_S,
                    now: float | None = None) -> float:
        """Windowed host-bubble fraction: inter-step gap seconds over the
        accounted interval seconds."""
        t = self._w_interval.sum(window_s, now)
        if t <= 0:
            return 0.0
        return self._w_bubble.sum(window_s, now) / t

    @property
    def steps(self) -> int:
        return self._steps

    @property
    def frac_sum_ok(self) -> bool:
        """True when every accounted step telescoped to 1.0 +/- FRAC_TOL."""
        return self._frac_sum_violations == 0

    @property
    def recent(self) -> list[StepAttribution]:
        return list(self._recent)

    def lifetime_mfu(self) -> float:
        if self._tot_interval <= 0:
            return 0.0
        return self._tot_flops / (self.peak_flops * self._tot_interval)

    def lifetime_mbu(self) -> float:
        if self._tot_interval <= 0:
            return 0.0
        return self._tot_bytes / (self.hbm_bw * self._tot_interval)

    def lifetime_bubble_frac(self) -> float:
        if self._tot_interval <= 0:
            return 0.0
        return self._tot_seconds["bubble"] / self._tot_interval

    def totals(self) -> dict:
        """Plain-number lifetime totals — what the fleet sums across
        replicas for aggregate efficiency (ratios never sum; totals do)."""
        return {"steps": self._steps, "tokens": self._tokens,
                "flops": self._tot_flops, "hbm_bytes": self._tot_bytes,
                "interval_s": self._tot_interval, "wall_s": self._tot_wall,
                "seconds": dict(self._tot_seconds),
                "frac_sum_violations": self._frac_sum_violations}

    def tenant_table(self) -> list[dict]:
        """Per-tenant cost rows, most expensive (FLOP-seconds) first, with
        each row's ``cost_frac`` share of the total metered compute."""
        rows = [a.as_dict() for a in self._tenants.values()]
        total = sum(r["flop_s"] for r in rows) or 1.0
        for r in rows:
            r["cost_frac"] = round(r["flop_s"] / total, 6)
        rows.sort(key=lambda r: (-r["flop_s"], r["tenant"]))
        return rows

    def stats(self) -> dict:
        """One JSON-able frame — what ``stats_snapshot()['efficiency']``
        carries and ``serve_top``'s eff pane renders."""
        now = self.clock()
        out: dict = {
            "steps": self._steps,
            "tokens": self._tokens,
            "flops_total": self._tot_flops,
            "hbm_bytes_total": self._tot_bytes,
            "accounted_s": round(self._tot_interval, 6),
            "mfu": round(self.lifetime_mfu(), 6),
            "mbu": round(self.lifetime_mbu(), 6),
            "bubble_frac": round(self.lifetime_bubble_frac(), 6),
            "frac_sum_ok": self.frac_sum_ok,
            "fracs": {k: round(v / self._tot_interval, 6)
                      if self._tot_interval > 0 else 0.0
                      for k, v in self._tot_seconds.items()},
            "windows": {label: {
                "mfu": round(self.mfu(w, now), 6),
                "mbu": round(self.mbu(w, now), 6),
                "bubble_frac": round(self.bubble_frac(w, now), 6),
            } for w, label in _WINDOWS},
            "tenants": self.tenant_table(),
            "worst_bubble": list(self._worst),
        }
        return out

    def perfdb_sample(self) -> dict:
        """Flat metrics for the perf flight recorder. ``mfu``/``mbu`` gate
        higher-better, ``bubble_frac`` lower-better (the perfdb direction
        overrides); ``tenant_*`` keys ride along informationally."""
        out = {"mfu": self.lifetime_mfu(), "mbu": self.lifetime_mbu(),
               "bubble_frac": self.lifetime_bubble_frac(),
               "eff_steps": float(self._steps),
               "eff_frac_sum_violations": float(self._frac_sum_violations),
               "tenant_count": float(len(self._tenants))}
        for row in self.tenant_table():
            out[f"tenant_tokens{{tenant={row['tenant']}}}"] = float(
                row["tokens"])
        return out

    def dump(self) -> dict:
        """Full forensic dump: the stats frame plus every retained step
        attribution (bounded by ``keep_steps``)."""
        return {"stats": self.stats(),
                "recent": [a.as_dict() for a in self._recent]}

    # -- fleet rollup helpers ------------------------------------------------

    @staticmethod
    def aggregate(ledgers) -> dict:
        """Fleet-level efficiency from per-replica ledgers: ratios are
        recomputed from summed totals (never averaged), tenant tables are
        merged by name, frac means weight by accounted interval."""
        ledgers = [led for led in ledgers if led is not None]
        if not ledgers:
            return {}
        flops = sum(led._tot_flops for led in ledgers)
        bytes_ = sum(led._tot_bytes for led in ledgers)
        interval = sum(led._tot_interval for led in ledgers)
        peak = sum(led.peak_flops * led._tot_interval for led in ledgers)
        pipe = sum(led.hbm_bw * led._tot_interval for led in ledgers)
        seconds = dict.fromkeys(BUCKETS, 0.0)
        for led in ledgers:
            for k, v in led._tot_seconds.items():
                seconds[k] += v
        return {
            "steps": sum(led._steps for led in ledgers),
            "tokens": sum(led._tokens for led in ledgers),
            "accounted_s": round(interval, 6),
            "mfu": round(flops / peak, 6) if peak > 0 else 0.0,
            "mbu": round(bytes_ / pipe, 6) if pipe > 0 else 0.0,
            "bubble_frac": round(seconds["bubble"] / interval, 6)
            if interval > 0 else 0.0,
            "fracs": {k: round(v / interval, 6) if interval > 0 else 0.0
                      for k, v in seconds.items()},
            "frac_sum_ok": all(led.frac_sum_ok for led in ledgers),
        }

    @staticmethod
    def merge_tenant_tables(tables) -> list[dict]:
        """Sum per-replica tenant cost tables by tenant name (totals are
        conserved across kill+requeue because billing happened where the
        work ran). Recomputes ``cost_frac`` over the merged total."""
        merged: dict[str, dict] = {}
        for table in tables:
            for row in table:
                m = merged.get(row["tenant"])
                if m is None:
                    merged[row["tenant"]] = {
                        k: v for k, v in row.items() if k != "cost_frac"}
                else:
                    for k in ("tokens", "steps", "flop_s", "hbm_s",
                              "flops", "hbm_bytes", "wall_s"):
                        m[k] += row.get(k, 0)
        rows = list(merged.values())
        total = sum(r["flop_s"] for r in rows) or 1.0
        for r in rows:
            r["cost_frac"] = round(r["flop_s"] / total, 6)
            for k in ("flop_s", "hbm_s", "wall_s"):
                r[k] = round(r[k], 9)
        rows.sort(key=lambda r: (-r["flop_s"], r["tenant"]))
        return rows
