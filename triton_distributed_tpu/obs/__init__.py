"""Unified observability layer: trace spans, metrics, and the comm ledger.

One substrate, three views, threaded through every layer of the stack:

  obs.trace        host-side span tracer (nested spans on
                   ``time.monotonic()``, per-process ring buffer; records
                   while enabled or while a profiler capture is live).
                   Spans emit ``jax.profiler.TraceAnnotation`` scopes with
                   their attributes so they land inside XProf captures;
                   the ring buffer exports merged
                   per-rank Chrome trace-event JSON for Perfetto. Also owns
                   ``group_profile`` (the XProf capture context re-exported
                   via ``runtime/utils.py``).
  obs.metrics      label-aware counters / gauges / histograms with flat
                   dict, delta-snapshot, and Prometheus text exposition.
                   ``serving.metrics`` is a re-export shim over this.
  obs.comm_ledger  per-(collective, axis) ledger of wire bytes, call
                   counts, and achieved-vs-``perf_model``-estimated
                   latency, fed by every collective entry point in
                   ``kernels/``. Near-zero-overhead no-op when disabled.

Always-on serving telemetry (bounded, constant-memory — a serving loop
runs for weeks):

  obs.window       time-bucketed ring of fixed log-spaced value buckets:
                   trailing-window ("last 10 s / 5 min") quantiles and
                   violation fractions at memory constant in request
                   count. ``Metrics(windowed=True)`` feeds it.
  obs.slo          declarative SLO objectives (ttft_p99, tbt_p99, error
                   rate, hit-rate floor) evaluated with fast+slow
                   burn-rate windows -> OK/WARN/BREACH state machine;
                   BREACH fires the resilience snapshot path.
  obs.blackbox     flight recorder: bounded ring of structured serving
                   lifecycle events, dumped whole into breach snapshots.
  obs.journey      request-journey tracing: per-request hop ids threaded
                   router -> replica -> scheduler -> engine, stitched
                   into one causal timeline with critical-path latency
                   attribution (queue/route/prefill/decode/preempted/
                   requeue fractions summing to 1); tail-kept detail,
                   O(1) summaries for everyone else.
  TailSampler      (obs.trace) per-request trace sampling that always
                   keeps slow/errored requests plus a deterministic
                   head-sampled fraction.
  obs.incident     always-on incident engine: deterministic robust-z +
                   CUSUM changepoint detectors with hysteresis over the
                   live signal set, cross-layer forensic auto-triage
                   into a ranked suspect list, and a bounded incident
                   ring with cross-replica merge.
  obs.replay       deterministic replay & what-if observatory: the
                   always-on ``ServeTrace`` recorder (arrivals, knobs,
                   calibrated virtual-time cost model), the
                   ``ReplayHarness`` that re-runs a trace through the
                   real fleet bit-identically or under counterfactual
                   configs, and the ranked ``WhatIfReport``.

Perf flight recorder (on top of the three views above):

  obs.roofline     joins the comm ledger with ``runtime/perf_model``
                   bounds: classifies every collective / step as compute-,
                   HBM- or ICI-bound and emits per-site
                   ``achieved_over_bound`` efficiency fractions.
  obs.perfdb       append-only JSONL run database keyed by an environment
                   fingerprint, with robust (best-quartile) delta
                   statistics and ``compare()`` verdicts —
                   ``tools/perf_gate.py`` gates CI on it.

Everything here is disabled by default and costs one attribute check per
call site when off — the serving/bench hot paths carry the hooks
permanently. Design note: docs/observability.md.
"""

from triton_distributed_tpu.obs import blackbox  # noqa: F401
from triton_distributed_tpu.obs import comm_ledger  # noqa: F401
from triton_distributed_tpu.obs import efficiency  # noqa: F401
from triton_distributed_tpu.obs import incident  # noqa: F401
from triton_distributed_tpu.obs import journey  # noqa: F401
from triton_distributed_tpu.obs import perfdb  # noqa: F401
from triton_distributed_tpu.obs import replay  # noqa: F401
from triton_distributed_tpu.obs import roofline  # noqa: F401
from triton_distributed_tpu.obs import slo  # noqa: F401
from triton_distributed_tpu.obs import trace  # noqa: F401
from triton_distributed_tpu.obs import window  # noqa: F401
from triton_distributed_tpu.obs.blackbox import Blackbox  # noqa: F401
from triton_distributed_tpu.obs.journey import (  # noqa: F401
    Journey,
    JourneyContext,
    JourneyRecorder,
)
from triton_distributed_tpu.obs.comm_ledger import (  # noqa: F401
    CommLedger,
    LedgerEntry,
)
from triton_distributed_tpu.obs.efficiency import (  # noqa: F401
    EfficiencyLedger,
    StepAttribution,
)
from triton_distributed_tpu.obs.incident import (  # noqa: F401
    Incident,
    IncidentEngine,
    SignalSpec,
)
from triton_distributed_tpu.obs.perfdb import (  # noqa: F401
    FingerprintMismatch,
    PerfDB,
    RunRecord,
    Verdict,
)
from triton_distributed_tpu.obs.replay import (  # noqa: F401
    CostModel,
    ReplayHarness,
    ReplayResult,
    ServeTrace,
    WhatIfConfig,
    WhatIfReport,
)
from triton_distributed_tpu.obs.roofline import RooflineRecord  # noqa: F401
from triton_distributed_tpu.obs.metrics import (  # noqa: F401
    Histogram,
    Metrics,
    parse_prometheus,
)
from triton_distributed_tpu.obs.slo import (  # noqa: F401
    Objective,
    SLOEngine,
    default_serving_slo,
)
from triton_distributed_tpu.obs.trace import (  # noqa: F401
    RequestTrace,
    SpanRecord,
    TailSampler,
    Tracer,
    group_profile,
    merge_chrome_traces,
)
from triton_distributed_tpu.obs.window import (  # noqa: F401
    WindowRing,
    WindowStats,
)

__all__ = [
    "Blackbox", "CommLedger", "CostModel", "EfficiencyLedger",
    "FingerprintMismatch", "Histogram", "Incident", "IncidentEngine",
    "Journey", "JourneyContext", "JourneyRecorder", "LedgerEntry",
    "Metrics", "Objective", "PerfDB", "ReplayHarness", "ReplayResult",
    "RequestTrace", "RooflineRecord", "RunRecord", "SLOEngine",
    "ServeTrace", "SignalSpec", "SpanRecord", "StepAttribution",
    "TailSampler", "Tracer", "Verdict", "WhatIfConfig", "WhatIfReport",
    "WindowRing", "WindowStats", "blackbox", "comm_ledger",
    "default_serving_slo", "efficiency", "group_profile", "incident",
    "journey", "merge_chrome_traces", "parse_prometheus", "perfdb",
    "replay", "roofline", "slo", "trace", "window",
]
