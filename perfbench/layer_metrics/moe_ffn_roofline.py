"""The routed experts' grouped products (gate-up and down, known by their
``name=``) against their roofline: the larger of (the three matrices of
every expert that got a row) over the chip's bandwidth and (the operations
of the pairs routed here) over its peak, the family's own counts, over the
kernels' device time in the traced span.

How many experts a step touches is the program's to count
(``moe_experts_touched``), and the harness hands a reader only the counters
``system.COUNTERS`` names. Until it names these, the counts here are what a
step's decoding rows give IF every routed expert is as likely as another
(the family's ``moe_expected``; seeded weights and a 0.01 bias make it
nearly so): an expectation, not a bound, stated as such in PERF.md. Rows
that prefill are not counted, nor steps that cross the span's edges, so it
reads low rather than high."""

from perfbench import peaks, xplane
from perfbench.layer_metrics.latent_attn_roofline import span_steps

PATTERN = r"moe_grouped_gemm"


def read(rec):
    expected = getattr(rec.family, "moe_expected", None)
    if rec.trace is None or expected is None:
        return None
    secs = xplane.seconds_matching(rec.trace["ops_s"], PATTERN)
    steps = span_steps(rec)
    if not secs or not steps:
        return None
    pairs = touched = 0.0
    for s in steps:
        p, t = expected(rec.sizes, s[3])
        pairs, touched = pairs + p, touched + t
    chip = peaks.peaks_for(rec.device_kind)
    floor_s = max(
        rec.family.moe_ffn_min_bytes(rec.sizes, touched)
        / chip["hbm_bytes_per_s"],
        rec.family.moe_ffn_flops(rec.sizes, pairs) / chip["bf16_flops"])
    return 100.0 * floor_s / secs
