"""The latent paged-attention kernel against its roofline: the larger of
(the least bytes it has to read: every context row once a layer, at the
row's unpadded width) over the chip's bandwidth and (its operations in the
absorbed form) over the chip's peak, the family's own counts, over the
kernel's device time in the traced span. The counts are of the steps that
lie wholly inside the span and of the rows that emitted a token in them (a
prefilling row's re-reads are not counted), the time is of every run in the
span: it reads low at the span's edges and cannot pass 100. The kernel is
known by its ``name=`` in the trace."""

from perfbench import peaks, xplane

PATTERN = r"latent_paged_attention"


def span_steps(rec):
    lo, hi = rec.trace["host_window"]
    return [s for s in rec.steps if lo <= s[0] and s[1] <= hi]


def read(rec):
    count_bytes = getattr(rec.family, "latent_attn_min_bytes", None)
    if rec.trace is None or count_bytes is None:
        return None
    secs = xplane.seconds_matching(rec.trace["ops_s"], PATTERN)
    context = [s[5] for s in span_steps(rec)]
    if not secs or not context:
        return None
    chip = peaks.peaks_for(rec.device_kind)
    floor_s = max(
        count_bytes(rec.sizes, context) / chip["hbm_bytes_per_s"],
        rec.family.latent_attn_flops(rec.sizes, context) / chip["bf16_flops"])
    return 100.0 * floor_s / secs
