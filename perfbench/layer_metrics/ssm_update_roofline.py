"""The one-token state update's kernel (known by its ``name=``) against its
roofline: the larger of (the least bytes it has to move: each decoding row's
recurrent state read and written once in every layer that keeps one) over
the chip's bandwidth and (its operations) over the chip's peak, the family's
own counts, over the kernel's device time in the traced span. The counts
are of the steps that lie wholly inside the span and of the rows that
decoded in them (each emitted a token; a prefilling row's chunk goes through
the chunk scan, not this kernel), the time is of every run in the span: it
reads low at the span's edges and cannot pass 100. Nothing here is an
expectation: the program counts the rows."""

from perfbench import peaks, xplane
from perfbench.layer_metrics.latent_attn_roofline import span_steps

PATTERN = r"ssm_state_update"


def read(rec):
    count_bytes = getattr(rec.family, "ssm_update_min_bytes", None)
    if rec.trace is None or count_bytes is None:
        return None
    secs = xplane.seconds_matching(rec.trace["ops_s"], PATTERN)
    rows = sum(s[3] for s in span_steps(rec))
    if not secs or not rows:
        return None
    chip = peaks.peaks_for(rec.device_kind)
    floor_s = max(
        count_bytes(rec.sizes, rows) / chip["hbm_bytes_per_s"],
        rec.family.ssm_update_flops(rec.sizes, rows) / chip["bf16_flops"])
    return 100.0 * floor_s / secs
