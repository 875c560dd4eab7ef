"""The window build of the paged-attention kernel against its roofline: the
larger of (the least bytes it has to read: ``window`` rows a decoding row a
window layer) over the chip's bandwidth and (its operations over the same
rows) over the chip's peak, the family's own counts, over the kernel's
device time in the traced span. The counts are of the steps that lie wholly
inside the span and of the rows that decoded in them (a prefilling row's
reads are not counted), the time is of every run in the span: it reads low
at the span's edges and cannot pass 100. Blocks and tiles are fetched whole
(a window of 128 lies in 9 blocks of 16), so 70-80 is what the bytes alone
allow; a grid step's latency holds it under that where a slot's window is
half a megabyte. A walk that started at block 0 again would read a few per
cent. The kernel is known by its ``name=`` in the trace; a program without
it (an older commit) gives nothing to read."""

from perfbench import peaks, xplane
from perfbench.layer_metrics.latent_attn_roofline import span_steps

PATTERN = r"window_paged_attention"


def read(rec):
    count_bytes = getattr(rec.family, "window_attn_min_bytes", None)
    if rec.trace is None or count_bytes is None:
        return None
    secs = xplane.seconds_matching(rec.trace["ops_s"], PATTERN)
    rows = sum(s[3] for s in span_steps(rec))
    if not secs or not rows:
        return None
    chip = peaks.peaks_for(rec.device_kind)
    floor_s = max(
        count_bytes(rec.sizes, rows) / chip["hbm_bytes_per_s"],
        rec.family.window_attn_flops(rec.sizes, rows) / chip["bf16_flops"])
    return 100.0 * floor_s / secs
