"""The gated short convolution's one-token update (the kernel known by its
``name=``) against its roofline: the larger of (the least bytes it has to
move through HBM: a decoding row's held window read and its new input
written, in every layer that keeps one; the family's count) over the chip's
bandwidth
and (its operations) over the chip's peak, the family's own counts, over the
kernel's device time in the traced span. The row-layers are the PROGRAM'S
count: the ``conv_rows_advanced`` attribute of its ``decode_step`` spans of
the traced span (a live token a conv layer; a mixed step's count holds its
chunks' tokens too, which do not go through this kernel, so its spans are
left out while the kernel's runs inside it are timed): it reads low and
cannot pass 100. Nothing here is an expectation. The kernel moves about a
megabyte a call, so what bounds it is a call's latency, not the bandwidth.
A program without the kernel or the attribute (an older commit) gives
nothing to read."""

from perfbench import peaks, program_spans, xplane

PATTERN = r"short_conv_update"
ROWS = "conv_rows_advanced"


def read(rec):
    count_bytes = getattr(rec.family, "short_conv_min_bytes", None)
    if rec.trace is None or count_bytes is None:
        return None
    secs = xplane.seconds_matching(rec.trace["ops_s"], PATTERN)
    rows = sum(r.attrs[ROWS] for r in program_spans.spans(rec) or ()
               if r.name == "decode_step" and r.attrs and ROWS in r.attrs)
    if not secs or not rows:
        return None
    chip = peaks.peaks_for(rec.device_kind)
    floor_s = max(
        count_bytes(rec.sizes, rows) / chip["hbm_bytes_per_s"],
        rec.family.short_conv_flops(rec.sizes, rows) / chip["bf16_flops"])
    return 100.0 * floor_s / secs
