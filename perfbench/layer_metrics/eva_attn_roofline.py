"""EVA attention's calls (the block walk's two builds known by their
``name=``, ``eva_attn_window`` and ``eva_attn_summary``) against their
roofline: the larger of (the least bytes they have to read: a K row and a V
row for every exact row and every summary row the decoding rows NEEDED; the
family's count) over the chip's bandwidth and (their operations over the
same rows) over the chip's peak, over the calls' device time in the traced
span. The rows are the PROGRAM'S count: the ``eva_exact_rows`` and
``eva_summary_rows`` attributes of its ``decode_step`` spans of the traced
span (what a decoding row at position p had to read, ``p % window + 1`` and
``(window / chunk) (p // window)``, summed over rows and layers: what the
step needed, not what a walk visited). A mixed step's spans are left out
while the calls inside it are timed, and blocks and tiles are fetched whole:
it reads low and cannot pass 100. A program without the calls or the
attributes (an older commit) gives nothing to read."""

from perfbench import peaks, program_spans, xplane

PATTERN = r"eva_attn"
ROWS = ("eva_exact_rows", "eva_summary_rows")


def read(rec):
    count_bytes = getattr(rec.family, "eva_attn_min_bytes", None)
    if rec.trace is None or count_bytes is None:
        return None
    secs = xplane.seconds_matching(rec.trace["ops_s"], PATTERN)
    rows = sum(r.attrs[name] for r in program_spans.spans(rec) or ()
               if r.name == "decode_step" and r.attrs
               for name in ROWS if name in r.attrs)
    if not secs or not rows:
        return None
    chip = peaks.peaks_for(rec.device_kind)
    floor_s = max(
        count_bytes(rec.sizes, rows) / chip["hbm_bytes_per_s"],
        rec.family.eva_attn_flops(rec.sizes, rows) / chip["bf16_flops"])
    return 100.0 * floor_s / secs
