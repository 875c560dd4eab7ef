"""The decode step against the HBM roofline: the least bytes a decode step
has to read (every linear weight and the head once, every decoding row's
keys and values once; the family's own count) at the chip's published bandwidth,
over the device time of the decode program's runs in the traced span. Mean
bytes a step over mean device time a run, so the span's edges do no harm.
A lower bound of bytes on each chip's share: it cannot pass 100."""

import re

from perfbench import peaks

PROGRAM = re.compile(r"decode_step")


def read(rec):
    if rec.trace is None:
        return None
    runs = [d for evs in rec.trace["module_events"].values()
            for name, _, d in evs if PROGRAM.search(name)]
    lo, hi = rec.trace["host_window"]
    steps = [s for s in rec.steps if s[2] == "decode" and lo <= s[0]
             and s[1] <= hi]
    if not runs or not steps:
        return None
    per_step = sum(rec.family.decode_step_min_bytes(rec.sizes, [s[5]])
                   for s in steps) / len(steps) / rec.n_chips
    floor_s = per_step / peaks.peaks_for(rec.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (sum(runs) / len(runs) / 1e9)
