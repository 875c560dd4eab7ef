"""Rows decoding per decode step over the slots there are."""

from perfbench import readers


def read(rec):
    steps = readers.window_steps(rec, "decode")
    if not steps:
        return None
    return 100.0 * sum(s[3] for s in steps) / (len(steps) * rec.n_slots)
