"""The program's own ``queue_wait_s`` histogram (submit to admission),
samples observed in the window."""

from perfbench import readers


def read(rec):
    return readers.percentile_or_none(
        [s * 1e3 for s in rec.queue_wait_s], 50)
