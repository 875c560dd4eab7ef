"""The median wait for the first token (the mean of the two middle waits
where their number is even)."""

import statistics

from perfbench import readers


def read(rec):
    waits = readers.ttft_ms(rec)
    return statistics.median(waits) if waits else None
