"""The mean wait for the first token over every request due in the window,
counted from when it was due."""

from perfbench import readers


def read(rec):
    waits = readers.ttft_ms(rec)
    return sum(waits) / len(waits) if waits else None
