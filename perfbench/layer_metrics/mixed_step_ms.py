"""Median wall time of the fleet steps in which a mixed step ran."""

from perfbench import readers


def read(rec):
    return readers.step_ms(rec, "mixed")
