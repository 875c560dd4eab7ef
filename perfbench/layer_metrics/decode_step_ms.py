"""Median wall time of the fleet steps in which only a decode step ran
(the call ends with the tokens on the host)."""

from perfbench import readers


def read(rec):
    return readers.step_ms(rec, "decode")
