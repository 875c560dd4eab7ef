"""Device time of the one-token state update's kernel (known by its
``name=``) over device busy time."""

from perfbench import readers
from perfbench.layer_metrics.ssm_update_roofline import PATTERN


def read(rec):
    return readers.trace_share(rec, PATTERN)
