"""Device time of the routed experts' grouped products (known by their
``name=``) over device busy time."""

from perfbench import readers
from perfbench.layer_metrics.moe_ffn_roofline import PATTERN


def read(rec):
    return readers.trace_share(rec, PATTERN)
