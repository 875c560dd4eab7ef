"""Device time of the window build of the paged-attention kernel (known by
its ``name=``) over device busy time."""

from perfbench import readers
from perfbench.layer_metrics.window_attn_roofline import PATTERN


def read(rec):
    return readers.trace_share(rec, PATTERN)
