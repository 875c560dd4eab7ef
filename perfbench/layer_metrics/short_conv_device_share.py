"""Device time of the gated short convolution's one-token update (the
kernel known by its ``name=``) over device busy time."""

from perfbench import readers
from perfbench.layer_metrics.short_conv_roofline import PATTERN


def read(rec):
    return readers.trace_share(rec, PATTERN)
