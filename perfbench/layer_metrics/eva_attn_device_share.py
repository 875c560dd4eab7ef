"""Device time of EVA attention's calls (the two builds of the block walk
known by their ``name=``: ``eva_attn_window`` over the ring, ``eva_attn_summary``
over the chunk summaries) over device busy time."""

from perfbench import readers
from perfbench.layer_metrics.eva_attn_roofline import PATTERN


def read(rec):
    return readers.trace_share(rec, PATTERN)
