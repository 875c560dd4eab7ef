"""Device time of the latent paged-attention kernel (known by its ``name=``)
over device busy time."""

from perfbench import readers
from perfbench.layer_metrics.latent_attn_roofline import PATTERN


def read(rec):
    return readers.trace_share(rec, PATTERN)
