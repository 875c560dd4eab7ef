"""Device time of the paged-attention kernel over device busy time.

The program gives its Pallas kernels no name (``kernel_metadata={}``), so
the kernel is known by its signature in the trace: the Mosaic custom call
whose first operand is the two-dimensional int32 block table. PERF.md asks
the ``tracing`` issue for a stable ``name=`` to replace this."""

from perfbench import readers

PATTERN = r"custom-call\(s32\[\d+,\d+\].*tpu_custom_call"


def read(rec):
    return readers.trace_share(rec, PATTERN)
