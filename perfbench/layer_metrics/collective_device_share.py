"""Device time of the collective kernels (known by their ``name=``: the
fused AG-GEMM with its tail product, GEMM-RS, the all-reduce, all-gather,
reduce-scatter and all-to-all kernels) plus XLA's own collective
operations, over device busy time; both are means over the chips' planes.
The fused kernels hold the product they hide their traffic behind, so on a
tensor-parallel decode step this is the linear layers' time, traffic
included: what the overlap leaves exposed is this time less the weights'
bytes at the chip's bandwidth, and the ``breakdown`` has the rows by name.
A program whose kernels carry no name (an older commit) gives nothing to
read."""

import re

from perfbench import xplane

KERNELS = (r"^%?(ag_gemm(_tail)?|gemm_rs|allreduce_(one|two)_shot"
           r"|allgather_(ring|push)|reduce_scatter_(one_shot|ring)"
           r"|ll_allgather|ep_all_to_all_(dispatch|combine)"
           r"|ag_group_gemm|group_gemm_rs|sp_ag_attention)(\.\d+)* = ")
XLA_OPS = (r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute"
           r"|all-to-all)(-start|-done)?(\.\d+)* = ")
PATTERN = f"{KERNELS}|{XLA_OPS}"


def read(rec):
    if rec.trace is None or not rec.trace["busy_s"]:
        return None
    ops = rec.trace["ops_s"]
    if not any(re.search(KERNELS, name) for name in ops):
        return None
    return 100.0 * xplane.seconds_matching(ops, PATTERN) / rec.trace["busy_s"]
