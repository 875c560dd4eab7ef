"""triton_distributed_tpu — a TPU-native distributed compute-communication
overlap framework.

This package provides the capabilities of the Triton-distributed reference
(ByteDance Seed) re-designed for TPU: device-initiated, semaphore-synchronized,
compute-overlapped distributed kernels written in Pallas/Mosaic, plus a library
of TP/EP/SP overlap ops (AG-GEMM, GEMM-RS, AllReduce, MoE AllToAll, distributed
FlashDecode, SP attention), model layers, a Qwen3 inference engine, an AOT
compile path, and a distributed autotuner.

Layer map (mirrors reference SURVEY.md §1, re-based on the TPU stack):

  L4 runtime   -> triton_distributed_tpu.runtime   (mesh bring-up, symmetric
                  workspaces, perf/profiling utils; analog of
                  python/triton_dist/utils.py in the reference)
  L5 language  -> triton_distributed_tpu.language   (wait/notify/rank/shmem-
                  style device API over pltpu semaphores + remote DMA; analog
                  of python/triton_dist/language/)
  L6 kernels   -> triton_distributed_tpu.kernels    (Pallas collective and
                  overlap kernels; analog of python/triton_dist/kernels/)
  L7 layers    -> triton_distributed_tpu.layers     (TP_MLP, TP_Attn, EP, SP)
  L8 models    -> triton_distributed_tpu.models     (Qwen3, KV cache, engine)
  Lx tools     -> triton_distributed_tpu.tools      (autotuner re-export, AOT
                  topology compile + serialized-executable cache, profiler;
                  analog of python/triton_dist/tools/)

The compute path is pure JAX/Pallas; native (C++) runtime IO lives in
``csrc/`` (mmap safetensors reader, built by ``make -C csrc`` and loaded via
ctypes with a pure-Python fallback — runtime/io_native.py). The AOT path is
``tools.aot``:
Mosaic-compilation of every flagship kernel against a detached TPU topology
descriptor at production shapes (tests/test_chip_compile.py) plus a
serialized-executable cache that cuts engine cold-start
(``Engine(aot_cache=True)``).
"""

__version__ = "0.1.0"

from triton_distributed_tpu.runtime.mesh import (  # noqa: F401
    make_mesh,
    get_default_mesh,
    set_default_mesh,
    initialize_distributed,
    Topology,
)
from triton_distributed_tpu.runtime.platform import (  # noqa: F401
    on_tpu,
    resolve_interpret,
)
from triton_distributed_tpu.runtime.utils import (  # noqa: F401
    perf_func,
    dist_print,
    assert_allclose,
)
