#!/usr/bin/env python
"""On-chip block-size sweep for ag_gemm_single_chip (and jnp.dot baseline).

Usage: python tools/sweep_matmul.py [M K N]

Timing notes: every call carries a constant host-side dispatch cost, and the
first call after switching executables is slower. So: warm each (program,
iters) twice, take the median of the best 3 of 7 calls, and compute the
per-iteration time as the slope between two loop lengths (cancels constant
overhead). Slopes implying > PEAK_TFLOPS are measurement faults and are
retried.
"""

import functools
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from triton_distributed_tpu.kernels.allgather_gemm import ag_gemm_single_chip  # noqa: E402
from triton_distributed_tpu.runtime.utils import dist_print  # noqa: E402

if len(sys.argv) == 1:
    M, K, N = 4096, 5120, 3200
elif len(sys.argv) == 4:
    M, K, N = (int(x) for x in sys.argv[1:4])
else:
    sys.exit("usage: sweep_matmul.py [M K N]  (all three or none)")
SHORT, LONG = 32, 96
PEAK_TFLOPS = 250.0  # above any plausible bf16 peak for this chip


def make_loop(matmul):
    @functools.partial(jax.jit, static_argnames=("n",))
    def loop(a, b, n):
        def body(_, acc):
            bb = b + (acc[0, 0] * 0).astype(b.dtype)
            return acc + matmul(a, bb).astype(jnp.float32)
        return jax.lax.fori_loop(0, n, body, jnp.zeros((M, N), jnp.float32))
    return loop


def _timed(loop, a, b, iters):
    t0 = time.perf_counter()
    out = loop(a, b, iters)
    float(out[0, 0])
    return (time.perf_counter() - t0) * 1e3


def _steady(loop, a, b, iters, calls=7):
    _timed(loop, a, b, iters)
    _timed(loop, a, b, iters)  # absorb executable-switch stalls
    ts = sorted(_timed(loop, a, b, iters) for _ in range(calls))
    return statistics.median(ts[:3])


def slope_ms(loop, a, b, flops, tries=3):
    ms = 1e-6
    for _ in range(tries):
        s = _steady(loop, a, b, SHORT)
        l = _steady(loop, a, b, LONG)
        ms = max((l - s) / (LONG - SHORT), 1e-6)
        if flops / ms / 1e9 <= PEAK_TFLOPS:
            return ms
    return ms  # last attempt, clamped positive even if implausible


def main():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (M, K), jnp.bfloat16)
    b = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.bfloat16)
    flops = 2 * M * K * N

    def report(name, ms):
        dist_print(f"{name:32s}: {ms:7.3f} ms  {flops / ms / 1e9:6.1f} "
                   "TFLOPs", flush=True)

    xla = make_loop(lambda a, b: jnp.dot(
        a, b, preferred_element_type=jnp.float32).astype(jnp.bfloat16))
    report("xla jnp.dot", slope_ms(xla, a, b, flops))

    from triton_distributed_tpu.kernels.allgather_gemm import (
        _matmul_vmem, _VMEM_BUDGET)
    cfgs = [(bm, bn, bk)
            for bm in (256, 512, 1024)
            for bn in (512, 640, 1600)
            for bk in (1280, 2560)
            if _matmul_vmem(bm, bn, bk, 2, 2) <= _VMEM_BUDGET]
    results = []
    for bm, bn, bk in cfgs:
        try:
            loop = make_loop(lambda a, b, bm=bm, bn=bn, bk=bk:
                             ag_gemm_single_chip(a, b, block_m=bm,
                                                 block_n=bn, block_k=bk))
            ms = slope_ms(loop, a, b, flops)
            results.append((ms, bm, bn, bk))
            report(f"pallas bm={bm} bn={bn} bk={bk}", ms)
        except Exception as e:
            dist_print(f"pallas bm={bm} bn={bn} bk={bk}: FAIL "
                       f"{type(e).__name__}", flush=True)
    results.sort()
    dist_print("\nbest:", results[:3])
    report("xla recheck", slope_ms(xla, a, b, flops))


if __name__ == "__main__":
    main()
