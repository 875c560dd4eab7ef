"""Tutorial 10 — End-to-end inference: models and the engine.

What you learn:

* The L7/L8 stack: ``ModelConfig`` presets (Qwen3 0.6b–32b, Llama-3
  family, ``tiny``), the scan-stacked decoder (``Qwen3`` — one compiled
  layer body for all layers), the donated ``KVCache``, and ``Engine``.
* The three forward modes and when each wins (reference
  ``torch`` / ``triton_dist`` / ``triton_dist_AR``):
  ``dist`` = AG-GEMM → attention → GEMM-RS per layer (large M),
  ``ar`` = local GEMMs + fused one-shot AllReduce (small-M decode),
  ``xla`` = jnp + lax collectives (the golden).
  All three generate TOKEN-FOR-TOKEN identically.
* The CUDA-Graph analogs: the jitted decode step (fixed shapes — one
  compiled program serves every step), and ``serve_scanned`` — prefill +
  the WHOLE decode loop as one ``lax.scan`` executable (one dispatch
  generates N tokens; essential when host dispatch latency dwarfs a
  sub-ms step).

Run:  python tutorials/10-e2e-inference-engine.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bootstrap import force_virtual_mesh  # noqa: E402

force_virtual_mesh(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from triton_distributed_tpu.models import Engine, ModelConfig  # noqa: E402
from triton_distributed_tpu.runtime.mesh import make_mesh  # noqa: E402

# Interpreter-sized: one dist-mode forward is tens of thousands of
# interpreter callbacks whatever the batch (about 20 s at TP=4, 65 s at
# TP=8), so the tutorial runs TP=2 and generates two tokens per mode: four
# dist forwards, the fewest that still print every line below. The long
# form is tests/test_qwen_e2e.py: the same three modes at TP=4 (TP=8 for
# ``ar``) and a scanned loop of three tokens, whose body runs twice.
WORLD, B, L0, GEN = 2, 8, 4, 2


def main():
    mesh = make_mesh({"tp": WORLD}, devices=jax.devices()[:WORLD])
    config = ModelConfig.from_name("tiny")   # interpreter-sized; real runs
    # use e.g. ModelConfig.from_name("Qwen/Qwen3-32B") on a v5p slice.
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, L0), 0,
                             config.vocab_size, jnp.int32)

    # Same random params for every engine so tokens are comparable.
    from triton_distributed_tpu.models import Qwen3

    params = Qwen3(config, block_n=8).init(jax.random.PRNGKey(0), mesh)

    # One Engine per mode: an Engine keeps its compiled steps, so reuse it.
    engines = {mode: Engine(config, mesh=mesh, mode=mode, params=params,
                            block_n=8)
               for mode in ("xla", "dist", "ar")}

    golden = np.asarray(engines["xla"].serve(ids, GEN))
    print(f"  xla golden tokens: {golden[0].tolist()} ...")

    for mode in ("dist", "ar"):
        got = np.asarray(engines[mode].serve(ids, GEN))
        np.testing.assert_array_equal(got, golden)
        print(f"  mode={mode:4s} tokens match the xla golden exactly")

    scanned = np.asarray(engines["dist"].serve_scanned(ids, GEN))
    np.testing.assert_array_equal(scanned, golden)
    print("  serve_scanned (whole decode loop, ONE executable) matches too")
    print("tutorial 10 ok: e2e engine, three modes, scanned decode loop")


if __name__ == "__main__":
    main()
