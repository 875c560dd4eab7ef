"""The benchmark's own tests run on the CPU (Pallas kernels under the
interpreter), like the repo's: ``python -m pytest perfbench/tests -q``.
Nothing here describes a TPU topology or loads the TPU's library."""

import os
import re
import sys

_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
