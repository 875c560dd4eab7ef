"""Platform detection and interpret-mode resolution.

The reference runs its kernels natively on GPU and has no CPU-simulation story
(SURVEY.md §4: "Multi-node without a cluster: not simulated"). We do better:
every Pallas kernel in this framework takes ``interpret=None`` and resolves it
here — on real TPU hardware kernels compile via Mosaic; anywhere else they run
under the Pallas TPU interpreter, which supports inter-chip remote DMA and
semaphores on a virtual CPU mesh (``--xla_force_host_platform_device_count``).
Two served kernels with nothing of the kind to simulate take their plain
``jax.numpy`` equal there instead (``plain_off_tpu``, below).

This is what lets ``tests/`` validate 8-way distributed kernels on a CPU-only
CI box, and it also provides a *race detector*
(``pltpu.InterpretParams(detect_races=True)``) — the analog of running the
reference under ``compute-sanitizer`` (scripts/launch.sh:169).
"""

from __future__ import annotations

import functools
import os
from typing import Any, Union

import jax

InterpretFlag = Union[bool, None, Any]  # Any = pltpu.InterpretParams


@functools.cache
def on_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    try:
        return jax.default_backend() == "tpu"
    except RuntimeError:
        return False


def cache_dir(*parts: str) -> str:
    """``<checkout>/.cache/<parts>`` — the one default home of everything
    the program caches (XLA's persistent compile cache, autotune winners,
    serialized executables). Fixed and inside the checkout: the path is
    part of the compile cache's key, so a directory that moves (home,
    temp, pid, time) never hits; ``.cache/`` is git-ignored. Each cache's
    own environment override still wins (``JAX_COMPILATION_CACHE_DIR``,
    ``TDT_AUTOTUNE_CACHE``, ``TDT_AOT_CACHE``)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".cache", *parts)


def resolve_interpret(interpret: InterpretFlag = None, *, detect_races: bool = False):
    """Resolve an ``interpret`` kernel argument.

    - ``None``  -> interpret iff not running on real TPU hardware.
    - ``True``/``False`` or an ``InterpretParams`` -> passed through,
      except ``True`` is upgraded to ``InterpretParams`` so TPU-specific
      primitives (remote DMA, semaphores) are simulated faithfully.
    """
    from jax.experimental.pallas import tpu as pltpu  # deferred: cheap import path

    if interpret is None:
        interpret = not on_tpu()
    if isinstance(interpret, pltpu.InterpretParams):
        return interpret
    if interpret is True:
        return pltpu.InterpretParams(detect_races=detect_races)
    return interpret  # explicit False: compiled path, even with detect_races


def plain_off_tpu(interpret: InterpretFlag) -> bool:
    """AUTO off the TPU takes the plain form: the ONE rule of the served
    kernels that have no remote DMA or semaphore to simulate and a plain
    ``jax.numpy`` equal standing beside them (``ssm_update
    .ssm_state_update``, ``short_conv_update.short_conv_update``,
    ``moe_utils.grouped_gemm_skip``). Under
    ``interpret=None`` where there is no TPU such a kernel's entry returns
    that equal, so a CPU run of a served step costs XLA's time and not the
    interpreter's callbacks (720 a step of a tiny hybrid). ``True`` is the
    interpreted kernel (its own unit test; ``Engine(..., interpret=True)``
    for a test that wants it inside a step), ``False`` hands Mosaic the
    kernel wherever the process runs (``tests/test_chip_compile.py`` lowers
    for a described chip from a CPU process), and on a TPU every value
    reaches the ``pallas_call``. ``paged_attention`` is not under this rule:
    its plain form is chosen by ``BatchEngine(paged_attn="gather")``."""
    return interpret is None and not on_tpu()
