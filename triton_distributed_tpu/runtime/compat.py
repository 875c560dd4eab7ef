"""The JAX spellings the rest of the tree imports from one place.

One installation (JAX 0.9.0): ``jax.shard_map``, ``jax.lax.axis_size`` and
the dict form of a remote ``device_id`` all exist, so these are plain
aliases. They keep their names because 39 files import them and the
comm-safety analyzer (``analysis/events.py``) patches ``axis_size`` and
``mesh_device_id`` here to trace kernels on the CPU.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map


def axis_size(axis_name) -> int:
    """Static size of a named mesh axis (patched by the analyzer)."""
    return jax.lax.axis_size(axis_name)


def mesh_device_id(axis: str, peer):
    """Remote-DMA / semaphore ``device_id`` for "rank ``peer`` along mesh
    ``axis``": the dict form, so unnamed axes keep this device's
    coordinates (required on multi-axis meshes)."""
    return {axis: peer}
