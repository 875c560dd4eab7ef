"""One module per model family, found by the ``family`` key of a
configuration's file. Everything that depends on what the model IS sits in
its family's module; no other file of the harness names a model, imports one
from the program or reads a field of its sizes beyond the five below.

A family is a module with:

- ``sizes(cfg) -> object``: the configuration's sizes, hashable (a frozen
  dataclass), with at least ``vocab_size``, ``max_length``, ``dtype``,
  ``n_layers`` and ``d_model``. All else is the family's own.
- ``program(cfg, sizes, seed, mesh, engine_kwargs) -> (model_config,
  params)``: the program's own configuration object and the seeded
  parameters in the program's layout, made on the device in one jitted call,
  born sharded. The one place where a family imports from the program;
  ``system.Served`` makes the mesh before and ``Engine`` and ``Fleet.build``
  after.
- ``global_weights(sizes, key)``: the seeded plain weights outside the
  layers, a dict with at least ``embed`` (``(vocab, d_model)``), and
  ``layer_weights(sizes, key, layer_index)``: those of ONE layer, told which
  (a family with layers of several kinds gives each its own shapes). The same
  keys as ``program`` uses (``weights.keys``), so the reference regenerates
  the program's numbers one layer at a time. The family jits them as it sees
  fit.
- ``head_weights(sizes, global_weights) -> dict`` with ``final_norm``,
  ``head`` (``(d_model, vocab)``) and ``eps``: what ``reference.head_block``
  applies after the last layer.
- ``layer_forward(h, layer_weights, sizes, layer_index, precision)``: the
  plain float32 forward pass of one layer over one whole sequence
  (``h``: ``(S, d_model)``), from ``reference``'s primitives (``rms_norm``,
  ``rope``, ``linear`` with its ``float32`` / ``fp8`` / ``int8`` arithmetic,
  ``attention``) and the family's own equations.
- ``decode_step_min_bytes(sizes, context_lens)``: the least bytes one decode
  step moves through HBM, and whatever further counts of operations or
  bytes the family's own per-layer readers want. A reader reaches them as
  ``rec.family.<count>(rec.sizes, ...)``.

The seeded generators (``weights.randw``, ``norm_weight``, ``keys``), the
reference's driver (``reference.forward_positions``), the comparison
(``check.compare``) and the chip's peaks (``peaks``) are shared by every
family and not copied.
"""

from __future__ import annotations

import importlib
import pkgutil

INTERFACE = ("sizes", "program", "global_weights", "layer_weights",
             "head_weights", "layer_forward", "decode_step_min_bytes")


def known() -> list:
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def load_family(cfg: dict):
    """The module named by the configuration's ``family``. There is no
    default: a file that names none, or one that is not there, fails with
    the list of those that are."""
    from perfbench.core import BenchFailure

    name = cfg.get("family")
    if name not in known():
        raise BenchFailure(
            f"the configuration's file states the family {name!r}; a family "
            f"is a module under perfbench/families/, and there are: {known()}")
    family = importlib.import_module(f"{__name__}.{name}")
    missing = [k for k in INTERFACE if not callable(getattr(family, k, None))]
    if missing:
        raise BenchFailure(f"family {name!r} lacks {missing} "
                           f"(the interface is in families/__init__.py)")
    return family
