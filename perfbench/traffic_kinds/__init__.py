"""One module per kind of traffic, found by the ``kind`` of a mix's file.

A kind is a class ``Plan(params, *, seed, seconds, vocab, max_total,
n_slots)`` with:

- ``standing()``: the requests in flight when the window opens, each a
  ``Planned`` with ``standing=True``. Set-up submits them and steps until
  each has its first token, so the window opens on the traffic's steady
  state and not on an idle fleet;
- ``take_due(now_s)``: the requests to submit now, each a ``Planned``;
- ``next_due_s()``: when the next one is due, or None;
- ``on_finish(planned, now_s)``: a request finished;
- ``close()``: the window has closed, nothing further becomes due.

Every seed gets the same set of sizes and gaps, dealt out in another order
(``lengths.dealt``), and its own token ids; where requests arrive on a
schedule, a prompt and the gap after it stay together (``open_poisson``). The standing population is the
same set for every seed: how much context the window opens on is not the
seed's to decide.
"""

from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class Planned:
    due_s: float            # seconds after the window opened
    prompt: list
    max_new_tokens: int
    client: int | None = None
    standing: bool = False


def load_kind(kind: str):
    if not kind.replace("_", "").isalnum():
        raise ValueError(f"bad traffic kind {kind!r}")
    return importlib.import_module(f"perfbench.traffic_kinds.{kind}").Plan
