"""Closed loop: ``clients`` callers, each sends its next request when its
last one has finished. The window opens on the loop's steady state and not
on its start: each client's first request is one caught part-way, at an
evenly spread share of its answer. What it would have generated up to there
is already in its prompt, and the rest is what it asks for. So the clients
are out of step and the caches are as long as they are in the middle of
such traffic, without the minutes of decoding that would build them. These
first requests are the standing population: set-up prefills them.

The schedule (which lengths meet which share, and which lengths follow) is
the same for every seed; the seed gives the token ids and the weights. A
window finishes some eight requests of thousands of tokens, so how many of
the standing ones end inside it, and how many chunks the few that follow
bring, IS the work: dealt out by the seed, the same sizes spread tokens per
second by 1.4% in a model of the step loop, against 0.1-0.6% measured
between runs of one schedule."""

from __future__ import annotations

from perfbench import lengths
from perfbench.traffic_kinds import Planned


class Plan:
    def __init__(self, params, *, seed, seconds, vocab, max_total, n_slots):
        clients = params["clients"]
        self.clients = n_slots if clients == "n_slots" else int(clients)
        later = self.clients * (int(params["rounds"]) - 1)

        def sizes(n, stream):
            return [lengths.fit_lengths(p, o, max_total) for p, o in zip(
                lengths.dealt(lengths.lognormal_quantiles(
                    n, **params["prompt"]), lengths.rng_for(0, stream)),
                lengths.dealt(lengths.lognormal_quantiles(
                    n, **params["output"]), lengths.rng_for(0, stream + 1)))]

        shares = lengths.dealt(
            [(i + 0.5) / self.clients for i in range(self.clients)],
            lengths.rng_for(0, 3))
        self._first = []
        for (p, o), share in zip(sizes(self.clients, 1), shares):
            given = min(o - 1, round(o * share))
            self._first.append((p + given, o - given))
        self._later = sizes(later, 5) if later else list(self._first)
        self._ids = lengths.rng_for(seed, 4)
        self._vocab = vocab
        self._taken = 0
        self._ready = []
        self._open = True

    def standing(self):
        return [Planned(0.0, lengths.token_ids(self._ids, p, self._vocab), o,
                        client, standing=True)
                for client, (p, o) in enumerate(self._first)]

    def take_due(self, now_s):
        out = []
        while self._open and self._ready and self._ready[0][0] <= now_s:
            due, client = self._ready.pop(0)
            p, o = self._later[self._taken % len(self._later)]
            self._taken += 1
            out.append(Planned(due, lengths.token_ids(self._ids, p,
                                                      self._vocab), o, client))
        return out

    def next_due_s(self):
        return self._ready[0][0] if self._open and self._ready else None

    def on_finish(self, planned, now_s):
        self._ready.append((now_s, planned.client))

    def close(self):
        self._open = False
