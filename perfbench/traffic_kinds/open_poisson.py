"""Open loop: independent users, arrivals on a schedule whether or not
earlier requests have finished. Arrivals at ``rate_rps`` with exponential
gaps; prompt and output lengths log-normal, clipped.

Every seed's window holds the same ``rate_rps x seconds`` lengths and gaps
(evenly spaced quantiles), dealt out by the seed so that every eight
consecutive arrivals span the distributions.

With ``standing`` in the parameters the window opens on the steady state.
The same arrival process is run backwards from the opening for as long as
the longest request lives; a request that arrived ``a`` seconds before is
caught part-way, as it would stand after ``a`` seconds of service at the
stated pace (``prefill_tokens_per_s``, then a token every ``token_s``):
what it would have generated is already in its prompt, the rest is what it
asks for, and one that would have finished is left out. That population is
the same for every seed (its sizes are dealt once, by ``STANDING_DEAL``; the
seed gives its token ids): dealt by the seed, how much context the window
opened on was the seed's doing, and the waits of the window followed it."""

from __future__ import annotations

from perfbench import lengths
from perfbench.traffic_kinds import Planned


STANDING_DEAL = 0      # the one deal of every seed's standing population


class Plan:
    def __init__(self, params, *, seed, seconds, vocab, max_total, n_slots):
        self.rate = float(params["rate_rps"])
        self._params = params
        self._vocab, self._max_total = vocab, max_total
        self._ids = lengths.rng_for(seed, 4)
        self.requests = [
            Planned(t, lengths.token_ids(self._ids, p, vocab), o)
            for t, p, o in self._arrivals(seconds, seed, stream=0)]
        self._next = 0

    def _arrivals(self, span_s, seed, stream):
        """(time, prompt, output) of ``rate x span_s`` arrivals inside
        (0, span_s), in order of time, dealt by ``seed``."""
        params = self._params
        n = max(1, round(self.rate * span_s))

        def deal(values, k):
            return lengths.dealt(values, lengths.rng_for(seed,
                                                         10 * stream + k))

        prompts = deal(lengths.lognormal_quantiles(n, **params["prompt"]), 1)
        outputs = deal(lengths.lognormal_quantiles(n, **params["output"]), 2)
        gaps = deal(lengths.exponential_quantiles(n, 1.0 / self.rate), 3)
        scale = span_s * n / (n + 0.5) / sum(gaps)
        out, t = [], 0.0
        for p, o, g in zip(prompts, outputs, gaps):
            t += g * scale
            out.append((t, *lengths.fit_lengths(p, o, self._max_total)))
        return out

    def standing(self):
        pace = self._params.get("standing")
        if not pace:
            return []
        token_s = float(pace["token_s"])
        prefill = float(pace["prefill_tokens_per_s"])
        lead_s = (self._params["prompt"]["hi"] / prefill
                  + self._params["output"]["hi"] * token_s)
        out = []
        for t, p, o in self._arrivals(lead_s, STANDING_DEAL, stream=1):
            served_s = lead_s - t - p / prefill
            given = max(0, int(served_s / token_s))
            if given < o:
                out.append(Planned(0.0, lengths.token_ids(
                    self._ids, p + given, self._vocab), o - given,
                    standing=True))
        return out

    def take_due(self, now_s):
        out = []
        while (self._next < len(self.requests)
               and self.requests[self._next].due_s <= now_s):
            out.append(self.requests[self._next])
            self._next += 1
        return out

    def next_due_s(self):
        return (self.requests[self._next].due_s
                if self._next < len(self.requests) else None)

    def on_finish(self, planned, now_s):
        pass

    def close(self):
        self._next = len(self.requests)
