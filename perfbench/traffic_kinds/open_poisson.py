"""Open loop: independent users, arrivals on a schedule whether or not
earlier requests have finished. Arrivals at ``rate_rps`` with exponential
gaps; prompt and output lengths log-normal, clipped.

Every seed's window holds the same ``rate_rps x seconds`` lengths and gaps
(evenly spaced quantiles), and the same UNITS of them: a prompt, its answer
and the gap AFTER it are put together once (``PAIRING_DEAL``), and the seed
deals the order of the units, so that every eight consecutive arrivals span
the prompts. What an arrival costs the next one is the part of its prefill
that is still to do when the next comes, which its prompt and the gap after
it decide: with the three dealt apart, each seed drew its own collisions
(the 20 ms gap behind the 3,000-token prompt, or behind the 64-token one),
and the mean wait for a first token followed the draw and not the program
(PERF.md section 6, PR 51).

With ``standing`` in the parameters the window opens on the steady state.
The same arrival process is run backwards from the opening for as long as
the longest request lives; a request that arrived ``a`` seconds before is
caught part-way, as it would stand after ``a`` seconds of service at the
stated pace (``prefill_tokens_per_s``, then a token every ``token_s``):
what it would have generated is already in its prompt, the rest is what it
asks for, and one that would have finished is left out. That population is
the same for every seed (its sizes are dealt once, each on its own, by
``STANDING_DEAL``; the seed gives its token ids): dealt by the seed, how
much context the window opened on was the seed's doing, and the waits of
the window followed it."""

from __future__ import annotations

from perfbench import lengths
from perfbench.traffic_kinds import Planned


STANDING_DEAL = 0      # the one deal of every seed's standing population
PAIRING_DEAL = 0       # the one pairing of a prompt, its answer and the gap
                       # after it, for every seed's window


class Plan:
    def __init__(self, params, *, seed, seconds, vocab, max_total, n_slots):
        self.rate = float(params["rate_rps"])
        self._params = params
        self._vocab, self._max_total = vocab, max_total
        self._ids = lengths.rng_for(seed, 4)
        self.requests = [
            Planned(t, lengths.token_ids(self._ids, p, vocab), o)
            for t, p, o in self._arrivals(seconds, seed, stream=0,
                                          pairing=PAIRING_DEAL)]
        self._next = 0

    def _arrivals(self, span_s, seed, stream, pairing=None):
        """(time, prompt, output) of ``rate x span_s`` arrivals inside
        (0, span_s), in order of time. With a ``pairing`` the sorted
        prompts, the answers and the gaps AFTER them are made units by that
        deal and ``seed`` deals the order of the units; the unit with the
        longest gap comes last, and half of that gap lies before the first
        arrival (no shorter gap is lost at the window's end, and with it
        what its prompt costs the next arrival). Without one ``seed`` deals
        the three apart."""
        params = self._params
        n = max(1, round(self.rate * span_s))

        def deal(values, by, k):
            return lengths.dealt(values, lengths.rng_for(by,
                                                         10 * stream + k))

        prompts = lengths.lognormal_quantiles(n, **params["prompt"])
        outputs = lengths.lognormal_quantiles(n, **params["output"])
        gaps = lengths.exponential_quantiles(n, 1.0 / self.rate)
        if pairing is None:
            units = list(zip(deal(prompts, seed, 1), deal(outputs, seed, 2)))
            before = deal(gaps, seed, 3)
        else:
            units = deal(list(zip(sorted(prompts), deal(outputs, pairing, 2),
                                  deal(gaps, pairing, 3))), seed, 1)
            units.sort(key=lambda u: u[2] == max(gaps))     # stable
            before = [max(gaps) / 2] + [u[2] for u in units[:-1]]
        scale = span_s * n / (n + 0.5) / sum(gaps)
        out, t = [], 0.0
        for (p, o, *_), g in zip(units, before):
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
