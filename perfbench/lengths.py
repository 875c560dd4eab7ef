"""Seeded draws shared by the traffic kinds.

Sizes are evenly spaced quantiles of the stated distribution, not samples,
so every seed runs the same set of sizes and gaps; the seed deals them out
in another order (``dealt``) and gives the token ids.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(n: int, *, median: float, sigma: float, lo: int,
                        hi: int) -> list[int]:
    """``n`` evenly spaced quantiles of a log-normal, clipped to [lo, hi]."""
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def exponential_quantiles(n: int, mean: float) -> list[float]:
    return [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose; any whole number is a seed."""
    return np.random.default_rng([int(seed) % (2 ** 63), stream])


STRATUM = 8


def dealt(values, rng) -> list:
    """The same values in an order the generator draws, so that every
    ``STRATUM`` consecutive ones span the whole range: the sorted values are
    split into interleaved groups of about that size (each holds every
    ``n_groups``-th value), each group is shuffled, and so is the order of
    the groups."""
    values = sorted(values)
    n_groups = max(1, round(len(values) / STRATUM))
    groups = [values[g::n_groups] for g in range(n_groups)]
    for g in groups:
        rng.shuffle(g)
    rng.shuffle(groups)
    return [v for g in groups for v in g]


def fit_lengths(prompt: int, output: int, max_total: int) -> tuple[int, int]:
    """Every request fits ``prompt + output <= max_total``: the prompt gives
    way first, down to one token."""
    if prompt + output > max_total:
        prompt = max(1, max_total - output)
    if prompt + output > max_total:
        output = max_total - prompt
    return prompt, output


def token_ids(rng, n: int, vocab: int) -> list[int]:
    return rng.integers(0, vocab, n).tolist()
