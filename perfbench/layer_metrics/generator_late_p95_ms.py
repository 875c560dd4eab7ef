"""How late the load generator submitted: submit time minus due time."""

from perfbench import readers


def read(rec):
    return readers.percentile_or_none(
        [(t.submit_t - t.due_t) * 1e3 for t in rec.tracked], 95)
