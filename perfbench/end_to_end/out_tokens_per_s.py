"""Output tokens stamped inside the window over the length of the window."""

from perfbench import stats


def read(rec):
    n = sum(stats.tokens_in(t.stamps, rec.t_open, rec.t_close)
            for t in rec.tracked)
    return n / (rec.t_close - rec.t_open)
