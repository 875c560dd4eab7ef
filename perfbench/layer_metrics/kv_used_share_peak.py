"""Peak share of the pool's blocks held by running sequences (sampled
every few steps from the pool's own accounting)."""


def read(rec):
    return 100.0 * max(rec.kv_live) if rec.kv_live else None
