from perfbench import readers


def read(rec):
    return readers.percentile_or_none(readers.gaps_ms(rec), 95)
