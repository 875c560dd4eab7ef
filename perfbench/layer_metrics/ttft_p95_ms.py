from perfbench import readers


def read(rec):
    return readers.percentile_or_none(readers.ttft_ms(rec), 95)
