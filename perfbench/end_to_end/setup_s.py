def read(rec):
    return rec.setup_s
