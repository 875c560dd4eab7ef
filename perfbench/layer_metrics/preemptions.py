def read(rec):
    return rec.counters.get("preemptions")
