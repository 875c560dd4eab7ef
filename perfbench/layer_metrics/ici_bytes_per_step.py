"""Megabytes a chip sends over the mesh in one compiled step, as the
program counts them when it traces the step (``perf_model``'s wire bytes of
every collective call an execution makes): the ``ici_bytes`` attribute of
the program's ``engine.dispatch`` spans, mean over the steps dispatched in
the traced span. A count, not a time: it moves when a step's collectives or
their shapes change. A program whose spans lack the attribute (an older
commit) gives nothing to read."""

from perfbench import program_spans


def read(rec):
    records = program_spans.spans(rec)
    sent = [r.attrs["ici_bytes"] for r in records or ()
            if r.name == program_spans.DISPATCH and r.attrs
            and "ici_bytes" in r.attrs]
    return sum(sent) / len(sent) / 1e6 if sent else None
