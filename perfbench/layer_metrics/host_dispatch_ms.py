"""Median length of the program's ``engine.dispatch`` spans in the traced
span: planning a step's rows, its operands, the call of the compiled step
and the start of the tokens' copy. The part of the host's turn that no
observer's removal can cut."""

from perfbench import program_spans


def read(rec):
    return program_spans.median_or_none(
        program_spans.lengths_ms(rec, program_spans.DISPATCH))
