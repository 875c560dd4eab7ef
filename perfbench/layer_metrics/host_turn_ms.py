"""The host's turn of a step, from inside the program: the median, over the
``fleet.step`` spans of the traced span that dispatched or read a step, of
the span's length less the waits for tokens inside it (``decode_step`` /
``mixed_step``). While a step is longer than the turn, the turn is hidden
behind the device and moves nothing end to end."""

from perfbench import program_spans


def read(rec):
    return program_spans.median_or_none(program_spans.turns_ms(rec))
