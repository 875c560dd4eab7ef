"""Median, over the steps of the traced span, of the program's
``engine.observe`` span: the gauges, the SLO and stats tick, the incident
detectors and the controller. What the observers cost a step."""

from perfbench import program_spans


def read(rec):
    return program_spans.median_or_none(
        program_spans.per_step_ms(rec, program_spans.OBSERVE))
