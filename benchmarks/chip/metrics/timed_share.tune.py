"""Percent of the search window in which the cost backend ran a
candidate's timed repeats (the ``measure.timed`` spans)."""

from benchmarks.chip.spans import span_share


def read(run):
    return span_share(run, {"measure.timed"})
