"""Percent of the search window spent setting up each workload's search:
the space, the cost backend with its operands, the engine and the tuner
(the ``tune.setup`` spans)."""

from benchmarks.chip.spans import span_share


def read(run):
    return span_share(run, {"tune.setup"})
