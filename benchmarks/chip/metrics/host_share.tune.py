"""Percent of the search window spent on the host around the
measurements: the engine's funnel (journal cache, static, learned and
shard stages) and its journal bookkeeping (``measure.funnel``,
``measure.journal``), and the tuner's own proposals, which are the self
time of ``tune.workload``."""

from benchmarks.chip.spans import span_share


def read(run):
    return span_share(run, {"measure.funnel", "measure.journal"}, self_of="tune.workload")
