"""Trials the tuner measured in the window (``MeasureStats`` through the
session's report)."""


def read(run):
    n = run.get("trials")
    return None if n is None else float(n)
