"""Percent of the device-idle time in the traced first seconds of the
search that no program span (``serve.*``, ``tune.*``, ``measure.*``)
covers; logs the idle time by the innermost span over it."""

from benchmarks.chip.spans import idle_unattributed as read  # noqa: F401
