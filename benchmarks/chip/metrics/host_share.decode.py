"""Percent of the serving window in which the engine's host work held a
call: staging, launches and the token fetch (``serve.stage``,
``serve.launch``, ``serve.fetch``), not its waits on the device."""

from benchmarks.chip.spans import serve_host_share as read  # noqa: F401
