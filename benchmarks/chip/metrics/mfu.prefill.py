"""Prefill's share of the bf16 peak: model FLOPs (matmuls, causal
attention, the head at the last position) over the ``prefill_s`` spans."""

from benchmarks.chip.readers import mfu_prefill as read  # noqa: F401
