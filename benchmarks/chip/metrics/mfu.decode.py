"""Decode's share of the bf16 peak: model FLOPs of each step (2 x matmul
weights x batch, plus attention over the valid cache) over the
``decode_s`` spans."""

from benchmarks.chip.readers import mfu_decode as read  # noqa: F401
