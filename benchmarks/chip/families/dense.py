"""The dense decoders (yi-6b, nemotron-4-15b): the float32 reference and
its float8 control (``reference.py``), and the model FLOPs of a prefill
call and of a decode loop (``counts.py``).  Dense states no keys beyond
the shared ones (``system._FIELDS``)."""

from benchmarks.chip.counts import decode_flops, prefill_flops  # noqa: F401
from benchmarks.chip.reference import control_gaps, served_gaps  # noqa: F401

FIELDS: dict = {}
