"""The prefill program's flash launches: their roofline time (per launch the
larger of FLOPs over the bf16 peak and least bytes over the memory
bandwidth, from the census) over their device time in the trace, in
percent."""

from benchmarks.chip.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "flash", "prefill")
