"""The decode program's gemm launches, the layer-indexed ones that read
each layer's weights in place and the head: their roofline time (per
launch the larger of FLOPs over the bf16 peak and least bytes over the
memory bandwidth, from the census) over their device time in the trace,
in percent."""

from benchmarks.chip.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "gemm", "decode")
