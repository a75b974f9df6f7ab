"""The tiled GEMM kernel (``kernels/gemm.py``): C = A @ B, B either a
2-D weight or one layer of a stacked (L, K, N) weight that the kernel
reads in place, with the layer index scalar-prefetched ahead of A.

Its operands, in the jaxpr and in the HLO custom-call alike, are (A, B)
or (index, A, stack); a launch's dims are ``(m, k, n)`` either way, and
its least traffic counts one ``K x N`` slab of the stack."""

from benchmarks.chip.counts import gemm_bytes, gemm_flops

NAME = "gemm_pallas"


def dims(shapes):
    """``(m, k, n)``, or None for operands of another form."""
    ranks = tuple(len(s) for s in shapes)
    if ranks == (2, 2):
        (m, k), (_, n) = shapes
    elif ranks == (1, 2, 3):
        (m, k), (_, _, n) = shapes[1:]
    else:
        return None
    return (m, k, n)


def flops(dims):
    return gemm_flops(*dims)


def bytes(dims, dtype):
    return gemm_bytes(*dims, dtype=dtype)
