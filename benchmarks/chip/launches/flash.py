"""The flash attention kernel (``kernels/flash_attention.py``): causal
GQA over the head-major Q (B, KV, G, S, hd) and K, V (B, KV, S, hd) that
it hands its ``pallas_call``.  A launch's dims are ``(b, heads,
kv_heads, s, hd)``."""

from benchmarks.chip.counts import flash_bytes, flash_flops

NAME = "flash_attention"


def dims(shapes):
    """``(b, heads, kv_heads, s, hd)``, or None for operands of another form."""
    if tuple(len(s) for s in shapes) != (5, 4, 4):
        return None
    b, kv, g, s, hd = shapes[0]
    return (b, kv * g, kv, s, hd)


def flops(dims):
    b, h, _, s, hd = dims
    return flash_flops(b, h, s, hd)


def bytes(dims, dtype):
    return flash_bytes(*dims, dtype=dtype)
