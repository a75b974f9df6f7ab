"""Pallas TPU GEMM kernel with tuner-selected multi-level tiling.

This is the compute hot-spot the paper optimizes, adapted to the TPU
memory hierarchy:

  level 0 (grid):      (M/bm, N/bn, K/bk) macro-steps; k is the innermost
                       grid dimension so the f32 accumulator lives in
                       VMEM across the contraction ("arbitrary" semantics)
  level 1 (BlockSpec): A (bm, bk), B (bk, bn) VMEM blocks, double-buffered
                       by the Pallas pipeline
  level 2 (sub-tile):  an in-kernel loop over (sub_m, sub_n) tiles feeding
                       the MXU — the paper's inner nesting levels
  level 3 (register):  reg_m/reg_n granularity is folded into sub-tile
                       alignment (the MXU/VREG packing on TPU is not
                       software-addressable the way CUDA registers are)

A :class:`TilingState` from the tuner maps onto (bm, bk, bn, sub_m,
sub_n) via :func:`kernel_config_from_state`.  The kernel is validated
against ``ref.py`` in interpret mode on CPU (tests sweep shapes/dtypes);
on a real TPU the same code JITs natively.

B may also be a stack of layers, ``(L, K, N)``, with the layer's index as
an operand: the index is scalar-prefetched and the B index map reads
that layer's blocks straight from the stack, so a decode step's GEMM
streams its weights from HBM once instead of after a copy of the layer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.analysis import VMEM_BUDGET_BYTES, gemm_working_set_bytes
from repro.core.config_space import TilingState

from . import check_interpret

__all__ = [
    "KernelConfig", "kernel_config_from_state", "gemm_pallas", "default_config",
    "STREAM_M", "STREAM_TILE_BYTES",
]

#: TPU tiling: the last two dims of every block are multiples of
#: (sublanes, lanes), or the whole array dim (the v5e compiler takes
#: 8-row blocks for bf16 as well as f32)
_SUBLANES, _LANES = 8, 128


#: GEMMs of at most this many rows stream B: a bf16 GEMM of M rows does
#: about M FLOPs per byte of B, and the v5e ridge is 197 TFLOP/s over
#: 819 GB/s, about 240 FLOPs per byte
STREAM_M = 128
#: least bytes of B a streaming grid step moves, so that the fixed cost
#: of a step is small against its copy
STREAM_TILE_BYTES = 2 << 20


def _tiled(block: int, dim: int, align: int) -> bool:
    return block == dim or block % align == 0


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    block_m: int
    block_k: int
    block_n: int
    sub_m: int = 0  # 0 = whole block (no inner split)
    sub_n: int = 0

    def resolved(self) -> "KernelConfig":
        sm = self.sub_m or self.block_m
        sn = self.sub_n or self.block_n
        return dataclasses.replace(self, sub_m=sm, sub_n=sn)

    def validate(self, m: int, k: int, n: int) -> None:
        c = self.resolved()
        if m % c.block_m or k % c.block_k or n % c.block_n:
            raise ValueError(
                f"blocks {(c.block_m, c.block_k, c.block_n)} do not divide "
                f"dims {(m, k, n)}"
            )
        if c.block_m % c.sub_m or c.block_n % c.sub_n:
            raise ValueError("sub-tiles must divide blocks")

    def tpu_aligned(self, m: int, k: int, n: int) -> bool:
        """Whether every block and sub-tile meets the TPU tiling: the
        compiler refuses anything else."""
        c = self.resolved()
        return (
            _tiled(c.block_m, m, _SUBLANES)
            and _tiled(c.block_k, k, _LANES)
            and _tiled(c.block_n, n, _LANES)
            and _tiled(c.sub_m, c.block_m, _SUBLANES)
            and _tiled(c.sub_n, c.block_n, _LANES)
        )


def kernel_config_from_state(s: TilingState) -> KernelConfig:
    """Interpret a tuner state as a kernel config."""
    cfg = KernelConfig(
        block_m=s.block_m,
        block_k=s.block_k,
        block_n=s.block_n,
        sub_m=s.sub_m,
        sub_n=s.sub_n,
    )
    m, k, n = s.dims()
    cfg.validate(m, k, n)
    return cfg


def _aligned_divisors(dim: int, align: int) -> list[int]:
    """Ascending divisors of ``dim`` that meet the TPU tiling: the
    multiples of ``align``, and the whole dim."""
    out = [d for d in range(align, dim, align) if dim % d == 0]
    return out + [dim]


def _stream_config(m: int, k: int, n: int, itemsize: int) -> Optional[KernelConfig]:
    """Blocks for a GEMM whose time is reading B (M <= ``STREAM_M``): all
    of M; the whole of K, else its largest aligned divisor that fits, so
    that A is read once; the narrowest aligned N block that moves
    ``STREAM_TILE_BYTES`` of B a step (else the widest that fits), each
    within ``VMEM_BUDGET_BYTES`` double-buffered.  None when nothing fits."""
    ns = _aligned_divisors(n, _LANES)
    for bk in reversed(_aligned_divisors(k, _LANES)):
        fit = [bn for bn in ns
               if gemm_working_set_bytes(m, bk, bn, itemsize) <= VMEM_BUDGET_BYTES]
        if fit:
            bn = next((bn for bn in fit if bk * bn * itemsize >= STREAM_TILE_BYTES),
                      fit[-1])
            return KernelConfig(block_m=m, block_k=bk, block_n=bn)
    return None


def default_config(m: int, k: int, n: int, itemsize: int = 2) -> KernelConfig:
    """Heuristic fallback when no tuning record exists.  At most
    ``STREAM_M`` rows, the streaming blocks of :func:`_stream_config`.
    Otherwise, per dim, the whole dim when it is within the target, else
    the largest divisor within the target that meets the TPU tiling
    (``tpu_aligned``).  A dim with no such divisor keeps its largest
    plain divisor; dispatch then sends the shape to XLA."""
    if m <= STREAM_M:
        cfg = _stream_config(m, k, n, itemsize)
        if cfg is not None:
            return cfg

    def block(dim: int, target: int, align: int) -> int:
        if dim <= target:
            return dim
        for d in range(target - target % align, 0, -align):
            if dim % d == 0:
                return d
        d = target
        while dim % d:
            d -= 1
        return d

    return KernelConfig(
        block_m=block(m, 256, _SUBLANES),
        block_k=block(k, 512, _LANES),
        block_n=block(n, 256, _LANES),
    )


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, sub_m: int,
                 sub_n: int, out_dtype):
    """Kernel body: accumulate A-block @ B-block into the VMEM scratch
    accumulator; flush to the output block on the last k step."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm, bk = a_ref.shape
    bn = b_ref.shape[1]
    n_sub_m = bm // sub_m
    n_sub_n = bn // sub_n
    if n_sub_m == 1 and n_sub_n == 1:
        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )
    else:
        # level-2 tiling: explicit MXU-facing sub-tiles (paper's inner loops)
        a = a_ref[...]
        b = b_ref[...]
        for im in range(n_sub_m):
            for jn in range(n_sub_n):
                sl_m = slice(im * sub_m, (im + 1) * sub_m)
                sl_n = slice(jn * sub_n, (jn + 1) * sub_n)
                acc_ref[sl_m, sl_n] += jnp.dot(
                    a[sl_m, :], b[:, sl_n], preferred_element_type=jnp.float32
                )

    @pl.when(k_idx == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def _vmem_limit(cfg: KernelConfig, in_bytes: int, out_bytes: int) -> Optional[int]:
    """``vmem_limit_bytes`` for a kernel whose double-buffered blocks,
    output and accumulator leave less than a quarter of the compiler's
    scoped limit spare; None keeps the compiler's own."""
    need = (gemm_working_set_bytes(cfg.block_m, cfg.block_k, cfg.block_n, in_bytes)
            + 2 * cfg.block_m * cfg.block_n * out_bytes)
    if need <= VMEM_BUDGET_BYTES * 3 // 4:
        return None
    return need + VMEM_BUDGET_BYTES // 2


@functools.partial(
    jax.jit, static_argnames=("config", "interpret", "out_dtype")
)
def gemm_pallas(
    a: jax.Array,
    b: jax.Array,
    config: KernelConfig,
    interpret: bool = False,
    out_dtype=None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """C = A @ B via the tiled Pallas kernel.  A: (M, K), B: (K, N); or B
    a stack (L, K, N) and ``layer`` the index of the B to use, which the
    kernel reads in place."""
    check_interpret(interpret)
    stacked = b.ndim == 3
    if stacked == (layer is None):
        raise ValueError(f"B {b.shape} needs a layer index exactly when it is stacked")
    (m, k), (k2, n) = a.shape, b.shape[-2:]
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    cfg = config.resolved()
    cfg.validate(m, k, n)
    out_dtype = out_dtype or a.dtype
    n_k = k // cfg.block_k
    grid = (m // cfg.block_m, n // cfg.block_n, n_k)

    body = functools.partial(
        _gemm_kernel,
        n_k=n_k,
        sub_m=cfg.sub_m,
        sub_n=cfg.sub_n,
        out_dtype=out_dtype,
    )
    if stacked:
        # the layer index is scalar-prefetched into SMEM, where the B
        # index map reads it: each block is copied from the stack itself
        prefetch = (jnp.reshape(layer, (1,)).astype(jnp.int32),)
        b_spec = pl.BlockSpec((None, cfg.block_k, cfg.block_n),
                              lambda i, j, kk, li: (li[0], kk, j))
        kernel = lambda li, *refs: body(*refs)
    else:
        prefetch = ()
        b_spec = pl.BlockSpec((cfg.block_k, cfg.block_n), lambda i, j, kk: (kk, j))
        kernel = body
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=[
            pl.BlockSpec((cfg.block_m, cfg.block_k), lambda i, j, kk, *_: (i, kk)),
            b_spec,
        ],
        out_specs=pl.BlockSpec((cfg.block_m, cfg.block_n), lambda i, j, kk, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((cfg.block_m, cfg.block_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                cfg, max(a.dtype.itemsize, b.dtype.itemsize), jnp.dtype(out_dtype).itemsize
            ),
        ),
        interpret=interpret,
        # the HLO instruction's name, which the benchmark's trace reduction
        # finds the kernel by (benchmarks/chip/trace.py)
        name="gemm_pallas",
    )(*prefetch, a, b)
