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
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config_space import TilingState

from . import check_interpret

__all__ = ["KernelConfig", "kernel_config_from_state", "gemm_pallas", "default_config"]

#: TPU tiling: the last two dims of every block are multiples of
#: (sublanes, lanes), or the whole array dim (the v5e compiler takes
#: 8-row blocks for bf16 as well as f32)
_SUBLANES, _LANES = 8, 128


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


def default_config(m: int, k: int, n: int) -> KernelConfig:
    """Heuristic fallback when no tuning record exists: per dim, the
    whole dim when it is within the target, else the largest divisor
    within the target that meets the TPU tiling (``tpu_aligned``).  A dim
    with no such divisor keeps its largest plain divisor; dispatch then
    sends the shape to XLA."""

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


@functools.partial(
    jax.jit, static_argnames=("config", "interpret", "out_dtype")
)
def gemm_pallas(
    a: jax.Array,
    b: jax.Array,
    config: KernelConfig,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """C = A @ B via the tiled Pallas kernel.  A: (M, K), B: (K, N)."""
    check_interpret(interpret)
    (m, k), (k2, n) = a.shape, b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    cfg = config.resolved()
    cfg.validate(m, k, n)
    out_dtype = out_dtype or a.dtype
    n_k = k // cfg.block_k
    grid = (m // cfg.block_m, n // cfg.block_n, n_k)

    kernel = functools.partial(
        _gemm_kernel,
        n_k=n_k,
        sub_m=cfg.sub_m,
        sub_n=cfg.sub_n,
        out_dtype=out_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((cfg.block_m, cfg.block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((cfg.block_k, cfg.block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((cfg.block_m, cfg.block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((cfg.block_m, cfg.block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the HLO instruction's name, which the benchmark's trace reduction
        # finds the kernel by (benchmarks/chip/trace.py)
        name="gemm_pallas",
    )(a, b)
