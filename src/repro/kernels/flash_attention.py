"""Pallas TPU flash-attention kernel (causal, GQA-aware).

The second compute hot spot after GEMM: the same online-softmax
algorithm the model stack uses in pure JAX
(models/common.chunked_causal_attention — which doubles as this kernel's
oracle), expressed as a pl.pallas_call with explicit VMEM tiling:

  layout:    Q (B, KV, G, S, hd) and K/V (B, KV, S, hd), head-major so
             every block's last two dims are (rows, hd) — the TPU tiling
             cannot take a single head out of a (heads, hd) tile
  grid:      (batch, kv_head, q_block)   — q blocks are parallel
  BlockSpec: Q (G, block_q, hd) · K/V (S, hd) streamed through an inner
             fori_loop over kv blocks
  scratch:   f32 accumulator (G·block_q, hd) + running max/sum
             (G·block_q, 1)

The G query heads of one KV head are folded into the rows of one 2-D
matmul against each K/V block, so every contraction is a plain MXU
matmul.  Like the tiled GEMM, (block_q, block_k) are tunable — the same
GemmConfigSpace machinery applies (2-factor compositions); see
tests/test_flash_kernel.py for the sweep.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import check_interpret

__all__ = ["flash_attention", "HEURISTIC_BLOCKS"]

#: (block_q, block_k) that dispatch runs without a tuning record
HEURISTIC_BLOCKS = (256, 512)

_NT = (((1,), (1,)), ((), ()))  # contract the last dims: a @ b.T


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, seq_k: int, causal: bool,
                  scale: float, out_dtype):
    """One (batch, kv_head, q_block) cell: stream kv blocks, online
    softmax into the VMEM accumulator."""
    iq = pl.program_id(2)
    g, _, hd = q_ref.shape
    rows = g * block_q

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32).reshape(rows, hd) * scale
    n_k = seq_k // block_k

    def body(ik, _):
        sl = pl.dslice(ik * block_k, block_k)
        kb = k_ref[sl, :].astype(jnp.float32)  # (block_k, hd)
        vb = v_ref[sl, :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, kb, _NT, preferred_element_type=jnp.float32
        )  # (g * block_q, block_k); row r is query iq * block_q + r % block_q
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
            q_pos = iq * block_q + row % block_q
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 1
            )
            logits = jnp.where(q_pos >= k_pos, logits, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, vb, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        return ()

    # causal: skip kv blocks entirely above the diagonal
    last = n_k if not causal else jnp.minimum(
        n_k, ((iq + 1) * block_q + block_k - 1) // block_k
    )
    jax.lax.fori_loop(0, last, body, ())
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = out.reshape(g, block_q, hd).astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "causal", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = HEURISTIC_BLOCKS[0],
    block_k: int = HEURISTIC_BLOCKS[1],
    causal: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, S, H, hd); k/v: (B, S, KV, hd); returns (B, S, H, hd).

    GQA folds the H = KV x G query heads so each grid cell attends one
    KV head; K/V stream once per (batch, kv_head)."""
    check_interpret(interpret)
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"blocks ({block_q},{block_k}) must divide ({sq},{sk})")
    qh = q.reshape(b, sq, kv, g, hd).transpose(0, 2, 3, 1, 4)  # (b, kv, g, sq, hd)
    kh = k.transpose(0, 2, 1, 3)  # (b, kv, sk, hd)
    vh = v.transpose(0, 2, 1, 3)
    grid = (b, kv, sq // block_q)

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        seq_k=sk,
        causal=causal,
        scale=1.0 / math.sqrt(hd),
        out_dtype=q.dtype,
    )
    q_spec = pl.BlockSpec(
        (None, None, g, block_q, hd), lambda ib, ih, iq: (ib, ih, 0, iq, 0)
    )
    kv_spec = pl.BlockSpec((None, None, sk, hd), lambda ib, ih, iq: (ib, ih, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * block_q, hd), jnp.float32),
            pltpu.VMEM((g * block_q, 1), jnp.float32),
            pltpu.VMEM((g * block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
