"""Operator registry — binds each tunable op to its search space, cost
oracles, and build recipes.

This is the single point where a new workload plugs into the tuner
stack.  An :class:`OpSpec` names:

* ``make_space``       — dims/depths -> :class:`~repro.core.space.SearchSpace`
* ``analytical_cost``  — the op's deterministic roofline oracle
* ``timed_operands`` / ``timed_fn`` — how :class:`XLATimedCost` realizes
  a schedule as a *timed XLA:CPU program* (operands + traceable fn)
* ``pallas_run``       — how :class:`PallasInterpretCost` executes the
  op's real Pallas kernel under a schedule (interpret mode on CPU)
* ``default_blocks``   — the kernel's heuristic blocks for a workload,
  where a measured search starts (:func:`heuristic_state`)

Everything downstream (tuners, the measurement engine, journals,
``TuningSession``, the tune CLI) resolves ops through :func:`get_op` and
never mentions GEMM concretely.  Registering here also registers the
op's state type (via the space modules), so persisted records/journal
rows deserialize for any bundled op.

Built-in ops:

  ``gemm``  — the paper's tiled matrix multiply (canonical instance)
  ``flash`` — blocked flash attention over ``(seq_q, seq_kv, head_dim)``
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence

import numpy as np

from .config_space import GemmConfigSpace, TilingState
from .flash_space import FlashAttnConfigSpace, FlashScheduleState
from .space import SearchSpace

__all__ = ["OpSpec", "OPS", "register_op", "get_op", "op_names", "heuristic_state"]


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Everything the tuner stack needs to know about one operator."""

    name: str
    state_type: type
    default_depths: tuple[int, ...]
    #: (dims, depths, **spec_kwargs) -> SearchSpace
    make_space: Callable[..., SearchSpace]
    #: (space, **kwargs) -> CostBackend (the op's analytical oracle)
    analytical_cost: Callable[..., object]
    #: (space, dtype, seed) -> operand arrays for the timed XLA program
    timed_operands: Callable[..., tuple]
    #: (space, state, dtype) -> traceable fn(*operands) realizing the schedule
    timed_fn: Callable[..., Callable]
    #: (space, state, operands, interpret) -> output array via the real
    #: Pallas kernel, or None when the op has no kernel binding
    pallas_run: Optional[Callable] = None
    #: dims -> the kernel's heuristic block size per factored dim (where
    #: dispatch runs without a record), or None
    default_blocks: Optional[Callable[[tuple], tuple[int, ...]]] = None


OPS: dict[str, OpSpec] = {}


def register_op(spec: OpSpec) -> None:
    OPS[spec.name] = spec


def get_op(name: str) -> OpSpec:
    try:
        return OPS[name]
    except KeyError:
        raise KeyError(
            f"unknown op {name!r}; registered ops: {sorted(OPS)}"
        ) from None


def op_names() -> list[str]:
    return sorted(OPS)


def heuristic_state(space: SearchSpace):
    """The schedule the op's kernel runs without a tuning record, as a
    state of ``space``; None when the op names no heuristic."""
    spec = get_op(space.op)
    if spec.default_blocks is None:
        return None
    return space.blocked_state(spec.default_blocks(space.dims))


# ---------------------------------------------------------------------------
# gemm — the paper's tiled matmul
# ---------------------------------------------------------------------------


def _gemm_space(dims: Sequence[int], depths: Sequence[int] = (), **kw) -> GemmConfigSpace:
    m, k, n = dims
    d_m, d_k, d_n = depths or (4, 2, 4)
    return GemmConfigSpace(m, k, n, d_m, d_k, d_n, **kw)


def _gemm_analytical(space, **kw):
    from .cost.analytical import AnalyticalTPUCost

    return AnalyticalTPUCost(space, **kw)


def _gemm_timed_operands(space: GemmConfigSpace, dtype: str, seed: int) -> tuple:
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((space.m, space.k)), dtype=dtype)
    B = jnp.asarray(rng.standard_normal((space.k, space.n)), dtype=dtype)
    return (A, B)


def _gemm_timed_fn(space: GemmConfigSpace, s: TilingState, dtype: str) -> Callable:
    """The tiled loop structure of ``s`` as an XLA program: fori_loop
    over the macro-grid with dynamic-sliced blocks, k innermost with
    VMEM-style accumulation."""
    import jax
    import jax.numpy as jnp

    lax = jax.lax
    gm, gk, gn = s.grid
    bm, bk, bn = s.block_m, s.block_k, s.block_n
    M, N = space.m, space.n

    def fn(A, B):
        C = jnp.zeros((M, N), dtype=dtype)

        def body(idx, C):
            ik = idx % gk
            rest = idx // gk
            i_n = rest % gn
            i_m = rest // gn
            a = lax.dynamic_slice(A, (i_m * bm, ik * bk), (bm, bk))
            b = lax.dynamic_slice(B, (ik * bk, i_n * bn), (bk, bn))
            c = jnp.dot(a, b)
            old = lax.dynamic_slice(C, (i_m * bm, i_n * bn), (bm, bn))
            return lax.dynamic_update_slice(C, old + c, (i_m * bm, i_n * bn))

        return lax.fori_loop(0, gm * gk * gn, body, C)

    return fn


def _gemm_pallas_run(space: GemmConfigSpace, s: TilingState, operands, interpret: bool):
    from repro.kernels.gemm import gemm_pallas, kernel_config_from_state

    cfg = kernel_config_from_state(s)  # ValueError -> inf at the caller
    A, B = operands
    return gemm_pallas(A, B, cfg, interpret=interpret)


def _gemm_default_blocks(dims) -> tuple[int, int, int]:
    from repro.kernels.gemm import default_config

    cfg = default_config(*dims)
    return cfg.block_m, cfg.block_k, cfg.block_n


# ---------------------------------------------------------------------------
# flash — blocked flash attention
# ---------------------------------------------------------------------------


def _flash_space(
    dims: Sequence[int], depths: Sequence[int] = (), **kw
) -> FlashAttnConfigSpace:
    seq_q, seq_kv, head_dim = dims
    d_q, d_kv = depths or (2, 2)
    return FlashAttnConfigSpace(seq_q, seq_kv, head_dim, d_q, d_kv, **kw)


def _flash_analytical(space, **kw):
    from .cost.flash_analytical import FlashAnalyticalCost

    return FlashAnalyticalCost(space, **kw)


def _flash_timed_operands(space: FlashAttnConfigSpace, dtype: str, seed: int) -> tuple:
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((space.seq_q, space.head_dim)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((space.seq_kv, space.head_dim)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((space.seq_kv, space.head_dim)), dtype=dtype)
    return (q, k, v)


def _flash_timed_fn(
    space: FlashAttnConfigSpace, s: FlashScheduleState, dtype: str
) -> Callable:
    """The blocked online-softmax loop of ``s`` as an XLA program —
    the CPU-timeable realization of the Pallas kernel's schedule
    (fori_loop over q grid cells, inner fori over kv blocks with the
    causal early exit)."""
    import jax
    import jax.numpy as jnp

    lax = jax.lax
    bq, bkv = s.block_q, s.block_kv
    n_q, n_kv = s.n_q_blocks, s.n_kv_blocks
    sq, hd = space.seq_q, space.head_dim
    causal = space.causal
    scale = 1.0 / math.sqrt(hd)

    def fn(q, k, v):
        out = jnp.zeros((sq, hd), dtype=dtype)

        def q_body(iq, out):
            qb = lax.dynamic_slice(q, (iq * bq, 0), (bq, hd)).astype(jnp.float32)
            qb = qb * scale

            def kv_body(ik, carry):
                acc, m_run, l_run = carry
                kb = lax.dynamic_slice(k, (ik * bkv, 0), (bkv, hd)).astype(jnp.float32)
                vb = lax.dynamic_slice(v, (ik * bkv, 0), (bkv, hd)).astype(jnp.float32)
                logits = qb @ kb.T  # (bq, bkv)
                if causal:
                    q_pos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
                    k_pos = ik * bkv + lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
                    logits = jnp.where(q_pos >= k_pos, logits, -1e30)
                m_new = jnp.maximum(m_run, logits.max(axis=-1))
                p = jnp.exp(logits - m_new[:, None])
                corr = jnp.exp(m_run - m_new)
                l_new = l_run * corr + p.sum(axis=-1)
                acc = acc * corr[:, None] + p @ vb
                return (acc, m_new, l_new)

            carry0 = (
                jnp.zeros((bq, hd), jnp.float32),
                jnp.full((bq,), -1e30, jnp.float32),
                jnp.zeros((bq,), jnp.float32),
            )
            # causal: skip kv blocks entirely above the diagonal
            last = n_kv
            if causal:
                last = jnp.minimum(n_kv, ((iq + 1) * bq + bkv - 1) // bkv)
            acc, _, l_run = lax.fori_loop(0, last, kv_body, carry0)
            ob = (acc / jnp.maximum(l_run, 1e-30)[:, None]).astype(dtype)
            return lax.dynamic_update_slice(out, ob, (iq * bq, 0))

        return lax.fori_loop(0, n_q, q_body, out)

    return fn


def _flash_pallas_run(
    space: FlashAttnConfigSpace, s: FlashScheduleState, operands, interpret: bool
):
    from repro.kernels.flash_attention import flash_attention

    q, k, v = operands
    q4 = q.reshape(1, space.seq_q, 1, space.head_dim)
    k4 = k.reshape(1, space.seq_kv, 1, space.head_dim)
    v4 = v.reshape(1, space.seq_kv, 1, space.head_dim)
    return flash_attention(
        q4, k4, v4,
        block_q=s.block_q,
        block_k=s.block_kv,
        causal=space.causal,
        interpret=interpret,
    )


def _flash_default_blocks(dims) -> tuple[int, int]:
    from repro.kernels.flash_attention import HEURISTIC_BLOCKS

    seq_q, seq_kv, _ = dims
    return min(HEURISTIC_BLOCKS[0], seq_q), min(HEURISTIC_BLOCKS[1], seq_kv)


register_op(
    OpSpec(
        name="gemm",
        state_type=TilingState,
        default_depths=(4, 2, 4),
        make_space=_gemm_space,
        analytical_cost=_gemm_analytical,
        timed_operands=_gemm_timed_operands,
        timed_fn=_gemm_timed_fn,
        pallas_run=_gemm_pallas_run,
        default_blocks=_gemm_default_blocks,
    )
)

register_op(
    OpSpec(
        name="flash",
        state_type=FlashScheduleState,
        default_depths=(2, 2),
        make_space=_flash_space,
        analytical_cost=_flash_analytical,
        timed_operands=_flash_timed_operands,
        timed_fn=_flash_timed_fn,
        pallas_run=_flash_pallas_run,
        default_blocks=_flash_default_blocks,
    )
)
