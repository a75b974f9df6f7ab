"""Record-aware kernel dispatch — the trace-time bridge from tuning
records to the ops models actually execute.

``gemm(x, w)`` is what the model stack calls for every projection /
FFN / expert matmul; ``models/common.attention_dispatch`` routes long
self-attention through :func:`flash_schedule`.  Dispatch policy (trace
time, all static):

  1. If the process-global kernel policy disables Pallas, lower to the
     pure-XLA path — XLA picks its own tiling.  The default policy
     follows the backend: on a TPU the Pallas kernels are on and compile
     natively; elsewhere (the CPU tests) they are off, and a policy that
     turns them on runs them in the Pallas interpreter.
  2. Otherwise consult the tuned record for the op's workload key
     (``records.workload_key_for`` under the policy's cost-backend
     namespace — written by `launch/tune.py`); fall back to the op's
     heuristic default when there is no record, or to XLA when the
     blocks don't divide the shape or miss the TPU tiling.

The lookup layer is **op-generic and memoized**: any op registered in
`repro.core.ops` resolves its tuned schedule state through
:func:`lookup_tuned_state`, keyed ``(op, dims, dtype, backend)``.  The
memo would otherwise hit the records store on every trace (a single
``gemm`` trace triggers three lookups: forward + both backward shapes);
it is invalidated by :func:`set_kernel_policy` and by any records
mutation/reload (via ``records.add_change_listener``).  Per-op dispatch
counters (:func:`dispatch_stats`) record — at trace time, so once per
compiled shape — whether a tuned record, the built-in heuristic, or the
XLA fallback drove each dispatch; the serving bench surfaces them.

The GEMM op is differentiable either way: the Pallas path installs a
custom_vjp whose backward passes are themselves tiled GEMMs (dA = g Bᵀ,
dB = Aᵀ g) so tuned kernels serve training too.

A decode step hands ``gemm`` one layer of its stacked weights as a
:class:`StackedWeight`.  The Pallas path reads that layer's blocks from
the stack in place (counted as ``stream``); the XLA path slices the
layer out, which XLA fuses into its dot.  The in-place path serves
inference only: it has no backward pass.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.records import add_change_listener, global_records, workload_key_for
from . import interpret_default
from .gemm import KernelConfig, default_config, gemm_pallas, kernel_config_from_state

__all__ = [
    "gemm",
    "StackedWeight",
    "KernelPolicy",
    "set_kernel_policy",
    "kernel_policy",
    "lookup_tuned_state",
    "flash_schedule",
    "invalidate_dispatch_cache",
    "dispatch_stats",
    "reset_dispatch_stats",
    "note_dispatch",
]


@dataclasses.dataclass
class KernelPolicy:
    #: None follows the backend: on exactly when it is a TPU
    use_pallas: Optional[bool] = None
    #: None follows the backend: the interpreter exactly where there is
    #: no TPU (``True`` on a TPU backend is refused by the kernels)
    interpret: Optional[bool] = None
    cost_backend: str = "analytical_tpu_v5e"  # records namespace to consult
    #: ops that consult TuningRecords at trace time; an op not listed
    #: here always uses its heuristic default (the opt-in knob for
    #: record-aware dispatch)
    record_ops: tuple[str, ...] = ("gemm", "flash")
    #: ops that actually run their Pallas kernel when ``use_pallas`` is
    #: on — lets a deployment (or bench) enable e.g. the flash kernel
    #: without routing every projection GEMM through Pallas too
    pallas_ops: tuple[str, ...] = ("gemm", "flash")

    def resolved(self) -> "KernelPolicy":
        """This policy with the backend's rule filled in for every
        field left at None."""
        interp = interpret_default()
        return dataclasses.replace(
            self,
            use_pallas=not interp if self.use_pallas is None else self.use_pallas,
            interpret=interp if self.interpret is None else self.interpret,
        )


_POLICY = KernelPolicy()


def kernel_policy() -> KernelPolicy:
    """The process-global policy, resolved against the backend."""
    return _POLICY.resolved()


def set_kernel_policy(policy: KernelPolicy) -> None:
    global _POLICY
    _POLICY = policy
    invalidate_dispatch_cache()  # cost_backend / record_ops may differ


# -- memoized op-generic record lookup ----------------------------------------

_MISS = object()
_CACHE_LOCK = threading.Lock()
_DISPATCH_CACHE: dict[tuple, object] = {}
_DISPATCH_STATS: dict[str, dict[str, int]] = {}
_STAT_FIELDS = (
    "records", "heuristic", "xla", "memo_hits", "store_lookups",
    "static_reject", "stream",
)


def invalidate_dispatch_cache() -> None:
    """Drop every memoized record lookup (registered as a records change
    listener, also run on policy swaps)."""
    with _CACHE_LOCK:
        _DISPATCH_CACHE.clear()


add_change_listener(invalidate_dispatch_cache)


def note_dispatch(op: str, source: str) -> None:
    """Count one trace-time dispatch decision for ``op``:
    ``source`` in {"records", "heuristic", "xla"} (plus internal
    memo/store counters, and ``stream``: a GEMM that read its layer of
    stacked weights in place)."""
    with _CACHE_LOCK:
        per_op = _DISPATCH_STATS.setdefault(
            op, {f: 0 for f in _STAT_FIELDS}
        )
        per_op[source] = per_op.get(source, 0) + 1


def dispatch_stats() -> dict[str, dict[str, int]]:
    with _CACHE_LOCK:
        return {op: dict(d) for op, d in _DISPATCH_STATS.items()}


def reset_dispatch_stats() -> None:
    with _CACHE_LOCK:
        _DISPATCH_STATS.clear()


def _static_reject_record(op: str, dims: tuple, dtype: str, st) -> bool:
    """True when a tuned record is provably unusable on the current
    hardware spec: the static analyzer (see ``repro.core.analysis``)
    classifies it ILLEGAL for this op workload — a stale record for
    another shape, a corrupted state, or a schedule whose working set
    no longer fits VMEM.  Any failure to even build the space/analyzer
    also rejects: falling back to the heuristic is always safe, serving
    a broken record never is."""
    try:
        from repro.core.analysis import ScheduleAnalyzer, dtype_in_bytes
        from repro.core.ops import get_op

        depths = tuple(len(r) for r in st.as_lists())
        space = get_op(op).make_space(tuple(dims), depths)
        analyzer = ScheduleAnalyzer(space, in_bytes=dtype_in_bytes(str(dtype)))
        return analyzer.analyze(st).illegal
    except Exception:
        return True


def lookup_tuned_state(op: str, dims: tuple, dtype: str):
    """Tuned schedule :class:`~repro.core.space.State` for one op
    workload, or None.  Consults the process-global
    :class:`TuningRecords` under the policy's cost-backend namespace;
    records the static analyzer rejects as ILLEGAL on the current spec
    are refused (counted as ``static_reject`` in ``dispatch_stats``, the
    caller falls back to its heuristic).  Memoized per
    ``(op, dims, dtype, backend)`` until records change.  Ops opt in
    via ``KernelPolicy.record_ops``."""
    pol = kernel_policy()
    if op not in pol.record_ops:
        return None
    key = (op, tuple(dims), str(dtype), pol.cost_backend)
    with _CACHE_LOCK:
        hit = _DISPATCH_CACHE.get(key, _MISS)
    if hit is not _MISS:
        note_dispatch(op, "memo_hits")
        return hit
    note_dispatch(op, "store_lookups")
    st = global_records().lookup_state(
        workload_key_for(op, tuple(dims), str(dtype), pol.cost_backend)
    )
    if st is not None and _static_reject_record(op, dims, dtype, st):
        note_dispatch(op, "static_reject")
        st = None  # memoized as a miss: refuse once per (shape, records)
    with _CACHE_LOCK:
        _DISPATCH_CACHE[key] = st
    return st


def _lookup_config(m: int, k: int, n: int, dtype: str) -> Optional[KernelConfig]:
    """GEMM spelling of the generic lookup: tuned state -> KernelConfig
    (None when there is no record, the record doesn't map, or its blocks
    miss the TPU tiling — the heuristic serves then)."""
    st = lookup_tuned_state("gemm", (m, k, n), dtype)
    if st is None:
        return None
    try:
        cfg = kernel_config_from_state(st)
    except (ValueError, AttributeError):  # foreign/unmappable record
        return None
    return cfg if cfg.tpu_aligned(m, k, n) else None


def flash_schedule(
    seq_q: int, seq_kv: int, head_dim: int, dtype: str
) -> Optional[tuple[int, int]]:
    """Tuned ``(block_q, block_kv)`` for one flash-attention workload, or
    None when no record fits.  Blocks must tile the sequences exactly —
    a record tuned for a different factorization never reaches the
    kernel."""
    st = lookup_tuned_state("flash", (seq_q, seq_kv, head_dim), dtype)
    if st is None:
        return None
    try:
        bq, bkv = st.block_q, st.block_kv
    except AttributeError:  # foreign record under a flash key
        return None
    if bq < 1 or bkv < 1 or seq_q % bq or seq_kv % bkv:
        return None
    return bq, bkv


def _pallas_ok(m: int, k: int, n: int, cfg: KernelConfig) -> bool:
    try:
        cfg.validate(m, k, n)
    except ValueError:
        return False
    return cfg.tpu_aligned(m, k, n)


def _bwd(cfg, interpret, res, g):
    a, b = res
    m, k = a.shape
    n = b.shape[1]
    # backward GEMMs get their own tuned configs (shapes differ)
    isz = g.dtype.itemsize
    cfg_da = _lookup_config(m, n, k, str(g.dtype)) or default_config(m, n, k, isz)
    cfg_db = _lookup_config(k, m, n, str(g.dtype)) or default_config(k, m, n, isz)
    da = (
        gemm_pallas(g, b.T, cfg_da, interpret=interpret)
        if _pallas_ok(m, n, k, cfg_da)
        else jnp.dot(g, b.T)
    ).astype(a.dtype)
    db = (
        gemm_pallas(a.T, g, cfg_db, interpret=interpret)
        if _pallas_ok(k, m, n, cfg_db)
        else jnp.dot(a.T, g)
    ).astype(b.dtype)
    return da, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gemm_pallas_diff(cfg: KernelConfig, interpret: bool, a, b):
    return gemm_pallas(a, b, cfg, interpret=interpret)


def _gemm_fwd(cfg, interpret, a, b):
    return gemm_pallas(a, b, cfg, interpret=interpret), (a, b)


_gemm_pallas_diff.defvjp(_gemm_fwd, _bwd)


class StackedWeight(NamedTuple):
    """Layer ``index`` of the stacked weights ``w`` (L, K, N), as a
    ``gemm`` operand that is read in place where the kernel can."""

    w: jax.Array
    index: jax.Array


def gemm(
    a: jax.Array,
    b: jax.Array | StackedWeight,
    config: Optional[KernelConfig] = None,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """2-D matmul through the kernel policy (see module docstring).

    Higher-rank LHS is flattened to 2-D and restored — every dense layer
    in `repro.models` funnels through here."""
    stacked = isinstance(b, StackedWeight)
    w = b.w if stacked else b
    if a.ndim < 2 or w.ndim != 2 + stacked:
        raise ValueError(f"gemm expects (.., K) @ (K, N), got {a.shape} @ {w.shape}")
    lead = a.shape[:-1]
    k = a.shape[-1]
    n = w.shape[-1]
    a2 = a.reshape((-1, k))
    m = a2.shape[0]

    pol = kernel_policy()
    enabled = (
        (pol.use_pallas and "gemm" in pol.pallas_ops)
        if use_pallas is None
        else use_pallas
    )
    if enabled:
        tuned = None if config is not None else _lookup_config(m, k, n, str(a.dtype))
        cfg = config or tuned or default_config(m, k, n, w.dtype.itemsize)
        if _pallas_ok(m, k, n, cfg):
            if stacked:
                note_dispatch("gemm", "stream")
                out = gemm_pallas(a2, w, cfg, interpret=pol.interpret, layer=b.index)
            else:
                note_dispatch("gemm", "records" if tuned else ("explicit" if config else "heuristic"))
                out = _gemm_pallas_diff(cfg, pol.interpret, a2, b)
            return out.reshape(lead + (n,))
        note_dispatch("gemm", "xla")
    if stacked:  # XLA fuses the slice of the layer into the dot
        b = jax.lax.dynamic_index_in_dim(w, b.index, 0, keepdims=False)
    out = jnp.dot(a2, b)
    return out.reshape(lead + (n,))
