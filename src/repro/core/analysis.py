"""Static schedule analysis — compile-free legality/feasibility verdicts
over :class:`~repro.core.space.SearchSpace` schedule states.

Every candidate a tuner proposes normally burns a measurement lane (or a
full XLA compile) even when it is *statically* doomed.  TVM bakes these
legality constraints into its schedule templates; here they live in one
analyzer that every layer shares, so the oracle, the measurement
engine's pre-filter, trace-time dispatch, and the audit CLI can never
disagree about what "cannot work" means.

Verdict lattice (``AnalysisResult.verdict``):

``ILLEGAL`` — provably cannot compile or fit.  Two reason families:

  * *structural* (``SearchSpace.structural_error``): wrong row count or
    nesting depth, a factor < 1 (a zero grid dim), a row product that
    does not equal its dimension (which also covers block > dim), or a
    constraint-hook rejection.  Every oracle already scores these
    ``inf`` via ``is_legitimate``.
  * ``vmem_overflow``: the double-buffered working set (including the
    f32 scratch, via the op's single budget function below) exceeds the
    ``TpuSpec`` VMEM budget.  Both analytical cost models delegate their
    feasibility cliff here, and ``XLATimedCost``'s guard uses the same
    ``working_set_bytes``, so ILLEGAL states measure ``inf`` under every
    backend.

``WASTEFUL`` — legal but dominated.  Reasons:

  * ``degenerate``: the padding ratio (padded MXU/VPU FLOPs over useful
    FLOPs) sits at the space's worst-case corner — no tiling at all on
    any aligned axis (for GEMM: ``sub_m == block_k == sub_n == 1``).
  * ``padding``: padding ratio at or above an advisory threshold
    (default 16x) — e.g. a lane-misaligned ``sub_n``.  Misalignment is
    WASTEFUL, *not* ILLEGAL: Pallas pads and compiles such blocks fine,
    it just wastes systolic cycles.
  * ``under_buffer``: working set below the double-buffer floor (two
    double-buffered operand tiles of minimal aligned shape) — the DMA
    engine cannot overlap anything useful.

``OK`` — no static objection.

Pruning policy (:func:`should_prune`, what ``MeasureEngine``'s
``analyze="prune"`` rejects): ILLEGAL plus *only* the ``degenerate``
WASTEFUL subclass.  ILLEGAL pruning is sequence-preserving by
construction (the oracle returns ``inf`` for exactly those states).
Degenerate states are the provable plateau maximum of the padding model
and can never be a returned best; empirically, pruning them leaves the
G-BFS final best bit-identical on the paper's 1024^3 protocol at every
fraction/seed while still avoiding trials.  Pruning the *broader*
WASTEFUL classes is NOT search-neutral — replacing their finite-bad
costs with ``inf`` flattens the cost gradient greedy search descends —
so ``padding``/``under_buffer`` only ever warn.

This module deliberately imports nothing from the rest of ``repro.core``
at module level (``TpuSpec`` is resolved lazily); the spaces and cost
models import *it*.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

__all__ = [
    "ILLEGAL",
    "WASTEFUL",
    "OK",
    "AnalysisResult",
    "ScheduleAnalyzer",
    "analyzer_for_backend",
    "should_prune",
    "register_padding_model",
    "gemm_working_set_bytes",
    "flash_working_set_bytes",
    "dtype_in_bytes",
]

ILLEGAL = "ILLEGAL"
WASTEFUL = "WASTEFUL"
OK = "OK"

#: dtype name -> element bytes (for analyzers built from a backend's
#: dtype string rather than an explicit in_bytes)
_DTYPE_BYTES = {
    "float64": 8, "f64": 8,
    "float32": 4, "f32": 4,
    "bfloat16": 2, "bf16": 2,
    "float16": 2, "f16": 2,
    "int8": 1, "uint8": 1,
}


def dtype_in_bytes(dtype: Optional[str], default: int = 2) -> int:
    """Element size of a dtype name; unknown/None falls back to bf16."""
    if dtype is None:
        return default
    return _DTYPE_BYTES.get(str(dtype), default)


# -- single-source VMEM budget functions --------------------------------------
# THE working-set arithmetic.  GemmConfigSpace/FlashAttnConfigSpace
# delegate their ``working_set_bytes`` here and the cost models' batch
# paths call these directly, so the double-buffer multiplier and scratch
# accounting exist exactly once.  Exact integer arithmetic — callers
# rely on bit-identical values.


#: the v5e compiler's scoped VMEM limit: the working set one kernel may
#: take (``TpuSpec.vmem_bytes``, ``XLATimedCost.vmem_guard_bytes`` and
#: the GEMM kernel's heuristic blocks all read it)
VMEM_BUDGET_BYTES = 16 << 20


def gemm_working_set_bytes(block_m: int, block_k: int, block_n: int,
                           in_bytes: int = 2) -> int:
    """Double-buffered A/B blocks plus the f32 accumulator."""
    return 2 * (block_m * block_k + block_k * block_n) * in_bytes \
        + block_m * block_n * 4


def flash_working_set_bytes(block_q: int, block_kv: int, seq_kv: int,
                            head_dim: int, in_bytes: int = 2) -> int:
    """Q block + fully resident K/V (the kernel's BlockSpec streams whole
    sequences per grid cell) + f32 accumulator, logits tile, and running
    max/sum."""
    return (
        (block_q * head_dim + 2 * seq_kv * head_dim) * in_bytes
        + block_q * head_dim * 4  # f32 accumulator
        + block_q * block_kv * 4  # logits/probability tile
        + 2 * block_q * 4  # running max + sum
    )


def _pad(x: int, g: int) -> int:
    return ((x + g - 1) // g) * g


# -- per-op padding models ----------------------------------------------------
# (tiles, ratio) per op: ``tiles(space, state)`` extracts the tunable
# MXU-facing tile values; ``ratio(space, tiles, spec, sub_gran)`` is
# padded FLOPs over useful FLOPs for those tiles.  The all-ones tile
# tuple is the space's worst corner — the "degenerate" class.


def _gemm_padding_tiles(space, s) -> tuple[int, ...]:
    return (s.sub_m, s.block_k, s.sub_n)


def _gemm_padding_ratio(space, tiles, spec, sub_gran: int) -> float:
    sub_m, bk, sub_n = tiles
    padded = _pad(sub_m, sub_gran) * _pad(bk, spec.mxu_k) * _pad(sub_n, spec.lane)
    return padded / (sub_m * bk * sub_n)


def _flash_padding_tiles(space, s) -> tuple[int, ...]:
    return (s.block_q, s.block_kv)


def _flash_padding_ratio(space, tiles, spec, sub_gran: int) -> float:
    bq, bkv = tiles
    hd = space.head_dim
    # the kernel's two MXU calls per kv visit: q @ k^T and p @ v
    padded = _pad(bq, sub_gran) * (
        _pad(hd, spec.mxu_k) * _pad(bkv, spec.lane)
        + _pad(bkv, spec.mxu_k) * _pad(hd, spec.lane)
    )
    return padded / (bq * 2 * hd * bkv)


_PADDING_MODELS: dict[str, tuple[Callable, Callable]] = {
    "gemm": (_gemm_padding_tiles, _gemm_padding_ratio),
    "flash": (_flash_padding_tiles, _flash_padding_ratio),
}


def register_padding_model(op: str, tiles: Callable, ratio: Callable) -> None:
    """Plug a padding model in for a new op (same shapes as the built-in
    gemm/flash entries); ops without one skip the WASTEFUL padding
    checks but still get structural + VMEM legality."""
    _PADDING_MODELS[op] = (tiles, ratio)


# -- results ------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AnalysisResult:
    """One verdict: ``(verdict, reason, detail)``.  ``reason`` is the
    stable machine-readable tag (what journal ``static`` rows and tests
    key on); ``detail`` is the human-readable explanation."""

    verdict: str
    reason: str = ""
    detail: str = ""

    @property
    def ok(self) -> bool:
        return self.verdict == OK

    @property
    def illegal(self) -> bool:
        return self.verdict == ILLEGAL

    @property
    def wasteful(self) -> bool:
        return self.verdict == WASTEFUL


_OK_RESULT = AnalysisResult(OK)


def should_prune(result: AnalysisResult) -> bool:
    """The engine's search-neutral prune policy: ILLEGAL (the oracle
    scores those ``inf`` anyway) plus the ``degenerate`` WASTEFUL
    subclass only (see module docstring for why the broader WASTEFUL
    classes must keep measuring)."""
    return result.illegal or (result.wasteful and result.reason == "degenerate")


class ScheduleAnalyzer:
    """Classifies schedule states of one space without compiling or
    running anything.  Verdicts are pure functions of
    ``(state, space identity, spec, in_bytes, thresholds)`` — memoized
    per state key, and two analyzers built with equal parameters agree
    on every state.

    ``spec`` is duck-typed (needs ``vmem_bytes``, ``sublane``, ``lane``,
    ``mxu_k``); default is the shared :class:`TpuSpec`, imported lazily
    so this module stays import-light.  ``vmem_budget_bytes`` overrides
    the spec's budget (e.g. ``XLATimedCost.vmem_guard_bytes``)."""

    def __init__(
        self,
        space,
        spec=None,
        in_bytes: int = 2,
        wasteful_padding_ratio: float = 16.0,
        vmem_budget_bytes: Optional[int] = None,
    ):
        if spec is None:
            from .cost.analytical import TpuSpec  # lazy: keep imports one-way

            spec = TpuSpec()
        self.space = space
        self.spec = spec
        self.in_bytes = int(in_bytes)
        self.wasteful_padding_ratio = float(wasteful_padding_ratio)
        self.vmem_budget_bytes = (
            int(vmem_budget_bytes)
            if vmem_budget_bytes is not None
            else int(spec.vmem_bytes)
        )
        self._sub_gran = spec.sublane.get(self.in_bytes, 8)
        # two double-buffered operand tiles of minimal aligned shape —
        # below this the DMA engine has nothing to overlap
        self.buffer_floor_bytes = 2 * 2 * self._sub_gran * spec.lane * self.in_bytes
        self._model = _PADDING_MODELS.get(getattr(space, "op", None))
        self._worst_ratio: Optional[float] = None
        self._cache: dict[str, AnalysisResult] = {}

    # -- components ----------------------------------------------------------
    def vmem_bytes(self, s) -> int:
        """The schedule's working set under this analyzer's dtype — the
        single budget source (the space delegates to the functions
        above)."""
        return self.space.working_set_bytes(s, self.in_bytes)

    def exceeds_vmem(self, s) -> bool:
        """The feasibility cliff both analytical cost models delegate
        to.  Kept allocation-free: this sits on the oracle hot path."""
        return self.space.working_set_bytes(s, self.in_bytes) > self.vmem_budget_bytes

    def padding_ratio(self, s) -> Optional[float]:
        """Padded-over-useful FLOPs for the state's MXU tiles, or None
        when the op has no registered padding model."""
        if self._model is None:
            return None
        tiles, ratio = self._model
        return ratio(self.space, tiles(self.space, s), self.spec, self._sub_gran)

    def worst_padding_ratio(self) -> Optional[float]:
        """The space's worst padding corner — every tunable tile at 1
        (for GEMM that is the untiled ``sub_m = block_k = sub_n = 1``
        class).  States *at* this ratio are the ``degenerate`` class."""
        if self._model is None:
            return None
        if self._worst_ratio is None:
            tiles, ratio = self._model
            n = len(tiles(self.space, self.space.initial_state()))
            self._worst_ratio = ratio(
                self.space, (1,) * n, self.spec, self._sub_gran
            )
        return self._worst_ratio

    # -- classification ------------------------------------------------------
    def analyze(self, s) -> AnalysisResult:
        try:
            key = s.key()
        except Exception:
            return self._classify(s)
        cached = self._cache.get(key)
        if cached is None:
            cached = self._cache[key] = self._classify(s)
        return cached

    def _classify(self, s) -> AnalysisResult:
        err = self._structural(s)
        if err is not None:
            return AnalysisResult(ILLEGAL, err[0], err[1])
        ws = self.vmem_bytes(s)
        if ws > self.vmem_budget_bytes:
            return AnalysisResult(
                ILLEGAL,
                "vmem_overflow",
                f"working set {ws} B exceeds the {self.vmem_budget_bytes} B "
                f"VMEM budget (in_bytes={self.in_bytes})",
            )
        ratio = self.padding_ratio(s)
        if ratio is not None:
            worst = self.worst_padding_ratio()
            if worst is not None and ratio >= worst:
                return AnalysisResult(
                    WASTEFUL,
                    "degenerate",
                    f"padding ratio {ratio:.0f}x is the space's worst corner "
                    f"(no tiling on any MXU/VPU-aligned axis)",
                )
            if ratio >= self.wasteful_padding_ratio:
                return AnalysisResult(
                    WASTEFUL,
                    "padding",
                    f"padding ratio {ratio:.1f}x >= "
                    f"{self.wasteful_padding_ratio:g}x: misaligned tiles "
                    f"waste most systolic cycles",
                )
        if ws < self.buffer_floor_bytes:
            return AnalysisResult(
                WASTEFUL,
                "under_buffer",
                f"working set {ws} B is below the {self.buffer_floor_bytes} B "
                f"double-buffer floor",
            )
        return _OK_RESULT

    def _structural(self, s) -> Optional[tuple[str, str]]:
        structural_error = getattr(self.space, "structural_error", None)
        try:
            if structural_error is not None:
                return structural_error(s)
            if self.space.is_legitimate(s):
                return None
            return ("illegitimate", "state fails the space's legitimacy check")
        except Exception as e:  # malformed rows: wrong types, bad arity
            return ("malformed", f"{type(e).__name__}: {e}")


def analyzer_for_backend(backend) -> ScheduleAnalyzer:
    """Build the analyzer matching a cost backend's measurement settings:
    its space, its element width (``in_bytes`` attribute or dtype), its
    chip spec when it carries one, and its VMEM guard when it overrides
    the spec budget (``XLATimedCost.vmem_guard_bytes``)."""
    in_bytes = getattr(backend, "in_bytes", None)
    if in_bytes is None:
        in_bytes = dtype_in_bytes(getattr(backend, "dtype", None))
    return ScheduleAnalyzer(
        backend.space,
        spec=getattr(backend, "spec", None),
        in_bytes=in_bytes,
        vmem_budget_bytes=getattr(backend, "vmem_guard_bytes", None),
    )
