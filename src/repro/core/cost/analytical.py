"""Analytical TPU-v5e cost model for tiled GEMM.

This is the default cost oracle in this container (which has no TPU and
is CPU-only): a physically-grounded roofline model of one chip executing
the Pallas kernel produced by a :class:`TilingState`.  It plays the role
the Titan Xp played in the paper — the thing the tuners query — while
being deterministic (optionally noisy) and cheap, which also lets tests
brute-force small spaces and check the tuners actually find the optimum.

Model (see DESIGN.md §2 for the state->kernel mapping):

  grid      = (m0, k0, n0) macro-steps, k innermost (C accumulates in VMEM)
  VMEM use  = 2*(bm*bk + bk*bn)*in_bytes (double-buffered) + bm*bn*4 (acc)
              -> inf ("fails to build") above the budget, like a TVM
              measurement failure
  compute   = #MXU calls * padded-call-flops / peak;  each call is
              (sub_m x bk) @ (bk x sub_n), padded to sublane/lane/MXU
              granularity -> misaligned tiles waste systolic cycles
  memory    = HBM traffic with k-innermost reuse:
              A read n0 times, B read m0 times, C written once
  overhead  = per-grid-step DMA/dispatch cost + per-MXU-call issue cost

  cost      = max(compute, memory) + overheads   [+ lognormal noise]
"""

from __future__ import annotations

import math

import numpy as np

from ..analysis import VMEM_BUDGET_BYTES, ScheduleAnalyzer, gemm_working_set_bytes
from ..config_space import GemmConfigSpace, TilingState
from .base import CostBackend

__all__ = ["TpuSpec", "AnalyticalTPUCost"]


def _analytical_from_spec(
    m: int, k: int, n: int, d_m: int, d_k: int, d_n: int,
    n_repeats: int, in_bytes: int, out_bytes: int,
    noise_sigma: float, seed: int,
) -> "AnalyticalTPUCost":
    """Worker-process factory (see ``CostBackend.worker_spec``)."""
    return AnalyticalTPUCost(
        GemmConfigSpace(m, k, n, d_m, d_k, d_n),
        n_repeats=n_repeats,
        in_bytes=in_bytes,
        out_bytes=out_bytes,
        noise_sigma=noise_sigma,
        seed=seed,
    )


class TpuSpec:
    """TPU v5e-like single-chip constants (shared with §Roofline)."""

    peak_flops = 197e12  # bf16 FLOP/s
    vpu_flops = 19.7e12  # elementwise f32 throughput (softmax path)
    hbm_bw = 819e9  # B/s
    ici_bw = 50e9  # B/s per link (used by the distributed roofline)
    vmem_bytes = VMEM_BUDGET_BYTES  # usable VMEM budget for one kernel
    sublane = {2: 16, 4: 8}  # dtype bytes -> sublane granularity
    lane = 128
    mxu_k = 128  # contraction granularity fed to the systolic array
    grid_step_overhead_s = 2.0e-7  # DMA issue + grid bookkeeping per step
    mxu_call_overhead_s = 5.0e-9  # per dot issue (pipelined, small)


def _pad(x: int, g: int) -> int:
    return ((x + g - 1) // g) * g


class AnalyticalTPUCost(CostBackend):
    name = "analytical_tpu_v5e"

    def __init__(
        self,
        space: GemmConfigSpace,
        n_repeats: int = 1,
        in_bytes: int = 2,  # bf16 inputs
        out_bytes: int = 2,
        noise_sigma: float = 0.0,
        seed: int = 0,
        spec: TpuSpec | None = None,
    ):
        super().__init__(space, n_repeats)
        self.in_bytes = in_bytes
        self.out_bytes = out_bytes
        self.noise_sigma = noise_sigma
        self.seed = seed
        self.spec = spec or TpuSpec()
        # the shared static analyzer owns the feasibility cliff, so this
        # oracle and the engine's pre-filter can never disagree
        self.analyzer = ScheduleAnalyzer(
            self.space, spec=self.spec, in_bytes=self.in_bytes
        )

    # -- components -----------------------------------------------------------
    def vmem_bytes(self, s: TilingState) -> int:
        return self.analyzer.vmem_bytes(s)

    def compute_time(self, s: TilingState) -> float:
        sp = self.spec
        gm, gk, gn = s.grid
        bm, bk, bn = s.block_m, s.block_k, s.block_n
        sub_m, sub_n = s.sub_m, s.sub_n
        sub_gran = sp.sublane.get(self.in_bytes, 8)
        n_calls = gm * gk * gn * (bm // sub_m) * (bn // sub_n)
        call_flops = (
            2.0
            * _pad(sub_m, sub_gran)
            * _pad(bk, sp.mxu_k)
            * _pad(sub_n, sp.lane)
        )
        return n_calls * call_flops / sp.peak_flops + n_calls * sp.mxu_call_overhead_s

    def memory_time(self, s: TilingState) -> float:
        sp = self.spec
        gm, gk, gn = s.grid
        M, K, N = self.space.m, self.space.k, self.space.n
        a_traffic = M * K * gn * self.in_bytes  # A streamed once per n0 slice
        b_traffic = K * N * gm * self.in_bytes  # B streamed once per m0 slice
        c_traffic = M * N * self.out_bytes  # k-innermost: C written once
        return (a_traffic + b_traffic + c_traffic) / sp.hbm_bw

    def overhead_time(self, s: TilingState) -> float:
        gm, gk, gn = s.grid
        return gm * gk * gn * self.spec.grid_step_overhead_s

    def breakdown(self, s: TilingState) -> dict:
        return {
            "vmem_bytes": self.vmem_bytes(s),
            "compute_s": self.compute_time(s),
            "memory_s": self.memory_time(s),
            "overhead_s": self.overhead_time(s),
        }

    # -- CostBackend ------------------------------------------------------------
    def measure_fingerprint(self) -> str:
        return (
            f"r{self.n_repeats}|noise{self.noise_sigma:g}|seed{self.seed}"
            f"|io{self.in_bytes}.{self.out_bytes}"
            + self.space_fingerprint()
        )

    def worker_spec(self):
        # extra_constraint is an arbitrary closure and self.spec could be
        # subclassed — neither survives the spec round-trip, so refuse to
        # ship rather than rebuild a subtly different model
        if self.space.extra_constraint is not None or type(self.spec) is not TpuSpec:
            return None
        sp = self.space
        return (
            "repro.core.cost.analytical:_analytical_from_spec",
            {
                "m": sp.m, "k": sp.k, "n": sp.n,
                "d_m": sp.d_m, "d_k": sp.d_k, "d_n": sp.d_n,
                "n_repeats": self.n_repeats,
                "in_bytes": self.in_bytes, "out_bytes": self.out_bytes,
                "noise_sigma": self.noise_sigma, "seed": self.seed,
            },
        )

    def _noise_factor(self, s: TilingState, repeat_idx: int) -> float:
        # Deterministic per-(state, repeat) measurement jitter.  Stable
        # across processes (python's hash() is salted per process).
        import zlib

        h = zlib.crc32(f"{self.seed}|{s.key()}|{repeat_idx}".encode()) & 0xFFFFFFFF
        rng = np.random.default_rng(h)
        return rng.lognormal(0.0, self.noise_sigma)

    def cost_once(self, s: TilingState, repeat_idx: int) -> float:
        if self.analyzer.exceeds_vmem(s):
            return math.inf  # kernel does not fit VMEM: measurement failure
        base = max(self.compute_time(s), self.memory_time(s)) + self.overhead_time(s)
        if self.noise_sigma <= 0.0:
            return base
        return float(base * self._noise_factor(s, repeat_idx))

    def _base_batch(self, states: list[TilingState]) -> np.ndarray:
        """Vectorized noise-free model: one numpy pass over the batch.

        Intermediate tile counts/FLOPs are accumulated as exact Python
        ints (they can exceed 2**53) and only converted to float64 for
        the final divisions, which keeps every element bit-identical to
        the scalar ``cost_once`` path.
        """
        sp = self.spec
        sub_gran = sp.sublane.get(self.in_bytes, 8)
        M, K, N = self.space.m, self.space.k, self.space.n
        vmem, n_calls, flops, steps, traffic = [], [], [], [], []
        for s in states:
            gm, gk, gn = s.grid
            bm, bk, bn = s.block_m, s.block_k, s.block_n
            vmem.append(gemm_working_set_bytes(bm, bk, bn, self.in_bytes))
            nc = gm * gk * gn * (bm // s.sub_m) * (bn // s.sub_n)
            cf = (
                2
                * _pad(s.sub_m, sub_gran)
                * _pad(bk, sp.mxu_k)
                * _pad(s.sub_n, sp.lane)
            )
            n_calls.append(nc)
            flops.append(nc * cf)
            steps.append(gm * gk * gn)
            traffic.append(
                M * K * gn * self.in_bytes
                + K * N * gm * self.in_bytes
                + M * N * self.out_bytes
            )
        compute = (
            np.asarray(flops, np.float64) / sp.peak_flops
            + np.asarray(n_calls, np.float64) * sp.mxu_call_overhead_s
        )
        memory = np.asarray(traffic, np.float64) / sp.hbm_bw
        base = np.maximum(compute, memory) + np.asarray(steps, np.float64) * sp.grid_step_overhead_s
        base[np.asarray(vmem) > sp.vmem_bytes] = math.inf
        return base

    def batch_cost(self, states) -> list[float]:
        """Vectorized batch measurement; value-identical to ``cost`` per
        state (the measurement engine's parallel-lane fast path)."""
        states = list(states)
        base = self._base_batch(states)
        out: list[float] = []
        for i, s in enumerate(states):
            b = float(base[i])
            if not self.space.is_legitimate(s) or math.isinf(b):
                out.append(math.inf)
                continue
            if self.noise_sigma <= 0.0 and self.n_repeats == 1:
                out.append(b)
                continue
            total = 0.0  # replicate cost()'s repeat-mean summation order
            for r in range(self.n_repeats):
                total += (
                    b
                    if self.noise_sigma <= 0.0
                    else float(b * self._noise_factor(s, r))
                )
            out.append(total / self.n_repeats)
        return out

    def optimum(self, max_states: int = 2_000_000) -> tuple[TilingState, float]:
        """Brute-force the space (only for small spaces / tests)."""
        if self.space.size() > max_states:
            raise ValueError("space too large to brute force")
        best_s, best_c = None, math.inf
        for s in self.space.enumerate():
            c = self.cost(s)
            if c < best_c:
                best_s, best_c = s, c
        assert best_s is not None
        return best_s, best_c
