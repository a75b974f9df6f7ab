"""Cost-backend protocol — the paper's "run the configuration on target
hardware" abstraction (TVM measure).  A backend times one op's schedule
states (``backend.op``, derived from its space) and returns seconds per
kernel invocation;
``math.inf`` marks a configuration that fails to build/run (illegitimate
on the hardware), matching how TVM reports failed measurements.

Backends expose two entry points:

* ``cost(s)`` — one state, the historical serial path;
* ``batch_cost(states)`` — a *batch* of states for the measurement
  engine's parallel lanes.  The base implementation is a serial loop
  (always correct); concrete backends override it with something
  genuinely concurrent: :class:`AnalyticalTPUCost` vectorizes the model
  with numpy, :class:`XLATimedCost` compiles candidates on a thread
  pool, and :class:`CountingCost` advances its simulated clock by the
  per-wave *maximum* lane time so ``n_workers`` parallel lanes are
  modeled honestly.

Whatever the override, ``batch_cost(states)[i]`` must equal
``cost(states[i])`` for a fresh backend — batching changes time
accounting, never values.

For *process-backed* measurement lanes
(:class:`~repro.core.executor.ProcessExecutor`), a backend additionally
advertises a **worker spec** — a picklable ``("module:callable",
kwargs)`` recipe that worker processes use to rebuild an equivalent
backend on their side of the process boundary (the backend object
itself is never pickled; JAX arrays and compiled-function caches don't
survive a pickle round-trip).  ``worker_spec()`` returns ``None`` for
backends that cannot be shipped.
"""

from __future__ import annotations

import abc
import importlib
import math
import operator
import os
import time
from typing import Optional, Sequence

from ..space import SearchSpace, State

__all__ = ["CostBackend", "CountingCost", "SleepingCost", "backend_from_spec"]


def backend_from_spec(spec: tuple[str, dict]) -> "CostBackend":
    """Rebuild a backend from a :meth:`CostBackend.worker_spec` recipe —
    the worker-process side of the executor boundary."""
    entry, kwargs = spec
    mod_name, _, attr = entry.partition(":")
    fn = operator.attrgetter(attr)(importlib.import_module(mod_name))
    return fn(**kwargs)


class CostBackend(abc.ABC):
    """Measures ``cost(s; m, k, n, d_m, d_k, d_n)`` (paper Sec. 3.3)."""

    name: str = "base"
    #: True for backends that run the schedule's program on JAX's device
    #: and time it: their cost grows with the schedule's grid, and only
    #: the process that holds the device can measure them
    measured: bool = False

    def __init__(self, space: SearchSpace, n_repeats: int = 1):
        self.space = space
        # paper: "arithmetic mean for 10 repeated trials"
        self.n_repeats = n_repeats

    @property
    def op(self) -> str:
        """Which operator this backend times (journal/cache scoping)."""
        return getattr(self.space, "op", "gemm")

    @abc.abstractmethod
    def cost_once(self, s: State, repeat_idx: int) -> float:
        ...

    def cost(self, s: State) -> float:
        if not self.space.is_legitimate(s):
            return math.inf
        total = 0.0
        for r in range(self.n_repeats):
            c = self.cost_once(s, r)
            if not math.isfinite(c):
                return math.inf
            total += c
        return total / self.n_repeats

    def batch_cost(self, states: Sequence[State]) -> list[float]:
        """Measure a batch; value-equivalent to ``[cost(s) for s in states]``."""
        return [self.cost(s) for s in states]

    def measure_fingerprint(self) -> str:
        """Identifies the backend's *measurement settings* (not just its
        name), so persistent caches never serve a cost measured under
        different settings — e.g. a different noise model or repeat
        count — as if it were this backend's measurement."""
        return f"r{self.n_repeats}" + self.space_fingerprint()

    def space_fingerprint(self) -> str:
        """Fingerprint component for non-default space construction
        kwargs (``SearchSpace.spec_kwargs``) — e.g. flash's ``causal``
        flag changes every measured value, so journals must scope on it.
        Empty kwargs contribute nothing, keeping pre-registry GEMM
        fingerprints (and their journals) valid."""
        kw = getattr(self.space, "spec_kwargs", dict)() or {}
        if not kw:
            return ""
        return "|" + ",".join(f"{k}={v!r}" for k, v in sorted(kw.items()))

    def worker_spec(self) -> Optional[tuple[str, dict]]:
        """Picklable ``("module:callable", kwargs)`` recipe that rebuilds
        an equivalent backend inside a measurement worker process, or
        ``None`` when this backend cannot cross a process boundary (see
        :func:`backend_from_spec`).  The rebuilt backend must produce the
        same costs as this one."""
        return None

    def compile_stats(self) -> Optional[dict]:
        """Cumulative build-cache counters for backends that compile
        programs (``compiles``/``mem_hits``/``disk_hits``/``evictions``/
        ``compile_s``/``n_timed``), or ``None`` for backends with no
        build step.  The measurement engine folds per-wave deltas into
        :class:`~repro.core.measure.MeasureStats` — across a process
        boundary the worker ships the delta back with each job result."""
        return None


class CountingCost(CostBackend):
    """Wraps another backend, counting measurements and charging a
    simulated (or real) wall-clock per trial — used by the benchmark
    harness to reproduce the paper's cost-vs-time plots without real
    hardware time.

    ``n_workers`` models parallel measurement lanes: a batched call is
    split into waves of ``n_workers`` states and each wave advances the
    simulated clock by its *maximum* lane time, so the clock of a
    parallel harness agrees with what ``TuningContext`` charges.  Each
    lane's charge is capped at ``timeout_s`` (AutoTVM-style measurement
    timeout), matching ``TuningContext.measure_timeout_s`` — without the
    cap, a pathological config (e.g. the untiled s0) charges minutes of
    simulated time here while the context charges 4 s, and the two
    clocks diverge.
    """

    def __init__(
        self,
        inner: CostBackend,
        simulated_overhead_s: float = 0.35,
        timeout_s: float = 4.0,
        n_workers: int = 1,
    ):
        super().__init__(inner.space, n_repeats=1)
        self.inner = inner
        self.name = f"counting({inner.name})"
        self.measured = inner.measured
        self.n_measured = 0
        self.simulated_clock_s = 0.0
        self.wall_started = time.monotonic()
        # TVM-style per-trial overhead: codegen + upload + launch. The
        # paper's Fig 7b horizontal axis is dominated by this, not by the
        # GEMM itself.
        self.simulated_overhead_s = simulated_overhead_s
        self.timeout_s = timeout_s
        self.n_workers = max(1, n_workers)

    def cost_once(self, s: State, repeat_idx: int) -> float:  # pragma: no cover
        raise RuntimeError("CountingCost delegates via cost()")

    def _lane_s(self, c: float) -> float:
        t = self.simulated_overhead_s
        if math.isfinite(c):
            t += min(c * self.inner.n_repeats, self.timeout_s)
        return t

    def cost(self, s: State) -> float:
        c = self.inner.cost(s)
        self.n_measured += 1
        self.simulated_clock_s += self._lane_s(c)
        return c

    def batch_cost(self, states: Sequence[State]) -> list[float]:
        out: list[float] = []
        for i in range(0, len(states), self.n_workers):
            wave = states[i : i + self.n_workers]
            costs = self.inner.batch_cost(wave)
            self.n_measured += len(wave)
            self.simulated_clock_s += max(self._lane_s(c) for c in costs)
            out.extend(costs)
        return out

    def compile_stats(self) -> Optional[dict]:
        return self.inner.compile_stats()

    def fraction_explored(self) -> float:
        return self.n_measured / max(1, self.space.size())


def _sleeping_from_spec(
    inner: tuple[str, dict],
    delay_s: float,
    hang_s: float,
    raise_keys: list,
    exit_keys: list,
    hang_keys: list,
) -> "SleepingCost":
    return SleepingCost(
        backend_from_spec(inner),
        delay_s=delay_s,
        hang_s=hang_s,
        raise_keys=raise_keys,
        exit_keys=exit_keys,
        hang_keys=hang_keys,
    )


class SleepingCost(CostBackend):
    """Hardware-in-the-loop stand-in: returns the inner backend's costs
    but *occupies real wall-clock* — ``delay_s`` of sleep per measurement,
    the way a device occupies a measurement lane.  This is what the
    executor layer is exercised and benchmarked against in a container
    with no accelerator: real lanes (threads/processes) overlap the
    sleeps, the simulated lane cannot.

    Failure injection (for executor crash/timeout isolation tests):
    states whose ``key()`` is in ``raise_keys`` raise, ``exit_keys``
    hard-kill the measuring process via ``os._exit`` (only meaningful
    under a :class:`~repro.core.executor.ProcessExecutor` — in-process it
    kills the session, which is exactly the failure mode process lanes
    exist to contain), and ``hang_keys`` sleep ``hang_s`` to trip the
    per-lane timeout.
    """

    def __init__(
        self,
        inner: CostBackend,
        delay_s: float = 0.05,
        hang_s: float = 3600.0,
        raise_keys: Sequence[str] = (),
        exit_keys: Sequence[str] = (),
        hang_keys: Sequence[str] = (),
    ):
        super().__init__(inner.space, n_repeats=1)
        self.inner = inner
        self.name = f"sleeping({inner.name})"
        self.measured = inner.measured
        self.delay_s = delay_s
        self.hang_s = hang_s
        self.raise_keys = frozenset(raise_keys)
        self.exit_keys = frozenset(exit_keys)
        self.hang_keys = frozenset(hang_keys)

    def cost_once(self, s: State, repeat_idx: int) -> float:  # pragma: no cover
        raise RuntimeError("SleepingCost delegates via cost()")

    def cost(self, s: State) -> float:
        key = s.key()
        if key in self.exit_keys:
            os._exit(13)  # simulated segfault: no exception, no cleanup
        if key in self.raise_keys:
            raise RuntimeError(f"injected measurement failure for {key}")
        time.sleep(self.hang_s if key in self.hang_keys else self.delay_s)
        return self.inner.cost(s)

    def measure_fingerprint(self) -> str:
        # sleeping changes lane occupancy, never the measured value
        return self.inner.measure_fingerprint()

    def compile_stats(self) -> Optional[dict]:
        return self.inner.compile_stats()

    def worker_spec(self) -> Optional[tuple[str, dict]]:
        inner_spec = self.inner.worker_spec()
        if inner_spec is None:
            return None
        return (
            "repro.core.cost.base:_sleeping_from_spec",
            {
                "inner": inner_spec,
                "delay_s": self.delay_s,
                "hang_s": self.hang_s,
                "raise_keys": sorted(self.raise_keys),
                "exit_keys": sorted(self.exit_keys),
                "hang_keys": sorted(self.hang_keys),
            },
        )
