"""What every driver shares: the run's context, the clock of backend
compiles, the device's description and the result line."""

from __future__ import annotations

import dataclasses
import json
import math
import statistics
import sys
import time
from typing import Optional

__all__ = [
    "Context", "CompileClock", "device_info", "memory_peak_bytes", "percentile", "median",
    "emit", "log", "wall",
]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    cell: object  # spec.Cell
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool  # CPU, reduced sizes: prints no result
    t_start: float  # perf_counter() when the process began to set up
    trace_dir: Optional[str] = None  # where the profiler writes
    readings: tuple = ()  # seeds to read the check and its control on, instead of a run


class CompileClock:
    """Counts XLA backend compiles in this process, from JAX's own
    monitoring events: a program loaded from the persistent
    compilation cache is not one."""

    def __init__(self):
        import jax

        self.n = 0
        self.s = 0.0

        def listener(event: str, seconds: float, **_) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.s += seconds

        jax.monitoring.register_event_duration_secs_listener(listener)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return statistics.median(values)


def emit(result: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard
    output, with the checks last in it."""
    checks = result.pop("checks")
    for name, c in checks.items():
        print(f"[check] {name} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def wall() -> float:
    return time.perf_counter()
