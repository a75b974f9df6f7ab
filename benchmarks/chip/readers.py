"""Reductions that the per-layer metric files share.  Each takes the
run's record (``run``: spans, counters, census, trace) and returns a
number, or None where the run holds nothing to read."""

from __future__ import annotations

from . import counts, spec
from . import trace as tr
from .harness import log

__all__ = [
    "idle_share", "mfu_prefill", "mfu_decode", "census_roofline_s", "census_flops",
    "kernel_roofline",
]


def idle_share(run) -> float | None:
    """Percent of the traced window in which no operation ran on the device."""
    trace, traced = run.get("trace"), run.get("traced")
    if trace is None or traced is None:
        return None
    window = traced[1] - traced[0]
    busy = trace.busy_s()
    if busy <= 0 or window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)


def mfu_prefill(run) -> float | None:
    """Model FLOPs of the window's prefill calls over their host-clock
    spans, as a percent of the chip's bf16 peak."""
    spans = run.get("prefill_s")
    if not spans or run.get("peak") is None:
        return None
    tr, model = run["traffic"], run["model"]
    family = spec.load_family(model["family"])
    flops = family.prefill_flops(model, tr["batch"], tr["prompt_len"]) * len(spans)
    return 100.0 * flops / sum(spans) / run["peak"].bf16_flops


def mfu_decode(run) -> float | None:
    """Model FLOPs of the window's decode loops over their spans."""
    spans = run.get("decode_s")
    if not spans or run.get("peak") is None:
        return None
    tr, model = run["traffic"], run["model"]
    steps = max(tr["gen_buckets"])
    family = spec.load_family(model["family"])
    flops = family.decode_flops(model, tr["batch"], tr["prompt_len"], steps) * len(spans)
    return 100.0 * flops / sum(spans) / run["peak"].bf16_flops


def census_roofline_s(launches, peak) -> float:
    return sum(c.count * counts.launch_roofline_s(c, peak) for c in launches)


def census_flops(launches) -> float:
    return sum(c.count * counts.launch_flops(c) for c in launches)


def kernel_roofline(run, kind: str, phase: str) -> float | None:
    """Roofline time of the traced calls' ``kind`` launches in the
    ``phase`` program (``prefill`` or ``decode``), from the census, over
    their device time in the trace, in percent.

    A program run is the phase's when it holds a GEMM of a shape that
    only that phase's census has; every launch of the kind in it counts.
    The launches found must be the census's, call for call.  A ``kind``
    with no file in ``launches/`` is an error that names the file."""
    spec.load_launch(kind)
    trace, census, traced = run.get("trace"), run.get("census"), run.get("traced")
    if trace is None or not census or traced is None or run.get("peak") is None:
        return None
    launches = [c for c in census[phase] if c.kind == kind]
    if not launches:
        return None
    other = "decode" if phase == "prefill" else "prefill"
    own = {c.dims for c in census[phase] if c.kind == "gemm"}
    own -= {c.dims for c in census[other] if c.kind == "gemm"}
    gemms = tr.module_kernels(trace, "gemm")
    kernels = tr.module_kernels(trace, kind)
    events = [
        e for (m, g), (_, k) in zip(gemms, kernels)
        if any(tr.launch_dims("gemm", x) in own for x in g)
        for e in k
    ]
    calls = traced[2]
    want = sum(c.count for c in launches) * calls
    if len(events) != want:
        log(f"{kind} in {phase}: {len(events)} launches in the trace, census says {want}")
        return None
    log(f"{kind} in {phase}: {len(events)} launches in the trace, as the census says")
    device_s = sum(e.dur_ns for e in events) / 1e9
    return 100.0 * census_roofline_s(launches, run["peak"]) * calls / device_s
