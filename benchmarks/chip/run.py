"""Run one benchmark cell once and print its result as the last line of
standard output.

    python -m benchmarks.chip.run --workload yi-6b.prefill-4k --seed 7 \
        --seconds 50 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled run.  The run needs a TPU: with none
it exits 2 and prints no result.  ``--cpu-rehearsal`` runs the same path
on the CPU at the registry's reduced sizes, prints what it found to
standard error and exits 3, again with no result.  ``--readings SEEDS``
reads, instead of a run, the correctness check on each seed and its
control beside it (the float32 reference with float8 e4m3 weights or
operands, put in the program's place), each with the check's verdict at
the cell's limit; it is how the limits in ``limits/`` were set.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from . import spec  # noqa: E402
from .harness import Context, device_info, emit, log  # noqa: E402
from .peaks import peak_for  # noqa: E402


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--readings", default="")
    ap.add_argument("--keep-trace", default=None,
                    help="write a summary of the trace's planes and events here")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def _compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in this
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping
    every program, so that only a cell's first run compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _per_layer(cell, run) -> dict:
    out = {}
    for m in cell.per_layer:
        value = spec.load_metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = _parse(argv)
    cell = spec.load().cell(args.workload)
    _compile_cache()
    device = device_info()
    rehearsal = device["platform"] != "tpu"
    if rehearsal and not args.cpu_rehearsal:
        log(f"JAX found no TPU (platform {device['platform']!r}); this benchmark "
            "measures the chip and prints nothing without one")
        return 2
    if device["count"] < cell.chips:
        log(f"cell {cell.name} needs {cell.chips} chips, JAX sees {device['count']}")
        return 2
    peak = None if rehearsal else peak_for(device["kind"])
    if rehearsal:
        from repro.kernels import ops

        ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True))  # the interpreter
    driver = importlib.import_module(f"{__package__}.drivers.{cell.traffic['driver']}")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    ctx = Context(
        cell=cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearsal=rehearsal, t_start=T_START, trace_dir=trace_dir,
        readings=tuple(int(s) for s in args.readings.split(",") if s),
    )
    try:
        if ctx.readings:
            rows = driver.readings(ctx, str(spec.ROOT))
            print(json.dumps({"readings": rows, "cell": cell.name, "device": device}))
            return 0
        result = _result(ctx, driver, device, peak, args.keep_trace)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if rehearsal:
        log("CPU rehearsal at reduced sizes, not a measurement: "
            + json.dumps({k: result[k] for k in ("correct", "attempted", "failed", "checks")}))
        log("rehearsal found readings for " + ", ".join(sorted(result["metrics"]))
            + "; a CPU prints no device metric")
        return 3
    emit(result)
    return 0


def _result(ctx, driver, device, peak, keep_trace) -> dict:
    cell = ctx.cell
    res = driver.run(ctx, str(spec.ROOT))
    run = res["run"]
    run["peak"] = peak
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    result = {"correct": res["correct"], "attempted": res["attempted"], "failed": res["failed"]}
    if ctx.trace:
        from . import trace as tr

        # run[key] for each profile the driver took; "trace" is the window's
        for key, where in (run.pop("trace_dirs", None) or {"trace": ctx.trace_dir}).items():
            path = tr.find_xplane(where)
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                stem = os.path.join(keep_trace, f"{cell.name}.{key}")
                with open(f"{stem}.describe.json", "w") as f:
                    json.dump(tr.describe(path), f)
                if os.path.getsize(path) < 16 << 20:
                    shutil.copy(path, f"{stem}.xplane.pb")
            run[key] = tr.load(path)
        t0, t1, _ = run["traced"]
        device["busy_s"] = run["trace"].busy_s()
        device["window_s"] = t1 - t0
        result["metrics"] = _per_layer(cell, run)
        result["device"] = device
        result["breakdown"] = {
            "device_ops": sorted(run["trace"].op_seconds().items(), key=lambda kv: -kv[1])[:10],
            "idle_gaps": run["trace"].idle_gaps(10),
        }
    else:
        result["metrics"] = {
            m["name"]: {"value": res["e2e"][m["name"]], "unit": m["unit"]} for m in cell.end_to_end
        }
        result["device"] = device
    result["checks"] = res["checks"]
    return result


if __name__ == "__main__":
    sys.exit(main())
