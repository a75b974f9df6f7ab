"""A timed GEMM tuning job: the tuner searches chip-timed schedules for
the GEMMs of one prefill call for ``--seconds`` of wall time, then the
records it wrote are installed and those GEMMs are timed through the
program's dispatch.

The census is the exact ``(m, k, n, dtype)`` that dispatch sees when the
configuration's prefill is traced at the traffic's batch and prompt
length, each with its count per call.  Each run starts from an empty
journal, an empty measurement cache and no persistent compilation cache,
as the first tuning of a model does; all of it lives in a temporary
directory that the run removes.

A traced run profiles twice: the first seconds of the search (the
device's idle share while the tuner works) and the timing rounds (the
kernels' own device time).
"""

from __future__ import annotations

import dataclasses
import math
import os
import shutil
import tempfile
import threading

from .. import system
from ..harness import CompileClock, log, median, memory_peak_bytes, wall

__all__ = ["run", "readings"]

def _operands(key, launch):
    import jax
    import jax.numpy as jnp

    m, k, n = launch.dims
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (m, k), jnp.float32).astype(launch.dtype)
    b = (jax.random.normal(kb, (k, n), jnp.float32) / math.sqrt(k)).astype(launch.dtype)
    return a, b


def control_product(a, b):
    """The control: the float32 product of the operands rounded to float8
    e4m3 (one scale per operand)."""
    import jax
    import jax.numpy as jnp

    from ..reference import fp8

    return jnp.dot(fp8(a), fp8(b), precision=jax.lax.Precision.HIGHEST)


def rel_err(out, ref) -> float:
    import jax.numpy as jnp

    out = out.astype(jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))


def run(ctx, root: str, control: bool = False) -> dict:
    """One run; ``control`` also reads the control's number beside the
    check's (``readings``)."""
    import jax
    import jax.numpy as jnp

    from repro.core import Budget, TrialJournal, TuningRecords, TuningSession, Workload
    from repro.core.cost import XLATimedCost
    from repro.core.records import set_global_records
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    tr = dict(ctx.cell.traffic)
    cfg = system.arch_config(ctx.cell.config, ctx.rehearsal)
    p_len = min(tr["census_prompt_len"], 512) if ctx.rehearsal else tr["census_prompt_len"]
    census = [
        c for c in system.prefill_census(cfg, tr["census_batch"], p_len, p_len + 1)
        if c.kind == "gemm"
    ]
    log("census " + ", ".join(f"{c.dims}x{c.count}" for c in census))
    tmp = tempfile.mkdtemp(prefix="tune-window-")
    try:
        journal = TrialJournal(f"{tmp}/journal.jsonl")
        records = TuningRecords(f"{tmp}/records.json")
        made = []

        def cost_factory(space):
            cost = XLATimedCost(space, n_repeats=3, seed=ctx.seed % (2**31), cache_dir=None)
            made.append(cost)
            return cost

        session = TuningSession(records, cost_factory=cost_factory, seed=ctx.seed % (2**31),
                                journal=journal)
        workloads = [
            Workload("gemm", c.dims, dtype=c.dtype, label="m{}k{}n{}".format(*c.dims))
            for c in census
        ]
        clock = CompileClock()
        jnp.zeros(()).block_until_ready()  # the runtime is up before the window
        t0 = wall()
        setup_s = t0 - ctx.t_start
        log(f"set-up {setup_s:.2f}s; search of {ctx.seconds}s starts")
        traced = {}
        trace_dirs = {}
        if ctx.trace:
            trace_dirs = {k: os.path.join(ctx.trace_dir, k) for k in ("trace", "census_trace")}

        def stop_trace():
            traced["t1"] = wall()  # the trace holds nothing later than this
            jax.profiler.stop_trace()

        if ctx.trace:  # the first seconds of the search
            jax.profiler.start_trace(trace_dirs["trace"])
            timer = threading.Timer(tr["trace_seconds"], stop_trace)
            timer.start()
        with journal:
            report = session.tune_arch(
                workloads=workloads, tuner_name=tr["tuner"],
                budget=Budget(max_time_s=float(ctx.seconds)),
                n_workers=tr["workers"], executor=tr["executor"],
            )
        t1 = wall()
        if ctx.trace:
            timer.cancel()
            timer.join()
            if "t1" not in traced:
                stop_trace()
        window_s = t1 - t0
        stats = report.stats
        log(f"search {window_s:.2f}s: trials={report.total_trials} "
            f"failures={stats.n_failures} compiles={stats.n_compiles} "
            f"compile_s={stats.compile_s:.2f} backend_compiles={clock.n}")
        for label, res in sorted(report.results.items()):
            log(f"tuned {label} best={res.best_cost:.6e}s state={res.best_state} trials={res.n_trials}")

        set_global_records(records)
        ops.set_kernel_policy(dataclasses.replace(ops.kernel_policy(), cost_backend=made[0].name))
        ops.reset_dispatch_stats()
        key = jax.random.PRNGKey(ctx.seed % (2**31))
        fns, args, errs, ctrl = [], [], {}, {}
        for i, c in enumerate(census):
            before = ops.dispatch_stats().get("gemm", {})
            a, b = _operands(jax.random.fold_in(key, i), c)
            fn = jax.jit(lambda a, b: ops.gemm(a, b))
            out = fn(a, b)
            after = ops.dispatch_stats().get("gemm", {})
            src = {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}
            ref = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
            errs[c.dims] = rel_err(out, ref)
            if control:
                ctrl[c.dims] = rel_err(control_product(a, b), ref)
            log(f"dispatch m{c.dims[0]}k{c.dims[1]}n{c.dims[2]} {src} err {errs[c.dims]:.4e}")
            fns.append(fn)
            args.append((a, b))
            del out, ref
        peak_bytes = memory_peak_bytes()
        if ctx.trace:
            jax.profiler.start_trace(trace_dirs["census_trace"])
        rounds = []
        for _ in range(tr["timing_rounds"]):
            ts = wall()
            last = None
            for fn, (a, b), c in zip(fns, args, census):
                for _ in range(c.count):
                    last = fn(a, b)
                last.block_until_ready()
            rounds.append(wall() - ts)
        if ctx.trace:
            jax.profiler.stop_trace()
        tuned_s = median(rounds)
        log(f"census timing rounds {rounds}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    err = max(errs.values())
    limit = ctx.cell.limits["gemm_rel_err"]["limit"]
    result = {
        "correct": within(err, ctx.cell.limits),
        "attempted": int(report.total_trials),
        "failed": int(stats.n_failures),
        "e2e": {"tuned_gemm_ms": tuned_s * 1e3, "setup_s": setup_s},
        "run": {
            "census": {"tune": census}, "window_s": window_s, "tuned_s": tuned_s,
            "trials": report.total_trials, "compile_s": stats.compile_s,
            "traced": (t0, traced.get("t1", t1), None), "rounds": len(rounds),
            "trace_dirs": trace_dirs,
        },
        "memory_peak_bytes": peak_bytes,
        "checks": {"gemm_rel_err": {"value": err, "limit": limit}},
    }
    if control:
        result["control"] = max(ctrl.values())
    return result


def within(err: float, limits: dict) -> bool:
    """The check: the widest relative error against the cell's limit."""
    return bool(err <= limits["gemm_rel_err"]["limit"])


def readings(ctx, root: str) -> list:
    """The check's number on each seed of ``ctx.readings`` and the
    control's beside it, each with the verdict of the run's own check at
    the cell's limit: a whole run each, since the records come from the
    search."""
    out = []
    for seed in ctx.readings:
        res = run(dataclasses.replace(ctx, seed=seed, readings=()), root, control=True)
        program = res["checks"]["gemm_rel_err"]["value"]
        row = {"seed": seed, "program": program, "correct": within(program, ctx.cell.limits),
               "control": res["control"],
               "control_correct": within(res["control"], ctx.cell.limits)}
        log(f"reading {row}")
        out.append(row)
    return out
