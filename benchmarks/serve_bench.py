"""Serving benchmark — the tune→serve loop as numbers
(``BENCH_serve.json``).

MaxText-style serving protocol over :class:`repro.launch.serve.ServeEngine`,
timing **prefill** and **autoregressive decode** separately:

  * **tune** — measure a handful of flash-attention schedules for the
    bench's prompt shape with :class:`PallasInterpretCost` (the actual
    Pallas kernel, interpret mode) and write the best into
    :class:`TuningRecords` — the same records file `launch/tune.py`
    produces;
  * **heuristic engine** — no records: ``attention_dispatch`` falls back
    to its built-in blocks.  Timed generate calls give tok/s and
    per-stage latency;
  * **tuned engine** — records installed: the trace picks up the tuned
    ``(block_q, block_kv)`` (asserted via the trace-time dispatch
    counters in the payload) and must serve at least as fast;
  * **warm restart** — a second engine over the same persistent
    executable cache directory must report **zero fresh compiles**
    (``warm_restart.zero_fresh_compiles``) — the AOT pre-warm replays
    prior work from disk.  Note its dispatch counters stay zero too:
    nothing is re-traced;
  * **stream** — an open-loop synthetic request stream (varied prompt
    lengths, exponential inter-arrivals) replayed through bucketed
    continuous batching; reports tokens/sec plus p50/p95/p99 latency per
    stage and per request.  This phase runs the default (pure-XLA)
    policy, so its tok/s is the stable metric the ``--diff`` regression
    gate tracks (kernel-interpret timings are too host-sensitive to
    gate on).

Usage::

  python -m benchmarks.serve_bench --quick     # CI smoke + artifact
  python -m benchmarks.run --only serve        # via the harness
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.registry import get_arch
from repro.core.flash_space import FlashAttnConfigSpace
from repro.core.records import (
    TuningRecords,
    set_global_records,
    workload_key_for,
)
from repro.kernels.ops import (
    KernelPolicy,
    dispatch_stats,
    reset_dispatch_stats,
    set_kernel_policy,
)
from repro.launch.serve import ServeEngine
from repro.models.api import Model

#: records namespace for this bench — costs come from the interpret-mode
#: Pallas kernel, so label them as such (dispatch consults the namespace
#: named by KernelPolicy.cost_backend)
BACKEND = "pallas_interpret_timed"


def _percentiles(xs) -> dict:
    a = np.asarray(xs, float)
    return {
        "p50": round(float(np.percentile(a, 50)), 5),
        "p95": round(float(np.percentile(a, 95)), 5),
        "p99": round(float(np.percentile(a, 99)), 5),
    }


def _tune_flash(space: FlashAttnConfigSpace, records: TuningRecords,
                n_candidates: int, repeats: int, cache_dir: str) -> dict:
    """Measure ``n_candidates`` schedules with the real (interpret-mode)
    kernel and keep-best into ``records`` under this bench's namespace."""
    from repro.core.cost.measured import PallasInterpretCost

    cost = PallasInterpretCost(
        space, n_repeats=repeats, cache_dir=cache_dir
    )
    cands = [s for s in space.enumerate() if space.is_legitimate(s)]
    # deterministic spread across the enumeration order
    if len(cands) > n_candidates:
        step = len(cands) / n_candidates
        cands = [cands[int(i * step)] for i in range(n_candidates)]
    best_s, best_c = None, math.inf
    for s in cands:
        c = cost.cost(s)
        if c < best_c:
            best_s, best_c = s, c
    key = workload_key_for("flash", space.dims, "float32", BACKEND)
    records.update(key, best_s, best_c, tuner="serve-bench-sweep",
                   n_trials=len(cands))
    return {
        "op": "flash",
        "dims": list(space.dims),
        "n_candidates": len(cands),
        "best_blocks": [best_s.block_q, best_s.block_kv],
        "best_cost_s": round(best_c, 5),
        **{f"cache_{k}": v for k, v in cost.compile_stats().items()},
    }


def _timed_engine(engine: ServeEngine, prompts: np.ndarray, gen: int,
                  repeats: int) -> dict:
    """Warm up once, then ``repeats`` timed generates; medians of the
    per-stage stage timings (prefill is where tuned flash blocks act —
    decode re-attends a single query row and is schedule-independent)."""
    b, p = prompts.shape
    engine.generate(prompts, gen)  # warmup: executables + buffers live
    pre, dec = [], []
    for _ in range(repeats):
        engine.generate(prompts, gen)
        pre.append(engine.stats["prefill_s"][-1])
        dec.append(engine.stats["decode_s"][-1])
    pre_s, dec_s = float(np.median(pre)), float(np.median(dec))
    return {
        "prefill_s": round(pre_s, 5),
        "decode_s": round(dec_s, 5),
        "prefill_tok_s": round(b * p / pre_s, 2),
        "decode_tok_s": round(b * gen / dec_s, 2),
        "tok_s": round(b * (p + gen) / (pre_s + dec_s), 2),
    }


def _stream_requests(n: int, rate_rps: float, len_lo: int, len_hi: int,
                     seed: int) -> list[tuple[float, int]]:
    """Open-loop arrivals: (arrival_time_s, prompt_len) with exponential
    inter-arrivals at ``rate_rps`` and uniform prompt lengths."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, n)
    t = np.cumsum(gaps)
    lens = rng.integers(len_lo, len_hi + 1, n)
    return list(zip(t.tolist(), lens.tolist()))


def _replay_stream(engine: ServeEngine, arrivals, gen: int) -> dict:
    """Discrete-event replay of continuous batching at batch
    granularity: requests are served in arrival order, greedily batched
    while they map to the same prompt bucket (ragged rows ride along
    via ``prompt_lens``); service times are the engine's measured
    wall-clock stage timings."""
    from repro.launch.serve import _bucket_for

    i, sim_t = 0, 0.0
    pre_lat, dec_lat, req_lat = [], [], []
    n_batches = 0
    while i < len(arrivals):
        t0, l0 = arrivals[i]
        bucket = _bucket_for(l0, engine.prompt_buckets)
        batch = [arrivals[i]]
        i += 1
        while (
            i < len(arrivals)
            and len(batch) < engine.max_batch
            and _bucket_for(arrivals[i][1], engine.prompt_buckets) == bucket
        ):
            batch.append(arrivals[i])
            i += 1
        sim_t = max(sim_t, batch[-1][0])  # open loop: wait for arrivals
        lens = np.array([l for _, l in batch], np.int32)
        prompts = np.zeros((len(batch), int(lens.max())), np.int32)
        for r, (_, ln) in enumerate(batch):
            prompts[r, :ln] = (np.arange(ln) * 7 + r) % engine.cfg.vocab_size
        engine.generate(prompts, gen, prompt_lens=lens)
        pre_s = engine.stats["prefill_s"][-1]
        dec_s = engine.stats["decode_s"][-1]
        sim_t += pre_s + dec_s
        n_batches += 1
        pre_lat.append(pre_s)
        dec_lat.append(dec_s)
        req_lat.extend(sim_t - t for t, _ in batch)
    span = sim_t - arrivals[0][0]
    service_s = sum(pre_lat) + sum(dec_lat)
    total_tokens = len(arrivals) * gen
    return {
        "n_requests": len(arrivals),
        "n_batches": n_batches,
        # open-loop delivered rate (arrival-gap dominated at low rates)
        "tok_s": round(total_tokens / span, 2),
        # saturated engine throughput: tokens per second of *service*
        # time — the stable metric the --diff regression gate tracks
        "service_tok_s": round(total_tokens / service_s, 2),
        "latency_s": {
            "prefill": _percentiles(pre_lat),
            "decode": _percentiles(dec_lat),
            "request": _percentiles(req_lat),
        },
        "bucket_misses": engine.stats["bucket_misses"],
    }


def main(
    quick: bool = False,
    out: str = "BENCH_serve.json",
    arch: str = "yi-6b",
    seed: int = 0,
    cache_root: str | None = None,
) -> dict:
    import jax

    seq = 256 if quick else 512          # > reduced attn_chunk_threshold (64)
    gen = 4 if quick else 8
    batch = 2
    repeats = 2 if quick else 3
    n_candidates = 4 if quick else 8
    n_stream = 12 if quick else 32

    cfg = get_arch(arch).reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    hd = cfg.resolved_head_dim

    own_root = cache_root is None
    root = cache_root or tempfile.mkdtemp(prefix="serve-bench-")
    d_tune = os.path.join(root, "tune")
    d_heur = os.path.join(root, "engine-heur")
    d_tuned = os.path.join(root, "engine-tuned")
    d_stream = os.path.join(root, "engine-stream")

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    max_len = seq + gen

    result: dict = {
        "bench": "serve",
        "quick": quick,
        "arch": arch,
        "shape": {"batch": batch, "seq": seq, "gen": gen, "head_dim": hd},
        "host": {"cpus": os.cpu_count(), "jax": jax.__version__},
    }
    saved_policy = KernelPolicy()
    try:
        # ---- tune: measure flash schedules, keep-best into records ---------
        records = TuningRecords(os.path.join(root, "records.json"))
        space = FlashAttnConfigSpace(seq, seq, hd)
        result["tune"] = _tune_flash(
            space, records, n_candidates, repeats, d_tune
        )

        # flash-only Pallas policy: the bench isolates attention dispatch
        # (projection GEMMs stay on XLA either way); the kernel runs
        # interpreted or compiled as the backend's rule says
        pol = KernelPolicy(
            use_pallas=True, cost_backend=BACKEND, pallas_ops=("flash",),
        )

        # ---- heuristic engine: no records ----------------------------------
        set_global_records(TuningRecords())
        set_kernel_policy(pol)
        reset_dispatch_stats()
        heur = ServeEngine(
            cfg, params, max_batch=batch, max_len=max_len,
            prompt_buckets=[seq], gen_buckets=[gen], cache_dir=d_heur,
        )
        heur_block = _timed_engine(heur, prompts, gen, repeats)
        heur_block["dispatch"] = dispatch_stats().get("flash", {})
        heur_block["cache"] = heur.cache_report()
        result.setdefault("engines", {})["heuristic"] = heur_block

        # ---- tuned engine: records drive the traced blocks -----------------
        set_global_records(records)
        set_kernel_policy(pol)  # also drops the dispatch memo
        reset_dispatch_stats()
        tuned = ServeEngine(
            cfg, params, max_batch=batch, max_len=max_len,
            prompt_buckets=[seq], gen_buckets=[gen], cache_dir=d_tuned,
        )
        tuned_block = _timed_engine(tuned, prompts, gen, repeats)
        tuned_block["dispatch"] = dispatch_stats().get("flash", {})
        tuned_block["cache"] = tuned.cache_report()
        result["engines"]["tuned"] = tuned_block
        result["tuned_record_dispatched"] = (
            tuned_block["dispatch"].get("records", 0) > 0
        )
        result["tuned_ge_heuristic_tok_s"] = (
            tuned_block["tok_s"] >= heur_block["tok_s"]
        )

        # ---- warm restart: same cache dir, zero fresh compiles -------------
        warm = ServeEngine(
            cfg, params, max_batch=batch, max_len=max_len,
            prompt_buckets=[seq], gen_buckets=[gen], cache_dir=d_tuned,
        )
        warm.generate(prompts, gen)
        wrep = warm.cache_report()
        result["warm_restart"] = {
            **wrep,
            "zero_fresh_compiles": wrep["compiles"] == 0,
        }

        # ---- open-loop stream under the default (pure-XLA) policy ----------
        set_kernel_policy(KernelPolicy())
        set_global_records(TuningRecords())
        stream_buckets = [16, 32, 64]
        stream = ServeEngine(
            cfg, params, max_batch=4, max_len=64 + gen,
            prompt_buckets=stream_buckets, gen_buckets=[gen],
            cache_dir=d_stream,
        )
        arrivals = _stream_requests(
            n_stream, rate_rps=4.0, len_lo=4, len_hi=64, seed=seed
        )
        # replay 1: latency percentiles (includes first-touch buffer
        # warmup, like a freshly restarted server); replays 2-4: median
        # service throughput over warm executables for the --diff gate
        result["stream"] = _replay_stream(stream, arrivals, gen)
        warm_tps = [
            _replay_stream(stream, arrivals, gen)["service_tok_s"]
            for _ in range(3)
        ]
        result["stream"]["service_tok_s"] = float(np.median(warm_tps))
        result["stream"]["buckets"] = stream_buckets
        result["stream"]["cache"] = stream.cache_report()
    finally:
        set_kernel_policy(saved_policy)
        set_global_records(TuningRecords())
        if own_root:
            shutil.rmtree(root, ignore_errors=True)

    with open(out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
    print(f"serve,tuned_blocks,{result['tune']['best_blocks']}")
    print(f"serve,heuristic_tok_s,{result['engines']['heuristic']['tok_s']}")
    print(f"serve,tuned_tok_s,{result['engines']['tuned']['tok_s']}")
    print(f"serve,tuned_record_dispatched,{result['tuned_record_dispatched']}")
    print(f"serve,warm_restart_compiles,{result['warm_restart']['compiles']}")
    print(f"serve,stream_tok_s,{result['stream']['tok_s']}")
    print(f"serve,stream_service_tok_s,{result['stream']['service_tok_s']}")
    print(f"serve,artifact,{out}")
    if not result["tuned_ge_heuristic_tok_s"]:
        print(
            "serve,WARNING,tuned engine slower than heuristic "
            f"({result['engines']['tuned']['tok_s']} < "
            f"{result['engines']['heuristic']['tok_s']} tok/s)",
            file=sys.stderr,
        )
    if not result["warm_restart"]["zero_fresh_compiles"]:
        print(
            "serve,WARNING,warm restart recompiled "
            f"{result['warm_restart']['compiles']} executables",
            file=sys.stderr,
        )
    return result


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced protocol")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-root", default=None,
                    help="persist executable caches here (default: tmp)")
    a = ap.parse_args()
    main(quick=a.quick, out=a.out, arch=a.arch, seed=a.seed,
         cache_root=a.cache_root)
