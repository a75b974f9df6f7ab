"""Offline serving in a closed loop: static batches through
``ServeEngine.generate`` until ``--seconds`` have passed.

Every request of a call is submitted when the call starts and is done
when its tokens reach the host, so a request's latency is its call's.
The window starts with the first call and ends when the last call that
started inside ``--seconds`` returns.  Traffic parameters (batch, prompt
and generated lengths, buckets) come from the traffic file; the prompts
from the seed.
"""

from __future__ import annotations

import gc
import math
import os

import numpy as np

from .. import spec, system, weights
from ..harness import CompileClock, log, memory_peak_bytes, percentile, wall

__all__ = ["run", "readings"]


def _traffic(ctx) -> dict:
    tr = dict(ctx.cell.traffic)
    if ctx.rehearsal:  # tiny sizes for the CPU: shapes only, no speed
        # 512 still runs flash at the reduced threshold and its blocks
        tr["prompt_len"] = min(tr["prompt_len"], 512)
        tr["prompt_buckets"] = [min(b, 512) for b in tr["prompt_buckets"]]
        tr["gen_tokens"] = min(tr["gen_tokens"], 8)
        tr["gen_buckets"] = [min(b, 8) for b in tr["gen_buckets"]]
    return tr


def _engine(cfg, params, tr, root):
    from repro.launch.serve import ServeEngine

    return ServeEngine(
        cfg, params, max_batch=tr["batch"],
        max_len=max(tr["prompt_buckets"]) + max(tr["gen_buckets"]),
        prompt_buckets=tr["prompt_buckets"], gen_buckets=tr["gen_buckets"],
        cache_dir=os.path.join(root, ".jax_cache", "serve_aot"),
    )


def _prompts(rng, tr, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, (tr["batch"], tr["prompt_len"]), dtype=np.int32)


def _check_sample(rng, n_requests: int, k: int) -> np.ndarray:
    """``k`` of the finished requests, drawn from the seed; all requests
    of a mix have one length, so every draw holds the longest."""
    return np.sort(rng.choice(n_requests, size=min(k, n_requests), replace=False))


def reference_rows(tokens: int, requests: int) -> int:
    """Requests per reference call: about 8k tokens at a time."""
    return max(1, min(requests, 8192 // tokens))


def _gaps(model, params, prompts, served, control: bool = False) -> np.ndarray:
    """Reference gaps of the served tokens, by the reference of the stated
    family (``families/<family>.py``), or its control's, a few rows per
    reference call (one shape: the last chunk is filled up with its first
    row)."""
    family = spec.load_family(model["family"])
    gaps = family.control_gaps if control else family.served_gaps
    rows = reference_rows(prompts.shape[1] + served.shape[1] - 1, len(prompts))
    out = []
    for i in range(0, len(prompts), rows):
        p, s = prompts[i:i + rows], served[i:i + rows]
        n = len(p)
        if n < rows:
            p = np.concatenate([p, np.repeat(p[:1], rows - n, 0)])
            s = np.concatenate([s, np.repeat(s[:1], rows - n, 0)])
        out.append(np.asarray(gaps(model, params, p, s))[:n])
    return np.concatenate(out)


def _setup(ctx, seed: int):
    cfg = system.arch_config(ctx.cell.config, ctx.rehearsal)
    model = system.stated_model(ctx.cell.config, cfg, ctx.rehearsal)
    tr = _traffic(ctx)
    params = weights.make(system.abstract_params(cfg), seed)
    return cfg, model, tr, params


def run(ctx, root: str) -> dict:
    import jax

    cfg, model, tr, params = _setup(ctx, ctx.seed)
    jax.block_until_ready(params)
    log(f"weights made at {wall() - ctx.t_start:.2f}s")
    engine = _engine(cfg, params, tr, root)
    log(f"engine ready at {wall() - ctx.t_start:.2f}s: {engine.cache_report()}")
    vocab = cfg.vocab_size
    warm = np.random.default_rng([ctx.seed, 0])
    engine.generate(_prompts(warm, tr, vocab), tr["gen_tokens"])  # first run of each program
    census = None
    if ctx.trace:
        max_len = engine.max_len
        census = {
            "prefill": system.prefill_census(cfg, tr["batch"], max(tr["prompt_buckets"]), max_len),
            "decode": system.decode_census(cfg, tr["batch"], max_len, max(tr["gen_buckets"])),
        }
    clock = CompileClock()
    n_pref, n_dec = len(engine.stats["prefill_s"]), len(engine.stats["decode_s"])
    compiles0 = engine.cache_report()["compiles"]

    rng = np.random.default_rng([ctx.seed, 1])
    calls, prompts_all, served_all = [], [], []
    traced = None
    t0 = wall()
    setup_s = t0 - ctx.t_start
    log(f"set-up {setup_s:.2f}s; window of {ctx.seconds}s starts")
    while wall() - t0 < ctx.seconds:
        prompts = _prompts(rng, tr, vocab)
        i = len(calls)
        if ctx.trace and i == 0:
            jax.profiler.start_trace(ctx.trace_dir)
        ts = wall()
        out = engine.generate(prompts, tr["gen_tokens"])
        te = wall()
        if ctx.trace and i == tr["trace_calls"] - 1:
            jax.profiler.stop_trace()
            traced = (calls[0]["t0"] if calls else ts, te, i + 1)
        calls.append({"t0": ts, "t1": te, "prompt_tokens": int(prompts.size),
                      "gen_tokens": int(out.size)})
        prompts_all.append(prompts)
        served_all.append(np.asarray(out))
    t1 = calls[-1]["t1"]
    if ctx.trace and traced is None:  # the window ended inside the traced calls
        jax.profiler.stop_trace()
        traced = (calls[0]["t0"], t1, len(calls))
    window_s = t1 - t0
    compiles = engine.cache_report()["compiles"] - compiles0 + clock.n
    prefill_s = engine.stats["prefill_s"][n_pref:]
    decode_s = engine.stats["decode_s"][n_dec:]
    peak_bytes = memory_peak_bytes()
    log(f"window {window_s:.3f}s, {len(calls)} calls; compiles in window {compiles}; "
        f"peak {peak_bytes} bytes")
    log("calls (start s, wall s, prefill s, decode s): " + " ".join(
        f"{c['t0'] - t0:.3f}/{c['t1'] - c['t0']:.4f}/{p:.4f}/{d:.4f}"
        for c, p, d in zip(calls, prefill_s, decode_s)))

    prompts_all = np.concatenate(prompts_all)
    served_all = np.concatenate(served_all)
    bad = ~((served_all >= 0) & (served_all < vocab)).all(axis=1)
    bad |= served_all.shape[1] != tr["gen_tokens"]
    del engine
    gc.collect()
    pick = _check_sample(np.random.default_rng([ctx.seed, 2]), len(prompts_all), tr["check_requests"])
    tc = wall()
    gaps = _gaps(model, params, prompts_all[pick], served_all[pick])
    gap_max = float(gaps.max())
    log(f"reference over {len(pick)} requests ({gaps.size} served tokens) took {wall() - tc:.2f}s; "
        f"gap max {gap_max:.6g} mean {float(gaps.mean()):.6g} share>0 {float((gaps > 0).mean()):.4f}")

    limit = ctx.cell.limits["logit_gap"]["limit"]
    n_req = len(prompts_all)
    lat = [c["t1"] - c["t0"] for c in calls for _ in range(tr["batch"])]
    e2e = {
        "prompt_tok_s": sum(c["prompt_tokens"] for c in calls) / window_s,
        "gen_tok_s": sum(c["gen_tokens"] for c in calls) / window_s,
        "request_p95_s": percentile(lat, 95),
        "setup_s": setup_s,
    }
    run_rec = {
        "model": model, "traffic": tr, "calls": calls, "window_s": window_s,
        "prefill_s": prefill_s, "decode_s": decode_s, "compiles_window": compiles,
        "census": census, "traced": traced,
    }
    return {
        "correct": within(gap_max, ctx.cell.limits) and not bad.any(),
        "attempted": n_req,
        "failed": int(bad.sum()),
        "e2e": e2e,
        "run": run_rec,
        "memory_peak_bytes": peak_bytes,
        "checks": {"logit_gap": {"value": gap_max, "limit": limit}},
    }


def within(gap: float, limits: dict) -> bool:
    """The check: the widest logit gap against the cell's limit."""
    return bool(gap <= limits["logit_gap"]["limit"])


def readings(ctx, root: str) -> list:
    """The check's number on each seed of ``ctx.readings`` and the
    control's beside it, each with the verdict of the run's own check at
    the cell's limit: the program serves as many requests as a run
    compares, then the reference reads both on the same sampled
    requests."""
    out = []
    for seed in ctx.readings:
        cfg, model, tr, params = _setup(ctx, seed)
        engine = _engine(cfg, params, tr, root)
        rng = np.random.default_rng([seed, 1])
        n_calls = math.ceil(tr["check_requests"] / tr["batch"])
        prompts, served = [], []
        for _ in range(n_calls):
            p = _prompts(rng, tr, cfg.vocab_size)
            served.append(np.asarray(engine.generate(p, tr["gen_tokens"])))
            prompts.append(p)
        del engine
        gc.collect()
        prompts, served = np.concatenate(prompts), np.concatenate(served)
        in_vocab = bool(((served >= 0) & (served < cfg.vocab_size)).all())
        pick = _check_sample(np.random.default_rng([seed, 2]), len(prompts), tr["check_requests"])
        program = float(_gaps(model, params, prompts[pick], served[pick]).max())
        control = float(_gaps(model, params, prompts[pick], served[pick], control=True).max())
        row = {"seed": seed, "program": program,
               "correct": within(program, ctx.cell.limits) and in_vocab,
               "control": control, "control_correct": within(control, ctx.cell.limits)}
        log(f"reading {row}")
        out.append(row)
        del params
        gc.collect()
    return out
