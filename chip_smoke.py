"""On-chip smoke test of the tune -> serve path on one TPU, at yi-6b's
published widths (random weights from a seed).

    python chip_smoke.py

Runs in one process (a chip belongs to one process at a time), phase by
phase; any failed phase raises and the script exits non-zero:

  device   JAX's first device must be a TPU; anything else exits 2
  kernels  the heuristic Pallas GEMM for yi-6b's five GEMMs at M = 8192
           (prefill) and M = 2 (decode), and flash attention at B 2,
           S 4096, H 32, KV 4, hd 128, compiled natively
           (``tpu_custom_call`` in the compiled program) and compared
           with an f32 reference
  tune     ``repro.launch.tune --arch yi-6b --cost xla --executor sim``:
           schedules timed on the chip; no lane failure, a finite best
           for every workload
  serve    ``repro.launch.serve --arch yi-6b`` at full width: 2 requests
           in the 4096-token bucket (above the flash threshold), 16
           generated tokens, GEMM and flash dispatched to Pallas; the
           prefill logits are compared with the same engine on XLA

It reads nothing that git ignores: the tune phase writes its records and
journal into a fresh ``chiprun_out/smoke_tune``.  JAX's persistent
compilation cache is where ``JAX_COMPILATION_CACHE_DIR`` says, else
``.jax_cache`` in this checkout.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "yi-6b"
PREFILL_TOKENS = 8192  # the tune CLI's token clamp: M of the tuned GEMMs
DECODE_BATCH = 2
FLASH_SHAPE = (2, 4096)  # (batch, seq) at the arch's heads
TUNE_TRIALS = 10
SERVE_ARGS = [
    "--arch", ARCH, "--requests", "2", "--prompt-len", "4096",
    "--buckets", "4096", "--gen", "16",
]
#: bf16 outputs: rounding alone is 2^-9 of each value; the kernels
#: accumulate in f32, so 1e-2 of the reference's largest magnitude
#: leaves room for summation order and nothing for a wrong result
KERNEL_TOL = 1e-2
#: Pallas and XLA prefill round differently in bf16 at each of the 32
#: layers; the logits must still agree to 5e-2 of their largest magnitude
LOGITS_TOL = 5e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def device_phase():
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        print(
            f"[smoke] JAX found no TPU (platform {d.platform!r}): this smoke "
            "measures the chip and has nothing to say without one",
            file=sys.stderr,
        )
        sys.exit(2)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _rel_err(out, ref) -> float:
    import jax.numpy as jnp

    out = out.astype(jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))


def _native(fn, *args):
    """Compile ``fn`` for the device; refuse a program without a Pallas
    kernel in it.  Returns (compiled, seconds to compile or to load)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError("compiled program holds no tpu_custom_call")
    return compiled, dt


def kernels_phase(cfg, tokens, flash_shape, seed: int = 0) -> None:
    """Each heuristic GEMM of ``cfg`` at every M in ``tokens``, and flash
    attention at ``flash_shape``, against f32 references."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import interpret_default
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.gemm import default_config, gemm_pallas
    from repro.models.common import chunked_causal_attention

    interp = interpret_default()  # False on the chip
    hi = jax.lax.Precision.HIGHEST
    key = jax.random.PRNGKey(seed)
    for t in tokens:
        for m, k, n, tag in cfg.gemm_workloads(1, t):
            kc = default_config(m, k, n)
            if not kc.tpu_aligned(m, k, n):
                raise RuntimeError(f"{tag} m={m}: heuristic {kc} misses the tiling")
            key, ka, kb = jax.random.split(key, 3)
            a = jax.random.normal(ka, (m, k), jnp.bfloat16)
            b = (jax.random.normal(kb, (k, n)) / math.sqrt(k)).astype(jnp.bfloat16)
            fn, dt = _native(
                lambda a, b, c=kc: gemm_pallas(a, b, c, interpret=interp), a, b
            )
            ref = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32), precision=hi)
            err = _rel_err(fn(a, b), ref)
            log(f"kernel gemm {tag} m={m} k={k} n={n} blocks="
                f"({kc.block_m},{kc.block_k},{kc.block_n}) compile={dt:.2f}s "
                f"max_rel_err={err:.3e} tol={KERNEL_TOL}")
            if not err <= KERNEL_TOL:
                raise RuntimeError(f"gemm {tag} m={m} off its reference")
    b, s = flash_shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, kvh, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, kvh, hd), jnp.bfloat16)
    fn, dt = _native(
        lambda q, k, v: flash_attention(q, k, v, interpret=interp), q, k, v
    )
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(chunked_causal_attention)(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
        )
    err = _rel_err(fn(q, k, v), ref)
    log(f"kernel flash b={b} s={s} h={h} kv={kvh} hd={hd} compile={dt:.2f}s "
        f"max_rel_err={err:.3e} tol={KERNEL_TOL}")
    if not err <= KERNEL_TOL:
        raise RuntimeError("flash attention off its reference")


def tune_phase(argv) -> None:
    """The tune CLI with ``argv``; every workload must end with a finite
    best and no lane may fail."""
    from repro.launch import tune

    try:
        report = tune.main(argv)
    except SystemExit as e:  # argparse errors and interrupts
        raise RuntimeError(f"tune exited with code {e.code}") from e
    if report.stats.n_failures:
        raise RuntimeError(f"tune: lane_failures={report.stats.n_failures}")
    for label, res in sorted(report.results.items()):
        log(f"tune {label} best={res.best_cost:.6e}s state={res.best_state} "
            f"trials={res.n_trials}")
        if not math.isfinite(res.best_cost):
            raise RuntimeError(f"tune: no finite best for {label}")
    if not report.results:
        raise RuntimeError("tune: no workload was tuned")
    log(f"tune lane_failures=0 compiles={report.stats.n_compiles} "
        f"compile_s={report.stats.compile_s:.2f}")


def serve_phase(argv, logits_tol: float = LOGITS_TOL) -> None:
    """The serve CLI with ``argv``, then its prefill again under XLA
    dispatch."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.launch import serve
    from repro.utils import spans

    ops.reset_dispatch_stats()
    res = serve.main(argv)
    engine, prompts, tokens = res["engine"], res["prompts"], res["tokens"]
    cfg = engine.cfg
    call = [r for r in spans.snapshot() if r.name == "serve.generate"][-1].attrs
    rep = engine.cache_report()
    log(f"serve tokens={tokens.tolist()}")
    log(f"serve prefill_s={engine.stats['prefill_s'][-1]:.4f} "
        f"decode_s={engine.stats['decode_s'][-1]:.4f} "
        f"prompt_bucket={call['prompt_bucket']} gen_bucket={call['gen_bucket']} "
        f"compiles={rep['compiles']} compile_s={rep['compile_s']:.2f}")
    stats = ops.dispatch_stats()
    for op, d in sorted(stats.items()):
        log(f"serve dispatch {op} " + " ".join(f"{k}={v}" for k, v in sorted(d.items())))
    for op in ("gemm", "flash"):
        d = stats.get(op, {})
        if not sum(d.get(s, 0) for s in ("records", "heuristic", "explicit")):
            raise RuntimeError(f"serve: {op} was never dispatched to Pallas")
    if tokens.shape != (len(prompts), call["gen_bucket"]) or not (
        (tokens >= 0) & (tokens < cfg.vocab_size)
    ).all():
        raise RuntimeError(f"serve: bad tokens {tokens.shape}")

    pallas_logits = engine.prefill(prompts)[0]
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=False))
    try:
        xla = serve.ServeEngine(
            cfg, engine.params, max_batch=engine.max_batch,
            max_len=engine.max_len, prompt_buckets=engine.prompt_buckets,
            prewarm=False,
        )
        xla_logits = xla.prefill(prompts)[0]
    finally:
        ops.set_kernel_policy(ops.KernelPolicy())
    v = cfg.vocab_size
    p, x = pallas_logits[..., :v], xla_logits[..., :v]
    if not bool(jnp.isfinite(p).all()):
        raise RuntimeError("serve: non-finite prefill logits")
    err = _rel_err(p, x)
    same_top = float(jnp.mean(jnp.argmax(p, -1) == jnp.argmax(x, -1)))
    log(f"serve prefill logits pallas vs xla max_rel_err={err:.3e} "
        f"tol={logits_tol} argmax_agree={same_top:.2f}")
    if not err <= logits_tol:
        raise RuntimeError("serve: Pallas prefill logits off the XLA path")


def _compile_clock() -> dict:
    """Seconds of XLA backend compilation in this process so far, from
    JAX's own monitoring events: a program loaded from the persistent
    compilation cache adds nothing."""
    import jax

    clock = {"s": 0.0}

    def listener(event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            clock["s"] += seconds

    jax.monitoring.register_event_duration_secs_listener(listener)
    return clock


def main() -> None:
    device = device_phase()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs.registry import get_arch
    from repro.utils.device import enable_compile_cache

    enable_compile_cache()
    clock = _compile_clock()
    cfg = get_arch(ARCH)
    tune_dir = os.path.join(ROOT, "chiprun_out", "smoke_tune")
    shutil.rmtree(tune_dir, ignore_errors=True)
    phases = [
        ("kernels", lambda: kernels_phase(
            cfg, (PREFILL_TOKENS, DECODE_BATCH), FLASH_SHAPE)),
        ("tune", lambda: tune_phase([
            "--arch", ARCH, "--cost", "xla", "--executor", "sim",
            "--max-trials", str(TUNE_TRIALS),
            "--records", os.path.join(tune_dir, "records.json"),
            "--compile-cache-dir", "none", "--checkpoint-dir", "none",
        ])),
        ("serve", lambda: serve_phase(SERVE_ARGS)),
    ]
    for name, run in phases:
        t0, c0 = time.perf_counter(), clock["s"]
        run()
        gc.collect()
        log(f"phase {name} ok in {time.perf_counter() - t0:.1f}s, "
            f"backend compile {clock['s'] - c0:.2f}s")
    log(f"backend compile total {clock['s']:.2f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
