"""Serving launcher: bucketed, AOT pre-warmed batched prefill + decode.

  python -m repro.launch.serve --arch yi-6b --reduced --requests 8 \
      --prompt-len 32 --gen 16 --cache-dir /tmp/serve.cache

The engine closes the tune->serve loop from the execution side:

* **Shape buckets** — incoming prompts are right-padded into a fixed set
  of prompt-length buckets (attention families only; SSM/hybrid state
  cannot tolerate pad tokens, so those run exact lengths), and decode
  runs a ``lax.scan`` loop compiled per generation-length bucket.
  Request-length jitter therefore never triggers a recompile: every
  request reuses one of a small, enumerable set of executables.
* **AOT pre-warm** — each (prefill, decode) executable is resolved
  through the persistent :class:`~repro.core.cost.measured.ExecutableCache`
  (the same two-layer memory+disk cache the measurement engine uses), so
  a warm restart deserializes prior compiles instead of redoing them;
  ``cache_report()`` exposes the compile/disk-hit counters the serving
  bench asserts on.  Cache keys fold in the kernel policy and the
  content of the global tuning records, because tuned records change the
  *traced program* (flash block sizes, GEMM tiles) — a stale executable
  can never be served for a different schedule.
* **Record-aware dispatch** — the traced prefill goes through
  ``models/common.attention_dispatch`` and ``kernels/ops.gemm``, so
  tuned schedules from `launch/tune.py` drive the actual kernels.
* **Single host transfer** — the decode loop accumulates tokens
  on-device inside the scan and transfers once per generate call
  (the per-token ``np.asarray`` sync of the naive engine is gone).

Correctness under padding: per-sequence seed logits come from each
prompt's own last real position (``Model.prefill(last_idx=...)``), pad
K/V rows are masked out of every decode step, and each sequence's
decode positions continue from its own true length
(``cache["valid_len"]``/``cache["prefill_len"]``, see
``models/common.decode_attention`` and ``transformer.decode_step``) —
so for dense/vlm/encdec a bucket-padded generation is bit-identical to
the exact-shape run, with the pad K/V slots simply dead weight in the
cache.  MoE is near-identical rather than exact: pad tokens contend for
expert capacity during prefill (GShard-style capacity buffers are a
function of every token in the fixed-shape batch), the standard
trade-off of any static-shape MoE server.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.core.cost.measured import ExecutableCache
from repro.core.records import global_records
from repro.kernels.ops import dispatch_stats, kernel_policy
from repro.models.api import Model
from repro.utils.device import device_kind, enable_compile_cache
from repro.utils.spans import span

__all__ = ["ServeEngine"]

#: families whose causal-attention masking makes right-padded prompts safe
_PADDABLE = ("dense", "vlm", "moe", "encdec")


def _bucket_for(n: int, buckets: Optional[Sequence[int]]) -> int:
    """Smallest configured bucket that fits ``n``; ``n`` itself when no
    bucket does (exact-shape compile, counted as a bucket miss)."""
    if buckets:
        for b in buckets:
            if b >= n:
                return b
    return n


class ServeEngine:
    """Bucketed batched engine: fixed max batch, greedy sampling, AOT
    executables resolved through a persistent cache (see module doc)."""

    def __init__(
        self,
        cfg,
        params,
        max_batch: int,
        max_len: int,
        prompt_buckets: Optional[Sequence[int]] = None,
        gen_buckets: Optional[Sequence[int]] = None,
        cache_dir: Optional[str] = None,
        prewarm: Optional[bool] = None,
        cache_capacity: int = 64,
    ):
        self.cfg = cfg
        self.model = Model(cfg)
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.pad_prompts = cfg.family in _PADDABLE
        self.prompt_buckets = sorted(prompt_buckets) if prompt_buckets else None
        self.gen_buckets = sorted(gen_buckets) if gen_buckets else None
        if self.prompt_buckets:
            need = self.prompt_buckets[-1] + (
                self.gen_buckets[-1] if self.gen_buckets else 0
            )
            if need > max_len:
                raise ValueError(
                    f"largest prompt bucket + largest gen bucket = {need} "
                    f"exceeds max_len={max_len}; the KV cache cannot hold a "
                    f"full-bucket request"
                )
        self.cache = ExecutableCache(capacity=cache_capacity, cache_dir=cache_dir)
        self._abs_params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params
        )
        self._fp = self._fingerprint()
        self.prewarm_s = 0.0
        self.stats = {
            "prefill_s": [],      # per generate() call
            "decode_s": [],       # per generate() call
            "prefill_buckets": {},  # bucket -> call count
            "bucket_misses": 0,   # prompts no configured bucket could hold
        }
        if prewarm is None:
            prewarm = bool(self.prompt_buckets or self.gen_buckets)
        if prewarm:
            self.prewarm()

    # -- executable resolution -------------------------------------------------
    def _fingerprint(self) -> str:
        """Everything that determines the traced program besides the
        input shapes: the arch config, the kernel policy, and the tuned
        records the trace-time dispatch will consult."""
        pol = kernel_policy()
        rec = global_records()
        rec_view = {k: rec.lookup(k).get("state") for k in sorted(rec.keys())}
        raw = json.dumps(
            {
                "cfg": dataclasses.asdict(self.cfg),
                "policy": dataclasses.asdict(pol),
                "records": rec_view,
            },
            sort_keys=True,
            default=str,
        )
        return hashlib.sha256(raw.encode()).hexdigest()[:20]

    def _raw_key(self, kind: str, dim: int) -> str:
        import jaxlib

        return (
            f"serve/{kind}/{self._fp}/b{self.max_batch}/maxlen{self.max_len}"
            f"/{kind[0]}{dim}/pad{int(self.pad_prompts)}/{device_kind()}"
            f"/jax{jax.__version__}/jaxlib{jaxlib.__version__}"
        )

    def _resolve(self, raw_key: str, build):
        """Memory LRU -> persistent disk layer -> fresh compile (then
        persisted for the next engine/restart)."""
        ckey = hashlib.sha256(raw_key.encode()).hexdigest()[:40]
        fn = self.cache.get_mem(ckey)
        if fn is not None:
            return fn
        fn = self.cache.get_disk(ckey)
        if fn is None:
            with span("serve.compile") as sp:
                fn = build()
            self.cache.count_compile(sp.seconds)
            self.cache.put_disk(ckey, fn)
        self.cache.put_mem(ckey, fn)
        return fn

    def _abstract_batch(self, p: int) -> dict:
        batch = {"tokens": jax.ShapeDtypeStruct((self.max_batch, p), jnp.int32)}
        if self.cfg.family == "encdec":
            batch["enc_frames"] = jax.ShapeDtypeStruct(
                (self.max_batch, self.cfg.encoder_len, self.cfg.d_model),
                jnp.dtype(self.cfg.compute_dtype),
            )
        return batch

    def _prefill_exec(self, p: int):
        def build():
            if self.pad_prompts:
                fn = lambda prm, b, last: self.model.prefill(
                    prm, b, self.max_len, last_idx=last
                )
                args = (
                    self._abs_params,
                    self._abstract_batch(p),
                    jax.ShapeDtypeStruct((self.max_batch,), jnp.int32),
                )
            else:
                fn = lambda prm, b: self.model.prefill(prm, b, self.max_len)
                args = (self._abs_params, self._abstract_batch(p))
            return jax.jit(fn).lower(*args).compile()

        return self._resolve(self._raw_key("prefill", p), build)

    def _abstract_cache(self) -> dict:
        cache = self.model.abstract_cache(self.max_batch, self.max_len)
        if self.pad_prompts:
            cache["valid_len"] = jax.ShapeDtypeStruct((self.max_batch,), jnp.int32)
            cache["prefill_len"] = jax.ShapeDtypeStruct((), jnp.int32)
        return cache

    def _decode_exec(self, g: int):
        def build():
            v = self.cfg.vocab_size

            def fn(prm, cache, logits):
                def step(carry, _):
                    cache, tok = carry
                    lg, cache = self.model.decode_step(prm, cache, tok)
                    nxt = jnp.argmax(lg[:, -1, :v], -1)[:, None].astype(jnp.int32)
                    return (cache, nxt), tok[:, 0]

                tok0 = jnp.argmax(logits[:, -1, :v], -1)[:, None].astype(jnp.int32)
                (_, _), toks = jax.lax.scan(step, (cache, tok0), None, length=g)
                return toks.T  # (B, g), accumulated on-device

            b_logits = jax.ShapeDtypeStruct(
                (self.max_batch, 1, self.cfg.padded_vocab), jnp.float32
            )
            return (
                jax.jit(fn)
                .lower(self._abs_params, self._abstract_cache(), b_logits)
                .compile()
            )

        return self._resolve(self._raw_key("decode", g), build)

    # -- warm path --------------------------------------------------------------
    def prewarm(self) -> None:
        """Resolve every configured (prefill, decode) bucket executable
        now — from disk on a warm restart (zero fresh compiles), from a
        compile on the first ever run."""
        with span("serve.prewarm") as sp:
            for p in self.prompt_buckets or ():
                self._prefill_exec(p)
            for g in self.gen_buckets or ():
                self._decode_exec(g)
        self.prewarm_s = sp.seconds

    def cache_report(self) -> dict:
        """The executable cache's counters, and ``stream``: the GEMM
        dispatches traced in this process that read a layer of stacked
        weights in place (``dispatch_stats``; an executable loaded from
        disk traces nothing)."""
        rep = dict(self.cache.stats())
        rep["stream"] = dispatch_stats().get("gemm", {}).get("stream", 0)
        rep["prewarm_s"] = self.prewarm_s
        rep["bucket_misses"] = self.stats["bucket_misses"]
        return rep

    # -- serving ----------------------------------------------------------------
    def _stage(self, prompts, prompt_lens):
        """Pad the prompts into their bucket and put them on the device:
        ``(bucket, the prefill executable's arguments, the cache entries
        that padding adds)``."""
        prompts = np.asarray(prompts, np.int32)
        b, p = prompts.shape
        assert b <= self.max_batch
        lens = (
            np.full((b,), p, np.int32)
            if prompt_lens is None
            else np.asarray(prompt_lens, np.int32)
        )

        if self.pad_prompts:
            bucket = _bucket_for(p, self.prompt_buckets)
            if self.prompt_buckets and bucket == p and p not in self.prompt_buckets:
                self.stats["bucket_misses"] += 1
        else:
            bucket = p  # exact shapes: SSM/hybrid state admits no pads
            if (lens != p).any():
                raise ValueError(
                    f"family {self.cfg.family} cannot serve ragged prompts"
                )
        assert bucket <= self.max_len

        toks = np.zeros((self.max_batch, bucket), np.int32)
        toks[:b, :p] = prompts
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "encdec":
            batch["enc_frames"] = jnp.zeros(
                (self.max_batch, self.cfg.encoder_len, self.cfg.d_model),
                jnp.dtype(self.cfg.compute_dtype),
            )
        if not self.pad_prompts:
            return bucket, (self.params, batch), {}
        true_len = np.full((self.max_batch,), bucket, np.int32)
        true_len[:b] = lens
        last_idx = jnp.asarray(true_len - 1, jnp.int32)
        pad_state = {
            "valid_len": jnp.asarray(true_len, jnp.int32),
            "prefill_len": jnp.asarray(bucket, jnp.int32),
        }
        return bucket, (self.params, batch, last_idx), pad_state

    def prefill(
        self,
        prompts: np.ndarray,
        prompt_lens: Optional[np.ndarray] = None,
    ):
        """Run prompts (B, P) int32 through the bucketed prefill
        executable; returns ``(logits, cache, bucket)`` with logits
        (max_batch, 1, padded_vocab) f32 taken at each row's last real
        position (see :meth:`generate` for ``prompt_lens``)."""
        with span("serve.stage"):
            bucket, args, pad_state = self._stage(prompts, prompt_lens)
        with span("serve.launch"):
            logits, cache = self._prefill_exec(bucket)(*args)
        cache.update(pad_state)
        return logits, cache, bucket

    def generate(
        self,
        prompts: np.ndarray,
        gen_tokens: int,
        prompt_lens: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """prompts: (B, P) int32; returns (B, gen_tokens).

        ``prompt_lens`` (B,) marks each row's true length when rows are
        already padded (the open-loop bench batches ragged requests);
        defaults to full-width prompts."""
        b = np.shape(prompts)[0]
        with span("serve.generate", batch=b) as call:
            with span("serve.prefill") as sp:
                logits, cache, bucket = self.prefill(prompts, prompt_lens)
                with span("serve.wait"):
                    logits.block_until_ready()
            self.stats["prefill_s"].append(sp.seconds)
            self.stats["prefill_buckets"][bucket] = (
                self.stats["prefill_buckets"].get(bucket, 0) + 1
            )

            g = _bucket_for(gen_tokens, self.gen_buckets)
            call.attrs.update(prompt_bucket=bucket, gen_bucket=g)
            with span("serve.decode") as sp:
                with span("serve.launch"):
                    toks_dev = self._decode_exec(g)(self.params, cache, logits)
                with span("serve.wait"):
                    toks_dev.block_until_ready()
                with span("serve.fetch"):
                    out = np.asarray(toks_dev)  # the one host transfer
            self.stats["decode_s"].append(sp.seconds)
        return out[:b, :gen_tokens]


def main(argv: Optional[Sequence[str]] = None) -> dict:
    """Serve ``--requests`` random prompts once and print a summary.
    Returns the engine, the prompts and the generated tokens for callers
    that check them (``chip_smoke.py``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-dir", default=None,
                    help="persistent AOT executable cache directory")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated prompt-length buckets to pre-warm")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    buckets = (
        [int(x) for x in args.buckets.split(",")] if args.buckets else None
    )
    engine = ServeEngine(
        cfg, params, max_batch=args.requests,
        max_len=max([args.prompt_len] + (buckets or [])) + args.gen,
        prompt_buckets=buckets, gen_buckets=[args.gen] if buckets else None,
        cache_dir=args.cache_dir,
    )
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size, (args.requests, args.prompt_len)).astype(np.int32)
    t0 = time.monotonic()
    out = engine.generate(prompts, args.gen)
    dt = time.monotonic() - t0
    total_new = args.requests * args.gen
    rep = engine.cache_report()
    print(
        f"[serve] {args.arch}: {args.requests} requests x {args.gen} tokens "
        f"in {dt:.2f}s = {total_new/dt:.1f} tok/s (greedy); "
        f"compiles={rep['compiles']} disk_hits={rep['disk_hits']} "
        f"prewarm={rep['prewarm_s']:.2f}s; sample: {out[0][:8].tolist()}"
    )
    return {"engine": engine, "prompts": prompts, "tokens": out}


if __name__ == "__main__":
    main()
