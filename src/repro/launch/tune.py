"""Offline operator autotuning — the paper's technique as a first-class
framework feature, retargeted through the op registry
(``repro.core.ops``) so any registered operator tunes through the same
stack.

``--op gemm`` (default) extracts every distinct GEMM workload the arch
executes at the given shape (qkv / attn-out / ffn / experts / lm-head,
see ArchConfig.gemm_workloads); ``--op flash`` tunes the flash-attention
kernel's ``(block_q, block_kv)`` schedule for the arch's attention shape
(or a default 4k/128 shape when no arch is named).  Either way the
workloads fan through one shared measurement engine + budget
(``TuningSession.tune_arch``) and the best configs land in a
TuningRecords JSON that ``kernels/ops.py`` consults at trace time.

  # GEMM, as always
  python -m repro.launch.tune --arch yi-6b --shape train_4k \
      --tuner g-bfs --fraction 0.001 --records records/yi-6b.json \
      --workers 8 --executor process --warm-start

  # flash attention on crash-isolated process lanes
  python -m repro.launch.tune --op flash --tuner g-bfs --fraction 0.001 \
      --workers 2 --executor process

``--workers N`` measures candidate batches on N parallel engine lanes;
``--executor`` picks how those lanes run: ``sim`` (default) keeps the
bit-identical simulated clock, ``thread`` runs lanes on a thread pool,
and ``process`` ships each lane to a persistent worker process with a
per-lane timeout — a backend crash or hang costs one ``inf`` trial, not
the session.  ``--warm-start`` seeds each search from this workload's
previous best record (or the nearest previously-tuned shape of the same
op + dtype, transplanted).  Every measurement is journaled next to the
records file under op-scoped keys, so re-runs and overlapping shapes are
served from cache; the journal's append handle is closed when tuning
ends.

``--cost xla`` swaps the analytical oracle for :class:`XLATimedCost` —
real timed XLA programs built per op by the registry's ``timed_fn``, run
on JAX's device (the TPU on a chip host, in this process: process lanes
run JAX on the CPU), each search starting at the kernel's heuristic
blocks.  Its compile cost is kept off the hot path:
``--n-build-workers`` compiles candidate batches in parallel, and a
persistent compiled-program cache (``--compile-cache-dir``, default next
to the journal; content keys carry the op) lets re-runs and process-lane
workers skip compilation entirely.  ``--reload-every N`` merges sibling
engines' journal rows every N waves, so concurrent tuning runs sharing
one journal file serve each other's fresh measurements mid-search.

``--shard I/N`` turns those concurrent runs into one *partitioned*
search: each process measures only the candidates it owns (a stable
hash of the state key, seeded per workload), defers the rest to its
siblings, and when the searches finish the shards elect the merged best
(lowest journaled cost) into the shared records — see
``repro.core.shard``:

  python -m repro.launch.tune --op flash --records r.json \
      --shard 0/2 --reload-every 2 &
  python -m repro.launch.tune --op flash --records r.json \
      --shard 1/2 --reload-every 2
"""

from __future__ import annotations

import argparse
import contextlib
import sys
from typing import Optional

from repro.configs.registry import get_arch, get_shape
from repro.core import (
    Budget,
    TrialJournal,
    TuningRecords,
    TuningSession,
    Workload,
    get_op,
    op_names,
)
from repro.core.cost import XLATimedCost
from repro.core.cost.base import SleepingCost
from repro.core.executor import EXECUTORS
from repro.core.fault import RetryPolicy
from repro.core.records import compile_cache_dir_for
from repro.core.shard import parse_shard
from repro.core.snapshot import TuneCheckpointer, TuneInterrupted
from repro.utils.device import enable_compile_cache


def _pad_dim(x: int) -> int:
    """Round a workload dim up so its odd part is small.  The paper's
    action space only moves powers of two between loop factors, so a
    large odd part (e.g. 29568 = 2^7·231) pins a >=231-way grid split on
    that dim; the kernel pads instead — exactly what Pallas BlockSpec
    padding does on TPU.  Multiples of 2048 keep the odd part <= 15 for
    every assigned arch while wasting < 7% FLOPs."""
    if x >= 2048:
        return ((x + 2047) // 2048) * 2048
    if x >= 128:
        return ((x + 127) // 128) * 128
    return x


def workloads_for_arch(arch_name: str, shape_name: str,
                       max_tokens: int = 8192) -> list[Workload]:
    """Per-arch GEMM list.  Token count is clamped: tiling choices
    saturate well below the full 1M-token batch and the search space for
    the M dimension explodes otherwise (the records are keyed by shape,
    so serving different M re-tunes or falls back to the heuristic)."""
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    tokens = min(shape.global_batch * shape.seq_len, max_tokens)
    out = []
    for (m, k, n, tag) in cfg.gemm_workloads(1, tokens):
        m = _pad_dim(min(m, max_tokens))
        out.append(
            Workload(
                "gemm", (m, _pad_dim(k), _pad_dim(n)),
                dtype=cfg.compute_dtype, label=f"{arch_name}/{tag}",
            )
        )
    return out


def flash_workloads_for_arch(
    arch_name: Optional[str], shape_name: str, max_seq: int = 8192
) -> list[Workload]:
    """Flash-attention workload list: the arch's causal self-attention
    shape ``(seq, seq, head_dim)`` at the given training shape, or a
    default 4k/128 shape when no arch is named."""
    shape = get_shape(shape_name)
    seq = _pad_dim(min(shape.seq_len, max_seq))
    if arch_name is None:
        head_dim, dtype, label = 128, "bfloat16", f"flash/s{seq}"
    else:
        cfg = get_arch(arch_name)
        head_dim = cfg.resolved_head_dim
        dtype = cfg.compute_dtype
        label = f"{arch_name}/flash_s{seq}"
    return [Workload("flash", (seq, seq, head_dim), dtype=dtype, label=label)]


def main(argv: Optional[list[str]] = None):
    """Tune the workloads the arguments name; returns the
    :class:`~repro.core.session.ArchTuneReport`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", default="gemm",
                    help="which registered operator to tune (validated "
                         "against the op registry after parsing, so "
                         "late-registered ops work too)")
    ap.add_argument("--arch", default=None,
                    help="architecture whose workloads to tune "
                         "(required for --op gemm)")
    ap.add_argument("--shape", default="train_4k")
    from repro.core.tuners import TUNERS

    ap.add_argument("--tuner", default="g-bfs", choices=sorted(TUNERS))
    ap.add_argument("--fraction", type=float, default=0.001)
    ap.add_argument("--max-trials", type=int, default=None,
                    help="TOTAL trial pool shared across the workloads")
    ap.add_argument("--records", default="records/tuning.json")
    ap.add_argument("--noise", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=1,
                    help="parallel measurement lanes per engine")
    ap.add_argument("--executor", default="sim", choices=sorted(EXECUTORS),
                    help="how lanes run: simulated clock (bit-identical), "
                         "threads, or crash-isolated worker processes")
    ap.add_argument("--warm-start", action="store_true",
                    help="seed each search from the nearest tuned shape")
    ap.add_argument("--journal", default=None,
                    help="trial-journal path (default: <records>.journal.jsonl; "
                         "'none' disables the persistent cache)")
    ap.add_argument("--cost", default="analytical", choices=["analytical", "xla"],
                    help="cost oracle: the op's analytical TPU model, or real "
                         "XLA programs timed on JAX's device (XLATimedCost)")
    ap.add_argument("--n-build-workers", type=int, default=4,
                    help="parallel XLA compile threads per backend "
                         "(--cost xla only)")
    ap.add_argument("--compile-cache-dir", default=None,
                    help="persistent compiled-program cache directory "
                         "(--cost xla; default: <journal>.xlacache; "
                         "'none' disables the on-disk layer)")
    ap.add_argument("--reload-every", type=int, default=0,
                    help="merge sibling engines' journal rows every N "
                         "measurement waves (mid-search cache sharing "
                         "between concurrent runs; 0 disables)")
    ap.add_argument("--analyze", default="off", choices=["off", "warn", "prune"],
                    help="static schedule pre-filter (repro.core.analysis): "
                         "'warn' classifies candidates and counts advisory "
                         "flags, 'prune' rejects provably-bad ones before "
                         "they occupy a measurement lane")
    ap.add_argument("--learned-filter", default="off", choices=["off", "on"],
                    help="learned proposal filter (repro.core.learn): score "
                         "each wave's candidates with a journal-trained "
                         "rank model and really measure only the "
                         "predicted-best fraction; skipped candidates are "
                         "journaled as {'c': null, 'pred': score} "
                         "provenance rows ('off' is bit-identical to the "
                         "historical engine)")
    ap.add_argument("--filter-keep", type=float, default=0.5,
                    help="fraction of each wave's candidates the learned "
                         "filter really measures (at least 1 per wave)")
    ap.add_argument("--filter-retrain-every", type=int, default=8,
                    help="retrain the filter's model from fresh journal "
                         "rows every N measurement waves")
    ap.add_argument("--filter-min-rows", type=int, default=32,
                    help="journal rows (same op/dtype/fingerprint) required "
                         "before the filter starts dropping candidates; "
                         "below it the engine measures everything")
    ap.add_argument("--retries", type=int, default=1,
                    help="max measurement attempts per candidate: transient "
                         "lane failures (crash/timeout/spawn/corrupt) are "
                         "re-queued into later waves with exponential "
                         "backoff instead of surfacing inf to the tuner "
                         "(1 = no retry)")
    ap.add_argument("--retry-backoff", type=float, default=0.25,
                    help="base backoff seconds between retry attempts "
                         "(doubled per attempt, deterministic jitter)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="crash-safe session snapshot directory (default: "
                         "<records>.tunestate; 'none' disables snapshots)")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="snapshot the search every N tuner rounds")
    ap.add_argument("--resume", action="store_true",
                    help="restore each workload's search from its latest "
                         "snapshot (finished workloads are served from "
                         "their done marker); measurements replay from "
                         "the journal, so the resumed search reaches the "
                         "same best state as an uninterrupted run")
    ap.add_argument("--shard", default="0/1",
                    help="run as shard I/N of an N-way sharded search: N "
                         "processes sharing one --journal each measure only "
                         "the candidates they own (stable hash of the state "
                         "key, seeded per workload), defer the rest to their "
                         "siblings, and elect the merged best into the "
                         "records when done (default 0/1: unsharded, "
                         "bit-identical to the plain engine)")
    ap.add_argument("--shard-wait", type=float, default=60.0,
                    help="seconds to wait for sibling shards' done markers "
                         "before electing over whatever reported")
    ap.add_argument("--measure-delay", type=float, default=0.0,
                    help="seconds of real lane occupancy added per "
                         "measurement (SleepingCost wrapper) — gives "
                         "interrupt/kill tests a window to land in")
    args = ap.parse_args(argv)

    try:
        shard = parse_shard(args.shard)
    except ValueError as e:
        ap.error(str(e))
    if shard.enabled and (args.journal == "none"):
        ap.error("--shard needs a shared --journal (it is the shards' "
                 "only communication channel)")

    if args.op not in op_names():
        # a clear CLI error instead of a deep registry KeyError later
        ap.error(
            f"unknown op {args.op!r}: not in the operator registry "
            f"(registered ops: {', '.join(sorted(op_names()))})"
        )

    if args.op == "gemm":
        if args.arch is None:
            ap.error("--op gemm needs --arch (whose GEMMs to tune)")
        workloads = workloads_for_arch(args.arch, args.shape)
    elif args.op == "flash":
        workloads = flash_workloads_for_arch(args.arch, args.shape)
    else:  # a future registered op: tune its default workload list
        ap.error(f"--op {args.op} has no workload lister wired up yet")

    journal_path = args.journal
    if journal_path is None:
        journal_path = args.records + ".journal.jsonl"
    journal = None if journal_path == "none" else TrialJournal(journal_path)

    if args.cost == "xla":
        enable_compile_cache()
        cache_dir = args.compile_cache_dir
        if cache_dir is None:
            cache_dir = (
                compile_cache_dir_for(journal_path)
                if journal_path != "none"
                else None
            )
        elif cache_dir == "none":
            cache_dir = None

        def cost_factory(space):
            # float32 operands (the CPU has no native bf16 pipeline
            # worth timing); seed fixes operand contents
            return XLATimedCost(
                space,
                n_repeats=3,
                seed=args.seed,
                n_build_workers=args.n_build_workers,
                cache_dir=cache_dir,
            )
    else:
        def cost_factory(space):
            # the op's own analytical oracle, resolved via the registry
            return get_op(space.op).analytical_cost(
                space, n_repeats=3, noise_sigma=args.noise, seed=args.seed
            )

    if args.measure_delay > 0:
        inner_factory = cost_factory

        def cost_factory(space, _inner=inner_factory):
            # real lane occupancy per measurement: the kill window that
            # interrupt/resume smoke tests land a SIGTERM inside
            return SleepingCost(_inner(space), delay_s=args.measure_delay)

    retry = (
        RetryPolicy(
            max_attempts=args.retries,
            backoff_s=args.retry_backoff,
            seed=args.seed,
        )
        if args.retries > 1
        else None
    )
    checkpoint_dir = args.checkpoint_dir
    if checkpoint_dir is None:
        checkpoint_dir = args.records + ".tunestate"
    checkpointer = (
        None
        if checkpoint_dir == "none"
        else TuneCheckpointer(checkpoint_dir, every_rounds=args.checkpoint_every)
    )
    if checkpointer is not None:
        checkpointer.install_signal_handlers()

    records = TuningRecords(args.records)
    session = TuningSession(
        records,
        cost_factory=cost_factory,
        seed=args.seed,
        journal=journal,
    )
    budget = Budget(max_fraction=args.fraction, max_trials=args.max_trials)
    try:
        with journal if journal is not None else contextlib.nullcontext():
            report = session.tune_arch(
                workloads=workloads,
                tuner_name=args.tuner,
                budget=budget,
                n_workers=args.workers,
                warm_start=args.warm_start,
                executor=args.executor,
                reload_every=args.reload_every,
                analyze=args.analyze,
                retry=retry,
                checkpointer=checkpointer,
                resume=args.resume,
                learned_filter=args.learned_filter,
                filter_keep=args.filter_keep,
                filter_retrain_every=args.filter_retrain_every,
                filter_min_rows=args.filter_min_rows,
                shard=shard,
                shard_wait_s=args.shard_wait,
            )
    except TuneInterrupted as e:
        print(
            f"[tune] interrupted at a round boundary ({e}); snapshot flushed "
            f"to {checkpoint_dir} — rerun with --resume to continue"
        )
        sys.exit(130)
    print(
        f"[tune] wrote {len(records)} records to {args.records} "
        f"(op={args.op} workers={report.n_workers} executor={args.executor} "
        f"cache_hit={report.stats.cache_hit_rate():.2f} "
        f"compile_cache_hit={report.stats.compile_cache_hit_rate():.2f} "
        f"compiles={report.stats.n_compiles} "
        f"trials_avoided={report.stats.trials_avoided} "
        f"trials_avoided_learned={report.stats.trials_avoided_learned} "
        f"learned_retrains={report.stats.n_learned_retrains} "
        f"deferred_to_sibling={report.stats.n_deferred_to_sibling} "
        f"served_by_sibling={report.stats.n_served_by_sibling} "
        f"lane_failures={report.stats.n_failures})"
    )
    return report


if __name__ == "__main__":
    main()
