"""Serving engine + tune launcher smoke tests."""

import numpy as np

import jax

import pytest

pytest.importorskip(
    "repro.dist", reason="repro.dist subsystem not present in this tree yet"
)

from repro.configs.registry import get_arch
from repro.core.flash_space import FlashScheduleState
from repro.core.records import (
    TuningRecords,
    set_global_records,
    workload_key_for,
)
from repro.kernels.ops import (
    KernelPolicy,
    dispatch_stats,
    flash_schedule,
    kernel_policy,
    reset_dispatch_stats,
    set_kernel_policy,
)
from repro.launch.serve import ServeEngine
from repro.launch.tune import workloads_for_arch
from repro.models.api import Model


@pytest.fixture
def clean_dispatch():
    """Isolate the process-global kernel policy + records the dispatch
    layer consults."""
    saved = kernel_policy()
    yield
    set_kernel_policy(saved)
    set_global_records(TuningRecords())
    reset_dispatch_stats()


def _reduced_model(arch="yi-6b", seed=0):
    cfg = get_arch(arch).reduced()
    model = Model(cfg)
    return cfg, model.init_params(jax.random.PRNGKey(seed))


def test_serve_engine_generates():
    cfg, params = _reduced_model()
    engine = ServeEngine(cfg, params, max_batch=2, max_len=24)
    prompts = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    out = engine.generate(prompts, gen_tokens=4)
    assert out.shape == (2, 4)
    assert (out >= 0).all() and (out < cfg.vocab_size).all()
    # greedy decoding is deterministic
    out2 = engine.generate(prompts, gen_tokens=4)
    np.testing.assert_array_equal(out, out2)


def test_tuned_record_drives_flash_dispatch(clean_dispatch):
    """A flash schedule tuned into records changes the blocks the traced
    attention actually uses — the tune→serve loop, observed via the
    trace-time dispatch counters."""
    cfg, params = _reduced_model()
    seq, hd = 128, cfg.resolved_head_dim  # > reduced attn_chunk_threshold
    pol = KernelPolicy(use_pallas=True, interpret=True, pallas_ops=("flash",))

    # no record: the built-in gate (256/512 divisibility) fails at 128,
    # so dispatch falls back to XLA
    set_global_records(TuningRecords())
    set_kernel_policy(pol)
    assert flash_schedule(seq, seq, hd, "float32") is None
    reset_dispatch_stats()
    eng = ServeEngine(cfg, params, max_batch=1, max_len=seq + 4,
                      prompt_buckets=[seq], gen_buckets=[4])
    assert dispatch_stats()["flash"]["xla"] >= 1
    prompts = (np.arange(seq, dtype=np.int32)[None] * 3) % cfg.vocab_size
    base = eng.generate(prompts, gen_tokens=4)

    # tune a record for this workload: the trace now picks up its blocks
    rec = TuningRecords()
    state = FlashScheduleState(q=(4, 32), kv=(2, 64))  # blocks (32, 64)
    rec.update(
        workload_key_for("flash", (seq, seq, hd), "float32",
                         pol.cost_backend),
        state, cost=1.0, tuner="test", n_trials=1,
    )
    set_global_records(rec)
    assert flash_schedule(seq, seq, hd, "float32") == (32, 64)
    reset_dispatch_stats()
    tuned = ServeEngine(cfg, params, max_batch=1, max_len=seq + 4,
                        prompt_buckets=[seq], gen_buckets=[4])
    stats = dispatch_stats()["flash"]
    assert stats["records"] >= 1 and stats["xla"] == 0
    # the tuned kernel is a numerics-equivalent schedule change
    np.testing.assert_array_equal(tuned.generate(prompts, 4), base)


def test_serve_prewarm_zero_compiles_on_restart(tmp_path, clean_dispatch):
    """A restarted ServeEngine over the same persistent cache directory
    rehydrates every bucket executable from disk: zero fresh compiles."""
    cfg, params = _reduced_model()
    mk = lambda: ServeEngine(
        cfg, params, max_batch=2, max_len=40,
        prompt_buckets=[8, 16], gen_buckets=[4],
        cache_dir=str(tmp_path / "aot"),
    )
    cold = mk()
    r = cold.cache_report()
    assert r["compiles"] == 3 and r["disk_hits"] == 0  # 2 prefill + 1 decode
    prompts = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    out_cold = cold.generate(prompts, gen_tokens=4)

    warm = mk()
    r = warm.cache_report()
    assert r["compiles"] == 0 and r["disk_hits"] == 3
    np.testing.assert_array_equal(warm.generate(prompts, 4), out_cold)
    assert warm.cache_report()["compiles"] == 0  # serving stayed warm


def test_cache_report_counts_stream_dispatches(clean_dispatch):
    """With Pallas on, building the decode executable traces the seven
    per-layer projections of a dense decoder as in-place reads of the
    stacked weights: ``cache_report()["stream"]`` counts them beside
    ``compiles``; with Pallas off there are none."""
    cfg, params = _reduced_model()
    set_kernel_policy(KernelPolicy(use_pallas=True, interpret=True))
    reset_dispatch_stats()
    eng = ServeEngine(cfg, params, max_batch=2, max_len=24, gen_buckets=[4])
    rep = eng.cache_report()
    assert rep["compiles"] == 1 and rep["stream"] == 7, rep
    set_kernel_policy(KernelPolicy(use_pallas=False))
    reset_dispatch_stats()
    eng = ServeEngine(cfg, params, max_batch=2, max_len=24, gen_buckets=[4])
    assert eng.cache_report()["stream"] == 0


def test_bucket_padding_avoids_recompiles(clean_dispatch):
    """Prompt-length jitter inside a bucket never compiles a new
    executable, and padded generation matches the exact-shape run."""
    cfg, params = _reduced_model()
    eng = ServeEngine(cfg, params, max_batch=2, max_len=40,
                      prompt_buckets=[16], gen_buckets=[4])
    assert eng.cache_report()["compiles"] == 2
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    outs = {}
    for n in (5, 9, 13, 16):
        outs[n] = eng.generate(prompts[:, :n], gen_tokens=4)
    assert eng.cache_report()["compiles"] == 2  # no new executables
    assert eng.stats["prefill_buckets"] == {16: 4}

    # padded rows decode bit-identically to their exact-shape runs:
    # per-sequence last_idx logits, pad K/V masking, per-sequence
    # decode positions (see launch/serve.py module doc)
    exact = ServeEngine(cfg, params, max_batch=2, max_len=40)
    for n in (5, 9, 13):
        np.testing.assert_array_equal(
            outs[n], exact.generate(prompts[:, :n], gen_tokens=4)
        )

    # ragged rows ride in one batch via prompt_lens
    rag = np.zeros((2, 16), np.int32)
    rag[0, :5] = prompts[0, :5]
    rag[1, :13] = prompts[1, :13]
    br = eng.generate(rag, gen_tokens=4, prompt_lens=np.array([5, 13]))
    np.testing.assert_array_equal(br[0], outs[5][0])
    np.testing.assert_array_equal(br[1], outs[13][1])
    assert eng.cache_report()["compiles"] == 2


def test_workloads_for_arch_cover_block_gemms():
    wls = workloads_for_arch("qwen2-72b", "train_4k")
    labels = {w.label.split("/")[-1] for w in wls}
    assert {"qkv", "attn_out", "ffn_in", "ffn_out", "lm_head"} <= labels
    for w in wls:
        assert w.m > 0 and w.k > 0 and w.n > 0

    moe_wls = workloads_for_arch("qwen3-moe-235b-a22b", "train_4k")
    moe_labels = {w.label.split("/")[-1] for w in moe_wls}
    assert {"expert_in", "expert_out", "router"} <= moe_labels

    ssm_wls = workloads_for_arch("mamba2-130m", "train_4k")
    ssm_labels = {w.label.split("/")[-1] for w in ssm_wls}
    assert {"ssm_in", "ssm_out"} <= ssm_labels


def test_tune_cli_writes_records(tmp_path):
    import sys

    from repro.launch import tune as tune_mod

    argv = sys.argv
    sys.argv = [
        "tune", "--arch", "whisper-tiny", "--shape", "train_4k",
        "--tuner", "g-bfs", "--max-trials", "40", "--fraction", "1.0",
        "--records", str(tmp_path / "r.json"),
    ]
    try:
        tune_mod.main()
    finally:
        sys.argv = argv
    from repro.core.records import TuningRecords

    rec = TuningRecords(str(tmp_path / "r.json"))
    assert len(rec) >= 3


def test_executable_keys_carry_the_device_kind(monkeypatch):
    """A serve executable compiled for one device kind is never served
    on another."""
    import repro.launch.serve as serve

    cfg, params = _reduced_model()
    engine = ServeEngine(cfg, params, max_batch=2, max_len=24)
    cpu_key = engine._raw_key("prefill", 16)
    assert "/cpu/" in cpu_key
    monkeypatch.setattr(serve, "device_kind", lambda: "TPU v5 lite")
    assert engine._raw_key("prefill", 16) != cpu_key


def test_prefill_seeds_generate():
    """``prefill`` is the first half of ``generate``: its logits pick the
    first generated token."""
    cfg, params = _reduced_model()
    engine = ServeEngine(cfg, params, max_batch=2, max_len=24)
    prompts = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    logits, cache, bucket = engine.prefill(prompts)
    assert logits.shape == (2, 1, cfg.padded_vocab) and bucket == 8
    first = np.argmax(np.asarray(logits)[:, -1, : cfg.vocab_size], -1)
    np.testing.assert_array_equal(engine.generate(prompts, 4)[:, 0], first)
