"""Operations, bytes and the kernel census, against hand counts."""

import dataclasses

import pytest

from benchmarks.chip import counts
from benchmarks.chip.peaks import Peak

V5E = Peak(bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9, source="test")


def test_gemm_counts_by_hand():
    # yi-6b's FFN up-projection in a 2 x 4096 prefill
    m, k, n = 8192, 4096, 11008
    assert counts.gemm_flops(m, k, n) == 2 * 8192 * 4096 * 11008 == 738734374912
    assert counts.gemm_bytes(m, k, n) == (8192 * 4096 + 4096 * 11008 + 8192 * 11008) * 2
    assert counts.gemm_bytes(m, k, n) == 337641472
    t = counts.roofline_s(counts.gemm_flops(m, k, n), counts.gemm_bytes(m, k, n), V5E)
    assert t == pytest.approx(738734374912 / 197e12)  # compute-bound
    # decode at M = 32 is bound by reading the weight
    t = counts.roofline_s(counts.gemm_flops(32, 4096, 11008), counts.gemm_bytes(32, 4096, 11008), V5E)
    assert t == pytest.approx((32 * 4096 + 4096 * 11008 + 32 * 11008) * 2 / 819e9)


def test_flash_counts_by_hand():
    # B 2, S 4096, H 32, KV 4, hd 128: causal pairs 4096 * 4097 / 2
    assert counts.flash_flops(2, 32, 4096, 128) == 4 * 2 * 32 * 128 * 4096 * 4097 / 2
    assert counts.flash_flops(2, 32, 4096, 128) == 274945015808
    q_o = 2 * 2 * 4096 * 32 * 128 * 2
    k_v = 2 * 2 * 4096 * 4 * 128 * 2
    assert counts.flash_bytes(2, 32, 4, 4096, 128) == q_o + k_v == 150994944
    launch = counts.Launch("flash", (2, 32, 4, 4096, 128), "bfloat16", 32)
    assert counts.launch_roofline_s(launch, V5E) == pytest.approx(274945015808 / 197e12)


def test_prefill_flops_is_matmuls_plus_attention():
    model = {"d_model": 8, "head_dim": 2, "n_heads": 4, "n_kv_heads": 2, "mlp": "swiglu",
             "d_ff": 16, "n_layers": 3, "vocab_size": 10}
    layer = 8 * (4 + 4) * 2 + 4 * 2 * 8 + 3 * 8 * 16  # qkv + o + gated MLP
    assert counts.matmul_params(model) == (layer, 80)
    want = 2 * 2 * 5 * 3 * layer + 2 * 2 * 80 + 3 * 4 * 2 * 4 * 2 * 5 * 6 / 2
    assert counts.prefill_flops(model, 2, 5) == want
    steps = 2 * (3 * layer + 80) * 2 * 3 + 4 * 2 * 3 * 4 * 2 * ((5 + 1) + (5 + 2) + (5 + 3))
    assert counts.decode_flops(model, 2, 5, 3) == steps


def test_census_of_a_reduced_prefill():
    """Every projection of every layer, the head at the last position,
    and one flash launch per layer, as dispatch traces them."""
    from repro.configs.registry import get_arch
    from repro.kernels import ops

    from benchmarks.chip import system

    cfg = get_arch("yi-6b").reduced(param_dtype="bfloat16", compute_dtype="bfloat16")
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True))
    try:
        got = {(c.kind, c.dims): c.count for c in system.prefill_census(cfg, 2, 512, 520)}
        dec = system.decode_census(cfg, 2, 520, 3)
    finally:
        ops.set_kernel_policy(ops.KernelPolicy())
    d, hd, h, kv, f, m = 64, 16, 4, 2, 128, 1024
    assert got == {
        ("gemm", (m, d, h * hd)): 4,  # wq and wo (64 -> 64) in 2 layers
        ("gemm", (m, d, kv * hd)): 4,  # wk, wv
        ("gemm", (m, d, f)): 4,  # wi, wg
        ("gemm", (m, f, d)): 2,
        ("gemm", (2, d, cfg.padded_vocab)): 1,  # the head, at the last positions
        ("flash", (2, h, kv, 512, hd)): 2,
    }
    assert {dataclasses.replace(c, count=0) for c in dec} >= {
        counts.Launch("gemm", (2, d, cfg.padded_vocab), "bfloat16", 0)}
    assert all(c.count % 3 == 0 for c in dec)


def test_census_of_a_reduced_decode_step():
    """Every projection of every layer, read in place from the stacked
    weights with a scalar-prefetched layer index, is a ``gemm`` launch of
    ``(m, k, n)``: as many a step as dispatch streamed at trace time
    times the layers, and nothing is left under ``other``."""
    from repro.configs.registry import get_arch
    from repro.kernels import ops

    from benchmarks.chip import system

    cfg = get_arch("yi-6b").reduced(param_dtype="bfloat16", compute_dtype="bfloat16")
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True))
    ops.reset_dispatch_stats()
    try:
        got = {(c.kind, c.dims): c.count for c in system.decode_census(cfg, 2, 520, 1)}
        stream = ops.dispatch_stats()["gemm"]["stream"]
        prefill = {(c.kind, c.dims) for c in system.prefill_census(cfg, 2, 512, 520)}
    finally:
        ops.set_kernel_policy(ops.KernelPolicy())
    d, hd, h, kv, f, m, layers = 64, 16, 4, 2, 128, 2, cfg.n_layers
    head = ("gemm", (m, d, cfg.padded_vocab))
    assert got == {
        ("gemm", (m, d, h * hd)): 2 * layers,  # wq and wo
        ("gemm", (m, d, kv * hd)): 2 * layers,  # wk, wv
        ("gemm", (m, d, f)): 2 * layers,  # wi, wg
        ("gemm", (m, f, d)): layers,
        head: 1,
    }
    assert stream == 7
    assert sum(n for k, n in got.items() if k != head) == stream * layers
    # the head alone is in both: which program run is the prefill's, by the
    # GEMMs only its census has, does not change with the decode launches
    assert set(got) & prefill == {head}


def test_unknown_kernel_is_other_by_its_name():
    """A ``pallas_call`` whose name no file in ``launches/`` gives is
    filed under ``other``, keyed by that name and its operand shapes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    fn = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                        name="mystery_kernel", interpret=True)
    got = counts.census(jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8, 128), jnp.float32)))
    assert got == [counts.Launch("other", ("mystery_kernel", ((8, 128),)), "float32", 1)]


def test_unknown_kernel_kind_names_the_file_to_add():
    from benchmarks.chip.readers import kernel_roofline

    with pytest.raises(LookupError, match="benchmarks/chip/launches/grouped_gemm.py"):
        kernel_roofline({}, "grouped_gemm", "decode")
    with pytest.raises(LookupError, match="benchmarks/chip/launches/grouped_gemm.py"):
        counts.launch_roofline_s(counts.Launch("grouped_gemm", (4, 8, 16), "bfloat16", 1), V5E)


def test_stacked_gemm_counts_one_layer_of_the_stack():
    """The layer-indexed launch reads one K x N slab of its (L, K, N)
    stack: the same operations and least bytes as a 2-D GEMM."""
    from benchmarks.chip import spec

    gemm = spec.load_launch("gemm")
    assert gemm.dims([(1,), (32, 4096), (32, 4096, 11008)]) == (32, 4096, 11008)
    assert gemm.dims([(32, 4096), (4096, 11008)]) == (32, 4096, 11008)
    assert gemm.dims([(32, 4096)]) is None
    launch = counts.Launch("gemm", (32, 4096, 11008), "bfloat16", 1)
    assert counts.launch_roofline_s(launch, V5E) == pytest.approx(
        (32 * 4096 + 4096 * 11008 + 32 * 11008) * 2 / 819e9)
