"""Pallas GEMM kernel vs the pure-jnp oracle: shape/dtype sweep +
property-based block configs + differentiability (interpret mode)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
pytest.importorskip("hypothesis")  # optional dev dep; skip, don't error
from hypothesis import given, settings, strategies as st

from repro.core.config_space import GemmConfigSpace, TilingState
from repro.kernels import ops
from repro.kernels.gemm import KernelConfig, default_config, gemm_pallas, kernel_config_from_state
from repro.kernels.ref import ref_gemm, ref_gemm_vjp

SHAPES = [
    (64, 64, 64),
    (128, 256, 64),
    (256, 128, 512),
    (8, 1024, 8),
]
CONFIGS = [
    KernelConfig(32, 64, 32),
    KernelConfig(64, 128, 64, sub_m=32, sub_n=32),
    KernelConfig(8, 128, 8),
]


def _rand(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 0.05)])
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_gemm_matches_ref(shape, dtype, tol):
    m, k, n = shape
    cfg = default_config(m, k, n)
    a = _rand((m, k), dtype)
    b = _rand((k, n), dtype, seed=1)
    out = gemm_pallas(a, b, cfg, interpret=True)
    ref = ref_gemm(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * 8,
    )


@pytest.mark.parametrize("cfg", CONFIGS, ids=str)
def test_gemm_explicit_configs(cfg):
    m, k, n = 128, 256, 128
    if m % cfg.block_m or k % cfg.block_k or n % cfg.block_n:
        pytest.skip("not divisible")
    a, b = _rand((m, k), "float32"), _rand((k, n), "float32", 1)
    out = gemm_pallas(a, b, cfg, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_gemm(a, b)), rtol=1e-4, atol=1e-3)


@given(
    em=st.integers(0, 2), ek=st.integers(0, 2), en=st.integers(0, 2),
    seed=st.integers(0, 100),
)
@settings(max_examples=12, deadline=None)
def test_gemm_tuner_state_configs(em, ek, en, seed):
    """Any legitimate tuner state maps to a kernel config that computes
    the right product (the tuner<->kernel contract)."""
    import random

    m, k, n = 64 << em, 64 << ek, 64 << en
    space = GemmConfigSpace(m, k, n)
    s = space.random_state(random.Random(seed))
    try:
        cfg = kernel_config_from_state(s)
    except ValueError:
        return  # config not realizable (e.g. sub-tile doesn't divide)
    # keep interpret-mode runtime sane
    if s.grid[0] * s.grid[1] * s.grid[2] > 64:
        return
    a, b = _rand((m, k), "float32"), _rand((k, n), "float32", 1)
    out = gemm_pallas(a, b, cfg, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_gemm(a, b)), rtol=1e-4, atol=1e-3)


def test_gemm_grad_matches_ref():
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True, interpret=True))
    try:
        a, b = _rand((64, 128), "float32"), _rand((128, 64), "float32", 1)
        g = _rand((64, 64), "float32", 2)

        def f(a, b):
            return jnp.sum(ops.gemm(a, b) * g)

        da, db = jax.grad(f, argnums=(0, 1))(a, b)
        da_ref, db_ref = ref_gemm_vjp(a, b, g)
        np.testing.assert_allclose(np.asarray(da), np.asarray(da_ref), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref), rtol=1e-4, atol=1e-4)
    finally:
        ops.set_kernel_policy(ops.KernelPolicy())


def test_gemm_dispatch_fallback():
    """Indivisible shapes fall back to XLA silently."""
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True, interpret=True))
    try:
        a, b = _rand((63, 127), "float32"), _rand((127, 65), "float32", 1)
        out = ops.gemm(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b), rtol=1e-4, atol=1e-4)
    finally:
        ops.set_kernel_policy(ops.KernelPolicy())


def test_gemm_higher_rank_lhs():
    a, b = _rand((4, 8, 32), "float32"), _rand((32, 16), "float32", 1)
    out = ops.gemm(a, b)
    assert out.shape == (4, 8, 16)
    np.testing.assert_allclose(
        np.asarray(out), np.einsum("abk,kn->abn", np.asarray(a), np.asarray(b)),
        rtol=1e-4, atol=1e-4,
    )


def test_records_dispatch(tmp_path):
    """A tuning record changes which config gemm() picks."""
    from repro.core.records import TuningRecords, set_global_records, workload_key, global_records

    old = global_records()
    try:
        rec = TuningRecords(str(tmp_path / "records.json"))
        s = TilingState((2, 1, 2, 16), (1, 64), (2, 1, 2, 16))
        rec.update(workload_key(64, 64, 64, "float32"), s, 1e-6, "g-bfs", 10)
        set_global_records(rec)
        ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True, interpret=True))
        a, b = _rand((64, 64), "float32"), _rand((64, 64), "float32", 1)
        out = ops.gemm(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b), rtol=1e-4, atol=1e-3)
    finally:
        set_global_records(old)
        ops.set_kernel_policy(ops.KernelPolicy())


def test_policy_follows_the_backend(monkeypatch):
    """The default policy is the backend's rule: Pallas off and
    interpreted on the CPU, on and compiled on a TPU; explicit fields
    stay as given."""
    pol = ops.KernelPolicy().resolved()
    assert (pol.use_pallas, pol.interpret) == (False, True)
    forced = ops.KernelPolicy(use_pallas=True, interpret=False).resolved()
    assert (forced.use_pallas, forced.interpret) == (True, False)
    monkeypatch.setattr(ops, "interpret_default", lambda: False)  # a TPU
    pol = ops.KernelPolicy().resolved()
    assert (pol.use_pallas, pol.interpret) == (True, False)


def test_interpret_refused_on_a_tpu_backend(monkeypatch):
    import repro.kernels as kernels
    from repro.core.cost.measured import PallasInterpretCost

    monkeypatch.setattr(kernels, "interpret_default", lambda: False)  # a TPU
    a, b = _rand((72, 128), "float32"), _rand((128, 128), "float32", 1)
    with pytest.raises(ValueError, match="TPU backend"):
        gemm_pallas(a, b, KernelConfig(8, 128, 128), interpret=True)
    with pytest.raises(ValueError, match="TPU backend"):
        PallasInterpretCost(GemmConfigSpace(64, 64, 64))


@pytest.mark.parametrize(
    "dims", [(8192, 11008, 4096), (8192, 4096, 11008), (2, 4096, 65536),
             (8200, 4096, 4096), (63, 127, 65)], ids=str,
)
def test_default_config_meets_tpu_tiling(dims):
    cfg = default_config(*dims)
    assert cfg.tpu_aligned(*dims), cfg
    cfg.validate(*dims)


def test_unaligned_heuristic_dispatches_to_xla():
    """A dim with no tiling-aligned divisor keeps an unaligned block,
    which dispatch refuses: the shape runs on XLA, counted as such.  (At
    most ``STREAM_M`` rows K is one whole block, which the tiling takes:
    the unaligned block needs more rows.)"""
    m, k, n = 256, 1000, 128
    assert default_config(16, k, n).tpu_aligned(16, k, n)
    assert not default_config(m, k, n).tpu_aligned(m, k, n)
    assert default_config(8192, 11008, 4096).block_k == 256  # was 344
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True, interpret=True))
    ops.reset_dispatch_stats()
    try:
        a, b = _rand((m, k), "float32"), _rand((k, n), "float32", 1)
        out = ops.gemm(a, b)
        assert ops.dispatch_stats()["gemm"]["xla"] == 1
        np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                                   rtol=1e-4, atol=1e-3)
    finally:
        ops.set_kernel_policy(ops.KernelPolicy())
        ops.reset_dispatch_stats()


# -- layer-indexed B: the decode step's in-place weight reads -----------------


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("m", [2, 32])
@pytest.mark.parametrize(
    "k,n,blocks",
    [
        (43 * 256, 384, None),  # yi's K of 11008; N of 3 x 128; the streaming blocks
        (3 * 256, 5 * 128, (256, 128)),  # several K and N steps through the stack
    ],
    ids=["k11008_n384_stream", "k768_n640_small_blocks"],
)
def test_layer_indexed_gemm_matches_dot(m, k, n, blocks, layer):
    """The kernel reading layer ``l`` of a stacked (L, K, N) B in place
    equals ``x @ w[l]``, at the first and the last layer."""
    n_layers = 3
    li = 0 if layer == "first" else n_layers - 1
    a = _rand((m, k), "float32")
    w = _rand((n_layers, k, n), "float32", seed=1)
    cfg = default_config(m, k, n, itemsize=4) if blocks is None else KernelConfig(m, *blocks)
    assert cfg.tpu_aligned(m, k, n), cfg
    out = gemm_pallas(a, w, cfg, interpret=True, layer=jnp.int32(li))
    want = np.asarray(a, np.float64) @ np.asarray(w[li], np.float64)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-3 * np.sqrt(k / 128))


@pytest.mark.parametrize("m", [4, 256])
def test_stacked_gemm_dispatch_counts_stream(m):
    """``ops.gemm`` on a StackedWeight: with Pallas on, one ``stream``
    dispatch that reads the layer in place, whatever the rows (the
    streaming blocks at 4, the per-dim blocks at 256); with it off, XLA's
    dot of the sliced layer.  Both equal ``x @ w[l]``."""
    a = _rand((m, 256), "float32")
    w = _rand((2, 256, 384), "float32", seed=1)
    want = np.asarray(a) @ np.asarray(w[1])
    sw = ops.StackedWeight(w, jnp.int32(1))
    ops.reset_dispatch_stats()
    try:
        np.testing.assert_allclose(np.asarray(ops.gemm(a, sw)), want, rtol=1e-4, atol=1e-3)
        assert ops.dispatch_stats().get("gemm", {}).get("stream", 0) == 0
        ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True, interpret=True))
        np.testing.assert_allclose(np.asarray(ops.gemm(a, sw)), want, rtol=1e-4, atol=1e-3)
        assert ops.dispatch_stats()["gemm"]["stream"] == 1
    finally:
        ops.set_kernel_policy(ops.KernelPolicy())
        ops.reset_dispatch_stats()


def _decode_gemms(arch: str, m: int) -> list:
    """(M, K, N) of every GEMM of one decode step of ``arch``: the seven
    per-layer projections of its dense blocks and the head."""
    from repro.configs.registry import get_arch

    c = get_arch(arch)
    d, hd, f = c.d_model, c.resolved_head_dim, c.d_ff
    kn = {(d, c.n_heads * hd), (d, c.n_kv_heads * hd), (c.n_heads * hd, d),
          (d, f), (f, d), (d, c.padded_vocab)}
    return [(m, k, n) for k, n in sorted(kn)]


DECODE_GEMMS = [
    (arch, dims)
    for arch, m in (("yi-6b", 32), ("yi-6b", 2), ("nemotron-4-15b", 2), ("nemotron-4-15b", 32))
    for dims in _decode_gemms(arch, m)
]


@pytest.mark.parametrize(
    "arch,dims", DECODE_GEMMS, ids=[f"{a}_{'x'.join(map(str, d))}" for a, d in DECODE_GEMMS]
)
def test_streaming_blocks_on_decode_shapes(arch, dims):
    """The heuristic's blocks at a decode shape meet the TPU tiling, move
    at least ``STREAM_TILE_BYTES`` of bf16 B a grid step, and keep the
    double-buffered working set within the VMEM budget."""
    from repro.core.analysis import VMEM_BUDGET_BYTES, gemm_working_set_bytes
    from repro.kernels.gemm import STREAM_M, STREAM_TILE_BYTES

    m, k, n = dims
    assert m <= STREAM_M
    cfg = default_config(m, k, n)
    assert cfg.tpu_aligned(m, k, n), cfg
    cfg.validate(m, k, n)
    assert cfg.block_m == m
    assert cfg.block_k * cfg.block_n * 2 >= STREAM_TILE_BYTES, cfg
    assert gemm_working_set_bytes(cfg.block_m, cfg.block_k, cfg.block_n, 2) <= VMEM_BUDGET_BYTES


def test_prefill_shapes_keep_their_blocks():
    """Above ``STREAM_M`` rows the heuristic is the per-dim rule it was."""
    assert default_config(8192, 4096, 11008) == KernelConfig(256, 512, 256)
    assert default_config(8192, 11008, 4096) == KernelConfig(256, 256, 256)
    assert default_config(8192, 6144, 24576) == KernelConfig(256, 512, 256)
