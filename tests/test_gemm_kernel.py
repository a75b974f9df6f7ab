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
    which dispatch refuses: the shape runs on XLA, counted as such."""
    m, k, n = 16, 1000, 128
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
