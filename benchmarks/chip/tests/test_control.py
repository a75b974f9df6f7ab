"""The control of each cell's check comes out as not correct at the
cell's own limit, where the program comes out as correct, on three
seeds, through the same verdict the run takes (``readings``).

The serving control is the float32 reference with its weights rounded
to float8 (e4m3), put in the program's place.  A test run cannot hold
the published widths, so it keeps each cell's depth and traffic and
cuts the widths to 256 (the head dim to 64, the MLP in the published
ratio, the vocabulary to 2048) and the prompts to at most 512 tokens.
On the chip, at the cells' own sizes, PERF.md gives both readings that
each limit lies between."""

import time

import pytest

from benchmarks.chip import spec, system
from benchmarks.chip.harness import Context

WIDTH = 256
MAX_PROMPT = 512


@pytest.fixture
def cut_widths(monkeypatch):
    from benchmarks.chip.drivers import serve_offline
    from repro.kernels import ops

    reduced = system.arch_config

    def arch(config, rehearsal=False):
        m = config["model"]
        d_ff = WIDTH * m["d_ff"] // m["d_model"] // 128 * 128
        return reduced(config, rehearsal).reduced(
            param_dtype="bfloat16", compute_dtype="bfloat16", d_model=WIDTH, d_ff=d_ff,
            n_layers=m["n_layers"], head_dim=64, vocab_size=2048)

    def traffic(ctx):
        tr = dict(ctx.cell.traffic)
        tr["prompt_len"] = min(tr["prompt_len"], MAX_PROMPT)
        tr["prompt_buckets"] = [min(b, MAX_PROMPT) for b in tr["prompt_buckets"]]
        return tr

    monkeypatch.setattr(system, "arch_config", arch)
    monkeypatch.setattr(serve_offline, "_traffic", traffic)
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True))
    yield
    ops.set_kernel_policy(ops.KernelPolicy())


@pytest.mark.parametrize("cell", ["yi-6b.prefill-4k", "nemotron-4-15b-8L.prefill-4k", "yi-6b.decode-b32"])
def test_fp8_control_fails_where_the_program_passes(cell, cut_widths, tmp_path):
    from benchmarks.chip.drivers import serve_offline

    c = spec.load().cell(cell)
    ctx = Context(cell=c, seed=1, seconds=1.0, trace=False, rehearsal=True,
                  t_start=time.perf_counter(), readings=(1, 2, 3))
    rows = serve_offline.readings(ctx, str(tmp_path))
    assert [r["seed"] for r in rows] == [1, 2, 3]
    limit = c.limits["logit_gap"]["limit"]
    for r in rows:
        assert r["correct"] and not r["control_correct"], r
        assert r["program"] <= limit < r["control"], r


@pytest.mark.parametrize("dims", [(256, 512, 256), (2, 512, 1024)])
def test_fp8_control_fails_the_gemm_check(dims):
    """The GEMM check's control at the cell's own limit: operands rounded
    to float8 put in the dispatched kernel's place."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.counts import Launch
    from benchmarks.chip.drivers.tune_window import _operands, control_product, rel_err, within
    from repro.kernels import ops

    limits = spec.load().cell("yi-6b.tune-gemm").limits
    for seed in range(3):
        a, b = _operands(jax.random.PRNGKey(seed), Launch("gemm", dims, "bfloat16", 1))
        ref = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
        assert within(rel_err(ops.gemm(a, b, use_pallas=False), ref), limits)
        assert not within(rel_err(control_product(a, b), ref), limits)
