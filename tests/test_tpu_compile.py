"""The main path's Pallas kernels compile for a TPU v5e at yi-6b widths.

Compiled for a v5e that is described, not attached (the TPU compiler is
installed with jaxlib): what Mosaic refuses here — a block that misses
the (8, 128) tiling, a kernel over its VMEM budget — it would refuse on
the chip.  Nothing runs, so these tests say nothing about results or
times.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import default_config, gemm_pallas

YI = get_arch("yi-6b")
NEMOTRON = get_arch("nemotron-4-15b")
PREFILL_TOKENS = 8192  # the tune CLI's token clamp
DECODE_BATCH = 2
GEMMS = [
    (m, k, n, f"{tag}_m{m}")
    for tokens in (PREFILL_TOKENS, DECODE_BATCH)
    for (m, k, n, tag) in YI.gemm_workloads(1, tokens)
]


def _layer_kn(c) -> list:
    """(K, N) of the per-layer dense projections of a decoder: q, k/v, o,
    the MLP's in and out."""
    d, hd = c.d_model, c.resolved_head_dim
    return sorted({(d, c.n_heads * hd), (d, c.n_kv_heads * hd), (c.n_heads * hd, d),
                   (d, c.d_ff), (c.d_ff, d)})


STREAM_GEMMS = [
    (m, k, n, f"{c.name}_m{m}_{k}x{n}")
    for c in (YI, NEMOTRON) for m in (2, 32) for (k, n) in _layer_kn(c)
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip) for s in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("m,k,n,tag", GEMMS, ids=[g[3] for g in GEMMS])
def test_heuristic_gemm_compiles_for_v5e(one_chip, m, k, n, tag):
    cfg = default_config(m, k, n)
    # dispatch sends this shape to the kernel, so the compiler must take it
    assert ops._pallas_ok(m, k, n, cfg), cfg
    compiled = _compile(
        lambda a, b: gemm_pallas(a, b, cfg), one_chip, (m, k), (k, n)
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    b, s = 2, 4096
    h, kv, hd = YI.n_heads, YI.n_kv_heads, YI.resolved_head_dim
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v),
        one_chip, (b, s, h, hd), (b, s, kv, hd), (b, s, kv, hd),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["gemm", "flash"])
def test_kernel_instruction_names_match_the_trace_reduction(one_chip, kind):
    """The benchmark finds each kernel in a device trace by its HLO
    instruction name (``benchmarks/chip/trace.py``); a rename would
    silence ``gemm_roofline.*`` and ``flash_roofline.prefill``.  Compiled
    through the program's own dispatch, at yi-6b's prefill shapes."""
    from benchmarks.chip.trace import _KERNELS
    from repro.models.common import attention_dispatch

    saved = ops.kernel_policy()
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True, interpret=False))
    try:
        if kind == "gemm":
            m, k, n = PREFILL_TOKENS, YI.d_model, YI.d_ff
            text = _compile(ops.gemm, one_chip, (m, k), (k, n)).as_text()
        else:
            b, s, h, kv, hd = 2, 4096, YI.n_heads, YI.n_kv_heads, YI.resolved_head_dim
            text = _compile(attention_dispatch, one_chip,
                            (b, s, h, hd), (b, s, kv, hd), (b, s, kv, hd)).as_text()
    finally:
        ops.set_kernel_policy(saved)
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert calls and all(_KERNELS[kind].match(c) for c in calls), calls[:2]


@pytest.mark.parametrize("m,k,n,tag", STREAM_GEMMS, ids=[g[3] for g in STREAM_GEMMS])
def test_layer_indexed_gemm_compiles_for_v5e(one_chip, m, k, n, tag):
    """The kernel that reads layer ``l`` of a stacked (L, K, N) weight in
    place, at its streaming blocks, compiles at yi-6b's and
    nemotron-4-15b's decode shapes."""
    cfg = default_config(m, k, n)
    assert ops._pallas_ok(m, k, n, cfg), cfg
    args = [jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((2, k, n), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)]
    compiled = jax.jit(lambda a, w, li: gemm_pallas(a, w, cfg, layer=li)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_reads_layer_weights_in_place(one_chip):
    """A 2-layer yi-6b decode step at published widths (batch 32, cache
    256) compiles with every per-layer projection a kernel that reads the
    stacked weights: no ``dynamic-slice`` fusion copies out a layer's
    weight, and seven kernels take a stacked (2, K, N) operand."""
    import dataclasses
    import re

    from repro.models.api import Model

    cfg = dataclasses.replace(YI, n_layers=2)
    model = Model(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(model.abstract_params())
    cache = on_chip(model.abstract_cache(32, 256))
    tokens = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=one_chip)
    saved = ops.kernel_policy()
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True, interpret=False))
    try:
        text = jax.jit(model.decode_step).lower(params, cache, tokens).compile().as_text()
    finally:
        ops.set_kernel_policy(saved)
    weights = {f"bf16[{k},{n}]" for k, n in _layer_kn(cfg)}
    copies = [line.strip() for line in text.splitlines()
              if "dynamic-slice" in line.split("=", 1)[0]
              and any(w in line.split("=", 1)[-1].split(" ", 2)[1] for w in weights)]
    assert not copies, copies[:2]
    stacked = re.compile(r"operand_layout_constraints=\{s32\[1\]\{0\}, "
                         r"bf16\[32,\d+\]\{1,0\}, bf16\[2,\d+,\d+\]")
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and stacked.search(line)]
    assert len(kernels) == 7, len(kernels)
