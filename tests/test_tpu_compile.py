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
PREFILL_TOKENS = 8192  # the tune CLI's token clamp
DECODE_BATCH = 2
GEMMS = [
    (m, k, n, f"{tag}_m{m}")
    for tokens in (PREFILL_TOKENS, DECODE_BATCH)
    for (m, k, n, tag) in YI.gemm_workloads(1, tokens)
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
