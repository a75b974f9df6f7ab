"""Record the small trace that ``test_trace.py`` reads, on a TPU.

    python -m benchmarks.chip.tests.record_trace benchmarks/chip/tests/data

Two calls of one jitted program that runs a Pallas GEMM (256 x 512 x
256), a flash attention launch (B 1, S 512, H 4, KV 1, hd 128) and an
XLA add, under the profiler.  Writes ``small.xplane.pb`` and
``small.json`` (what the program ran, for the test to compare).
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.gemm import default_config, gemm_pallas

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace needs a TPU")
    m, k, n = 256, 512, 256
    cfg = default_config(m, k, n)

    @jax.jit
    def step(a, b, q, kk, v):
        c = gemm_pallas(a, b, cfg)
        o = flash_attention(q, kk, v)
        return c + 1, o

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k), jnp.bfloat16)
    b = jax.random.normal(key, (k, n), jnp.bfloat16)
    q = jax.random.normal(key, (1, 512, 4, 128), jnp.bfloat16)
    kv = jax.random.normal(key, (1, 512, 1, 128), jnp.bfloat16)
    jax.block_until_ready(step(a, b, q, kv, kv))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(2):
        jax.block_until_ready(step(a, b, q, kv, kv))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    with open(os.path.join(out_dir, "small.json"), "w") as f:
        json.dump({"calls": 2, "gemm": [m, k, n], "flash": [1, 4, 1, 512, 128],
                   "device_kind": jax.devices()[0].device_kind}, f)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
