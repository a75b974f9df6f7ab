"""End-to-end kernel flow through the operator registry: tune a
workload per op, persist the records, and execute the real Pallas
kernels (interpret mode on CPU, compiled on a TPU) with the tuned
schedules, validated against their oracles.

The op registry (`repro.core.ops`) is the only place that knows what a
"gemm" or a "flash" is — the tuner invocation below is identical for
both, and a new op plugs in the same way (space + cost + builds, one
`register_op` call).

  PYTHONPATH=src python examples/tune_and_run_kernel.py

The CLI equivalent of the flash half (any registered op tunes through
the same launcher):

  PYTHONPATH=src python -m repro.launch.tune --op flash --tuner g-bfs \
      --fraction 0.001 --workers 2 --executor process
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax.numpy as jnp

from repro.core import (
    Budget,
    TuningRecords,
    Workload,
    get_op,
    set_global_records,
    workload_key_for,
)
from repro.core.tuners import GBFSTuner
from repro.kernels import interpret_default
from repro.kernels import ops as kernel_ops
from repro.kernels.ref import ref_gemm


def tune(wl: Workload, fraction: float = 0.01):
    """One registry-driven tuning run — identical for every op."""
    spec = get_op(wl.op)
    space = spec.make_space(wl.dims, wl.depths)
    cost = spec.analytical_cost(space)
    res = GBFSTuner(space, cost, seed=0).tune(Budget(max_fraction=fraction))
    print(f"[{wl.op}] tuned {wl.dims}: {res.best_state} "
          f"(model cost {res.best_cost*1e6:.2f} us, {res.n_trials} trials)")
    return space, res


def main():
    records = TuningRecords("records/example.json")

    # ---- gemm: tune, record, dispatch the Pallas kernel -------------------
    m = k = n = 256
    gemm_wl = Workload("gemm", (m, k, n), dtype="float32")
    _, res = tune(gemm_wl)
    records.update(
        workload_key_for("gemm", (m, k, n), "float32"),
        res.best_state, res.best_cost, "g-bfs", res.n_trials,
    )
    set_global_records(records)

    # interpreted on the CPU, compiled natively on a TPU
    kernel_ops.set_kernel_policy(kernel_ops.KernelPolicy(use_pallas=True))
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    out = kernel_ops.gemm(a, b)  # dispatches Pallas w/ the tuned BlockSpec
    err = float(jnp.max(jnp.abs(out - ref_gemm(a, b))))
    print(f"pallas-vs-ref gemm max abs err: {err:.2e}")
    assert err < 1e-3

    # ---- flash: same registry, same tuner, different op -------------------
    seq, hd = 256, 64
    flash_wl = Workload("flash", (seq, seq, hd), dtype="float32")
    # the 256-token flash space is tiny (81 schedules): afford a full
    # sweep so the demo lands on the true optimum
    fspace, fres = tune(flash_wl, fraction=1.0)
    records.update(
        flash_wl.key("analytical_tpu_v5e"),
        fres.best_state, fres.best_cost, "g-bfs", fres.n_trials,
    )

    # run the real flash kernel with the tuned (block_q, block_kv)
    # schedule via the registry's kernel binding, vs a jnp oracle
    flash = get_op("flash")
    operands = flash.timed_operands(fspace, "float32", seed=0)
    tuned_out = flash.pallas_run(fspace, fres.best_state, operands,
                                 interpret=interpret_default())
    import jax

    q, kk, v = operands
    logits = (q @ kk.T) / np.sqrt(hd)
    mask = np.tril(np.ones((seq, seq), dtype=bool))
    logits = jnp.where(mask, logits, -1e30)
    ref = jax.nn.softmax(logits, axis=-1) @ v
    ferr = float(jnp.max(jnp.abs(tuned_out.reshape(seq, hd) - ref)))
    print(f"pallas-vs-ref flash max abs err: {ferr:.2e} "
          f"(block_q={fres.best_state.block_q}, "
          f"block_kv={fres.best_state.block_kv})")
    assert ferr < 1e-3
    print("OK: tuned Pallas kernels match their oracles for both ops")


if __name__ == "__main__":
    main()
