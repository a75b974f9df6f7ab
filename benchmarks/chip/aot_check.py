"""Compile a cell's programs for a described TPU v5e, without the chip,
and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python -m benchmarks.chip.aot_check \
        --workload nemotron-4-15b-8L.prefill-4k

The programs are the ones a run of the cell builds: the seeded weights,
the engine's prefill and decode at the traffic's batch and buckets, and
the reference over one checked request.  A program that does not fit
is refused here by the TPU compiler, as it would be on the chip.
"""

from __future__ import annotations

import argparse
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--only", default="weights,prefill,decode,reference")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import ops
    from repro.models.api import Model

    from . import reference, spec, system, weights
    from .drivers.serve_offline import reference_rows

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load().cell(args.workload)
    cfg = system.arch_config(cell.config)
    model_sizes = system.stated_model(cell.config, cfg)
    tr = cell.traffic
    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True, interpret=False))
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    model = Model(cfg)
    params = on_chip(model.abstract_params())
    b = tr["batch"]
    p = max(tr["prompt_buckets"])
    g = max(tr["gen_buckets"])
    max_len = p + g
    progs = {}
    if "weights" in args.only:
        progs["weights"] = (weights.program(model.abstract_params()),
                            (jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip),))
    if "prefill" in args.only:
        progs["prefill"] = (
            lambda prm, bt, last: model.prefill(prm, bt, max_len, last_idx=last),
            (params, {"tokens": jax.ShapeDtypeStruct((b, p), jnp.int32, sharding=chip)},
             jax.ShapeDtypeStruct((b,), jnp.int32, sharding=chip)),
        )
    if "decode" in args.only:
        cache = model.abstract_cache(b, max_len)
        cache["valid_len"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        cache["prefill_len"] = jax.ShapeDtypeStruct((), jnp.int32)
        v = cfg.vocab_size

        def decode(prm, cache, logits):
            def step(carry, _):
                cache, tok = carry
                lg, cache = model.decode_step(prm, cache, tok)
                return (cache, jnp.argmax(lg[:, -1, :v], -1)[:, None].astype(jnp.int32)), tok[:, 0]

            tok0 = jnp.argmax(logits[:, -1, :v], -1)[:, None].astype(jnp.int32)
            return jax.lax.scan(step, (cache, tok0), None, length=g)[1].T

        progs["decode"] = (decode, (params, on_chip(cache), jax.ShapeDtypeStruct(
            (b, 1, cfg.padded_vocab), jnp.float32, sharding=chip)))
    if "reference" in args.only:
        t = tr["prompt_len"] + tr["gen_tokens"] - 1
        rows = reference_rows(t, tr["check_requests"])
        items = tuple(sorted((k, v) for k, v in model_sizes.items()
                             if not isinstance(v, (dict, list))))
        progs["reference"] = (
            lambda prm, tok: reference._logits(prm, tok, model_items=items,
                                               first=tr["prompt_len"] - 1, control=False),
            (params, jax.ShapeDtypeStruct((rows, t), jnp.int32, sharding=chip)),
        )
    for name, (fn, fargs) in progs.items():
        m = jax.jit(fn).lower(*fargs).compile().memory_analysis()
        total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes \
            - m.alias_size_in_bytes
        print(f"[aot] {cell.name} {name}: args {m.argument_size_in_bytes / 1e9:.3f} GB, "
              f"out {m.output_size_in_bytes / 1e9:.3f} GB, temp {m.temp_size_in_bytes / 1e9:.3f} GB, "
              f"alias {m.alias_size_in_bytes / 1e9:.3f} GB, total {total / 1e9:.3f} GB", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
