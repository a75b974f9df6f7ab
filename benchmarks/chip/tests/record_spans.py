"""Record the small annotated trace that ``tests/test_span_readers.py``
reads, on a TPU.

    python -m benchmarks.chip.tests.record_spans benchmarks/chip/tests/data

Three calls of one jitted program (a chain of 2048^3 bf16 matmuls),
each under a ``serve.generate`` span with ``serve.launch`` and
``serve.wait`` children, then a host sleep of 50 ms inside a
``serve.stage`` span (the device idles inside a known span), then 30 ms
of sleep outside any span, all under the profiler.  Writes
``small_spans.xplane.pb`` and ``small_spans.json``: the span records of
the traced calls, the traced window on ``time.perf_counter`` (taken as
the benchmark's tune run takes it) and the sleeps.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

IN_SPAN_S, OUT_SPAN_S, CALLS = 0.05, 0.03, 3


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.utils import spans

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_spans needs a TPU")

    @jax.jit
    def step(a):
        for _ in range(8):
            a = (a @ a) * 0.5
        return a

    a = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048), jnp.bfloat16)
    step(a).block_until_ready()
    tmp = tempfile.mkdtemp()
    spans.reset()
    t0 = time.perf_counter()
    jax.profiler.start_trace(tmp)
    for _ in range(CALLS):
        with spans.span("serve.generate", batch=1):
            with spans.span("serve.launch"):
                out = step(a)
            with spans.span("serve.wait"):
                out.block_until_ready()
            with spans.span("serve.stage"):
                time.sleep(IN_SPAN_S)
        time.sleep(OUT_SPAN_S)
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small_spans.xplane.pb"))
    with open(os.path.join(out_dir, "small_spans.json"), "w") as f:
        json.dump({
            "calls": CALLS, "in_span_sleep_s": IN_SPAN_S, "out_span_sleep_s": OUT_SPAN_S,
            "traced": [t0, t1], "spans": [list(r) for r in spans.snapshot()],
            "device_kind": jax.devices()[0].device_kind,
        }, f)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
