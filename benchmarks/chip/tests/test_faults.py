"""The check decides ``correct`` on what the timed path produced: with
the path sound a run is correct, with a served token or a GEMM answer
altered where it is produced it is not.  Runs the drivers on the CPU at
the registry's reduced sizes, past the harness's look for a chip."""

import time

import numpy as np
import pytest

from benchmarks.chip import spec
from benchmarks.chip.harness import Context


@pytest.fixture
def pallas_interpreted():
    from repro.kernels import ops

    ops.set_kernel_policy(ops.KernelPolicy(use_pallas=True))
    yield
    ops.set_kernel_policy(ops.KernelPolicy())


def _run(cell: str, root, seconds: float = 1.0):
    from benchmarks.chip.drivers import serve_offline, tune_window

    c = spec.load().cell(cell)
    ctx = Context(cell=c, seed=2**33 + 5, seconds=seconds, trace=False, rehearsal=True,
                  t_start=time.perf_counter())
    driver = {"serve_offline": serve_offline, "tune_window": tune_window}[c.traffic["driver"]]
    return driver.run(ctx, str(root))


@pytest.mark.parametrize("cell", ["yi-6b.prefill-4k", "nemotron-4-15b-8L.prefill-4k", "yi-6b.decode-b32"])
def test_serving_sound_and_altered_token(cell, tmp_path, monkeypatch, pallas_interpreted):
    from repro.launch.serve import ServeEngine

    res = _run(cell, tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0

    sound = ServeEngine.generate

    def altered(self, prompts, gen_tokens, prompt_lens=None):
        out = np.array(sound(self, prompts, gen_tokens, prompt_lens))
        out[:, -1] = (out[:, -1] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(ServeEngine, "generate", altered)
    res = _run(cell, tmp_path)
    assert not res["correct"], res["checks"]
    check = res["checks"]["logit_gap"]
    assert check["value"] > check["limit"]


def test_tuning_sound_and_altered_answer(tmp_path, monkeypatch, pallas_interpreted):
    from repro.kernels import ops

    res = _run("yi-6b.tune-gemm", tmp_path, seconds=2.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0

    sound = ops.gemm

    def altered(a, b, *args, **kw):
        return sound(a, b, *args, **kw).at[0, 0].add(64.0)

    monkeypatch.setattr(ops, "gemm", altered)
    res = _run("yi-6b.tune-gemm", tmp_path, seconds=2.0)
    assert not res["correct"], res["checks"]
