"""The trace reduction, on synthetic intervals and on a small trace
recorded on a TPU v5e by ``record_trace.py``."""

import json
import pathlib

import pytest

from benchmarks.chip import trace

DATA = pathlib.Path(__file__).parent / "data"


def test_union_of_intervals():
    assert trace.union_ns([]) == 0
    assert trace.union_ns([(0, 10)]) == 10
    assert trace.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert trace.union_ns([(20, 30), (0, 10), (2, 3)]) == 20  # nested and unsorted


@pytest.mark.parametrize("kind,hlo,dims", [
    ("gemm", "%gemm_pallas.66 = bf16[32,11008]{1,0} custom-call(s32[1]{0} %bitcast.5, "
     "bf16[32,4096]{1,0} %fusion.3, bf16[32,4096,11008]{2,1,0} %param.7), "
     'custom_call_target="tpu_custom_call"', (32, 4096, 11008)),
    ("gemm", "%gemm_pallas.50 = bf16[8192,4096]{1,0} custom-call(bf16[8192,11008]{1,0} %a, "
     'bf16[11008,4096]{1,0} %b), custom_call_target="tpu_custom_call"', (8192, 11008, 4096)),
    ("flash", "%flash_attention.6 = bf16[2,4,8,4096,128]{4,3,2,1,0} custom-call("
     "bf16[2,4,8,4096,128]{4,3,2,1,0} %q, bf16[2,4,4096,128]{3,2,1,0} %k, "
     'bf16[2,4,4096,128]{3,2,1,0} %v), custom_call_target="tpu_custom_call"',
     (2, 32, 4, 4096, 128)),
])
def test_launch_dims_of_an_hlo_line(kind, hlo, dims):
    """A kernel event is found by its ``pallas_call`` name and read as the
    census reads its jaxpr: the layer-indexed GEMM's scalar-prefetched
    index comes first, as in the jaxpr's operands."""
    event = trace.Event(hlo, 0.0, 1.0)
    assert trace.launch_dims(kind, event) == dims
    t = trace.Trace(device={"/device:TPU:0": {"XLA Ops": [event]}}, host={})
    assert trace.kernel_events(t, kind) == [event]
    other = "flash" if kind == "gemm" else "gemm"
    assert trace.kernel_events(t, other) == []


def test_busy_and_idle_gaps_of_synthetic_trace():
    ev = lambda n, a, b: trace.Event(n, a, b - a)  # noqa: E731
    t = trace.Trace(
        device={"/device:TPU:0": {"XLA Ops": [ev("a", 0, 10), ev("b", 5, 20), ev("c", 50, 60)]}},
        host={"/host:CPU/python": [ev("generate", 0, 100), ev("np.asarray", 25, 45)]},
    )
    assert t.busy_s() == pytest.approx(30e-9)
    assert t.span_s() == pytest.approx(60e-9)
    assert t.op_seconds() == pytest.approx({"a": 10e-9, "b": 15e-9, "c": 10e-9})
    # the one hole, 20..50, named after the innermost host event at its middle
    assert t.idle_gaps() == [["np.asarray", pytest.approx(30e-9)]]


@pytest.fixture(scope="module")
def small():
    if not (DATA / "small.xplane.pb").is_file():
        pytest.skip("no recorded trace")
    return trace.load(str(DATA / "small.xplane.pb")), json.loads((DATA / "small.json").read_text())


def test_recorded_trace_has_one_device_with_ops(small):
    t, meta = small
    assert list(t.device) == ["/device:TPU:0"]
    ops = t.ops()["/device:TPU:0"]
    assert ops and all(e.dur_ns >= 0 for e in ops)
    assert 0 < t.busy_s() <= t.span_s()
    assert t.modules()["/device:TPU:0"], "the executable's runs are on the modules line"


def test_recorded_trace_names_the_kernels(small):
    t, meta = small
    calls = meta["calls"]
    per = t.op_seconds()
    assert trace.kernel_launches(t, "gemm") == calls
    assert trace.kernel_launches(t, "flash") == calls
    assert 0 < trace.kernel_seconds(t, "gemm") < sum(per.values())
    assert 0 < trace.kernel_seconds(t, "flash") < sum(per.values())


def test_tune_gemm_roofline_reads_the_recorded_kernels(small):
    """``gemm_roofline.tune`` divides the census roofline by the kernels'
    device time, and reads nothing where the trace's launches are not
    the census's, round for round.  (At this size XLA keeps the operands
    on chip, so the kernel beats the HBM roofline: the share passes 100%
    here, and only the census's sizes on the chip keep it below.)"""
    from benchmarks.chip import peaks, spec
    from benchmarks.chip.counts import Launch
    from benchmarks.chip.readers import census_roofline_s

    t, meta = small
    read = spec.load_metric_reader("gemm_roofline.tune")
    launch = Launch("gemm", tuple(meta["gemm"]), "bfloat16", 1)
    run = {"census_trace": t, "census": {"tune": [launch]}, "rounds": meta["calls"],
           "peak": peaks.peak_for(meta["device_kind"])}
    expect = census_roofline_s([launch], run["peak"]) * meta["calls"]
    assert read(run) == pytest.approx(100 * expect / trace.kernel_seconds(t, "gemm"))
    assert read(dict(run, rounds=meta["calls"] + 1)) is None
    other = Launch("gemm", (128, 512, 256), "bfloat16", 1)
    assert read(dict(run, census={"tune": [other]})) is None


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a v5e described, not attached; described inside the
    fixture, never at import (one process at a time may load the TPU
    library)."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_layer_indexed_gemm_hlo_reads_as_its_census(one_chip):
    """The layer-indexed GEMM as the TPU compiler emits it, at yi-6b's
    decode shape: its instruction is found by the kernel's name, and its
    operands (the index, A, the stack) read as the census's ``(m, k, n)``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.gemm import default_config, gemm_pallas

    m, k, n = 32, 4096, 11008
    cfg = default_config(m, k, n)
    args = [jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((2, k, n), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)]
    from jaxlib._jax import HloPrintOptions

    compiled = jax.jit(lambda a, w, li: gemm_pallas(a, w, cfg, layer=li)).lower(*args).compile()
    opts = HloPrintOptions()  # as the profiler names an op: operand shapes printed
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    assert trace._KERNELS["gemm"].match(calls[0]), calls[0]
    assert trace.launch_dims("gemm", trace.Event(calls[0], 0.0, 1.0)) == (m, k, n), calls[0]
