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
