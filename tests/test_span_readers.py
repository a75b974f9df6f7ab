"""The benchmark's span reductions (``benchmarks/chip/spans.py``) and the
per-layer metrics that read them, on synthetic run records and on a
small trace recorded on a TPU v5e by
``benchmarks/chip/tests/record_spans.py``."""

import json
import pathlib

import pytest

from benchmarks.chip import spans as bs
from benchmarks.chip import spec
from benchmarks.chip import trace as tr
from repro.utils.spans import Record

DATA = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "tests" / "data"
MAIN, LANE, POOL = 1, 2, 3  # threads


def _records(rows):
    """``(name, start, end, thread, id, parent)`` rows; every record
    under root 1."""
    return [Record(n, a, b, t, i, p, 1, {}) for n, a, b, t, i, p in rows]


#: a tuned workload over 0..10 s: set-up, then one measure_many call with
#: a funnel, a wave on a lane thread (two compiles overlapping on the
#: build pool), the journal; proposals in 1..2 and 9..10 (self time)
TUNE = _records([
    ("tune.setup", -5.0, -4.0, MAIN, 20, None),  # before the window
    ("tune.workload", 0.0, 10.0, MAIN, 1, None),
    ("tune.setup", 0.0, 1.0, MAIN, 2, 1),
    ("tune.measure", 2.0, 9.0, MAIN, 3, 1),
    ("measure.funnel", 2.0, 2.5, MAIN, 4, 3),
    ("measure.lanes", 2.5, 8.0, MAIN, 5, 3),
    ("measure.compile", 2.6, 6.0, LANE, 6, None),
    ("measure.compile", 3.0, 5.0, POOL, 7, None),
    ("measure.warmup", 6.0, 6.5, LANE, 8, None),
    ("measure.timed", 6.5, 7.5, LANE, 9, None),
    ("measure.journal", 8.0, 8.5, MAIN, 10, 3),
])
TUNE_RUN = {"spans": TUNE, "traced": (0.0, 6.0, None), "window_s": 10.0}

#: two serving calls in a window of 100..110 s
SERVE = _records([
    ("serve.stage", 100.0, 100.5, MAIN, 2, 1),
    ("serve.launch", 100.5, 101.0, MAIN, 3, 1),
    ("serve.wait", 101.0, 104.0, MAIN, 4, 1),
    ("serve.launch", 104.0, 104.25, MAIN, 5, 1),
    ("serve.wait", 104.25, 105.0, MAIN, 6, 1),
    ("serve.fetch", 105.0, 105.25, MAIN, 7, 1),
    ("serve.stage", 109.5, 111.0, MAIN, 8, 1),  # half outside the window
])
SERVE_RUN = {"spans": SERVE, "calls": [{"t0": 100.0}], "window_s": 10.0}


@pytest.mark.parametrize("fn,args,want", [
    (bs.merge, ([(5, 6), (0, 2), (1, 3), (4, 4)],), [(0, 3), (5, 6)]),
    (bs.intersect, ([(0, 3), (5, 9)], [(2, 6), (8, 10)]), [(2, 3), (5, 6), (8, 9)]),
    (bs.subtract, ([(0, 10)], [(1, 2), (4, 5), (9, 12)]), [(0, 1), (2, 4), (5, 9)]),
    (bs.subtract, ([(0, 3), (5, 9)], [(2, 6)]), [(0, 2), (6, 9)]),
])
def test_interval_algebra(fn, args, want):
    assert fn(*args) == want


@pytest.mark.parametrize("metric,run,want", [
    ("timed_share.tune", TUNE_RUN, 10.0),
    ("setup_share.tune", TUNE_RUN, 10.0),  # the earlier set-up is outside
    ("host_share.tune", TUNE_RUN, 30.0),  # funnel .5 + journal .5 + self 2
    ("host_share.prefill", SERVE_RUN, 100.0 * 2.0 / 10.0),
    ("host_share.decode", SERVE_RUN, 100.0 * 2.0 / 10.0),
])
def test_span_metrics_on_synthetic_runs(metric, run, want):
    read = spec.load_metric_reader(metric)
    assert read(run) == pytest.approx(want)
    assert read(dict(run, spans=[])) is None  # a program without spans
    assert read({k: v for k, v in run.items() if k != "window_s"}) is None


def test_overlapping_compiles_count_once():
    assert bs.span_share(TUNE_RUN, {"measure.compile"}) == pytest.approx(34.0)


def _ev(name, a, b):
    return tr.Event(name, a, b - a)


#: the profiler's session started 40 us after ``traced[0]`` (0 s), where
#: the trace's clock reads zero
SESSION_NS = 40e3


def _synthetic_trace():
    """The tune run's first 6 s in a trace: the device busy at 2.50-2.55
    and 5.5-5.6 s; the host plane holds the spans that closed before the
    trace stopped at 6 s, and Python-tracer noise."""
    ns = lambda s: s * 1e9 - SESSION_NS  # noqa: E731
    ops = [_ev("%fusion.1 = f32[8] fusion()", ns(a), ns(b))
           for a, b in [(2.50, 2.55), (5.5, 5.6)]]
    host = [_ev(r.name, ns(r.start) + 700, ns(r.end) - 300) for r in TUNE
            if 0 <= r.start and r.end <= 6.0]
    noise = [_ev("measured.py:346 _ensure", ns(0.5), ns(5.5)),
             _ev("tune.py:120 main", ns(0.0), ns(6.0))]
    return tr.Trace(device={"/device:TPU:0": {"XLA Ops": ops}},
                    host={"/host:CPU/python": host + noise})


def test_clock_offset_pairs_trace_events_with_records():
    """The prior (the session starting at ``traced[0]``) is refined by the
    trace's own program spans: each event starts 700 ns after its record."""
    events = bs._program_events(_synthetic_trace())
    assert [e.name for e in events] == [
        "tune.setup", "measure.funnel", "measure.compile", "measure.compile",
    ]  # the workload, the measure call and the lanes were still open at 6 s
    assert bs.clock_offset_ns(events, TUNE, 0.0) == pytest.approx(-SESSION_NS + 700)
    assert bs.clock_offset_ns([], TUNE, 0.0) == 0.0  # nothing to refine it
    far = bs.clock_offset_ns(events, TUNE, 1e9)  # no record within 5 ms: the prior
    assert far == 1e9


def test_idle_unattributed_on_a_synthetic_trace():
    """Idle in 0..6 s: 5.85 s.  The workload's record, open when the
    trace stopped, covers all of it (its proposals at 1..2 count as its
    own), so none is unattributed.  With the closed spans alone, 1..2
    and 2.55..2.6 lie outside every span."""
    run = dict(TUNE_RUN, trace=_synthetic_trace())
    assert bs.idle_unattributed(run) == pytest.approx(0.0, abs=1e-6)
    read = spec.load_metric_reader("idle_unattributed.tune")
    assert read(run) == pytest.approx(0.0, abs=1e-6)
    only_closed = [r for r in TUNE if r.end <= 6.0]
    got = bs.idle_unattributed(dict(run, spans=only_closed))
    assert got == pytest.approx(100 * 1.05 / 5.85, rel=1e-4)
    assert bs.idle_unattributed(dict(run, spans=[])) is None


@pytest.fixture(scope="module")
def recorded():
    meta = json.loads((DATA / "small_spans.json").read_text())
    run = {"trace": tr.load(str(DATA / "small_spans.xplane.pb")),
           "traced": (*meta["traced"], None),
           "spans": [Record(*r) for r in meta["spans"]]}
    return run, meta


def test_recorded_trace_holds_the_program_spans(recorded):
    run, meta = recorded
    events = bs._program_events(run["trace"])
    names = [e.name for e in events]
    for name in ("serve.generate", "serve.launch", "serve.wait", "serve.stage"):
        assert names.count(name) == meta["calls"], name
    prior = -run["traced"][0] * 1e9
    offset = bs.clock_offset_ns(events, run["spans"], prior)
    assert abs(offset - prior) < 1e6  # the session starts within 1 ms of traced[0]
    for e, r in zip(events, sorted(run["spans"], key=lambda r: r.start)):
        assert e.name == r.name
        assert abs(e.start_ns - (r.start * 1e9 + offset)) < 50e3


def test_recorded_idle_lies_where_the_host_slept(recorded):
    """The device idles through each call's 50 ms in ``serve.stage``,
    which holds it, and through its 30 ms outside any span, which no
    span holds; so does the profiler's own start before the first span."""
    run, meta = recorded
    calls, a, b = meta["calls"], meta["in_span_sleep_s"], meta["out_span_sleep_s"]
    by_name, none_s, idle_s = bs.idle_by_span(run)
    assert by_name["serve.stage"] == pytest.approx(calls * a, rel=0.05)
    host = [e for line in run["trace"].host.values() for e in line]
    starting = (bs._program_events(run["trace"])[0].start_ns
                - min(e.start_ns for e in host)) / 1e9
    assert none_s == pytest.approx(calls * b + starting, rel=0.05)
    assert sum(by_name.values()) + none_s == pytest.approx(idle_s)
    assert bs.idle_unattributed(run) == pytest.approx(100 * none_s / idle_s)
    assert bs.idle_unattributed(dict(run, spans=[])) is None
