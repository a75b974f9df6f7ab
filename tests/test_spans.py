"""Spans (``repro.utils.spans``): nesting, ids, the bounded buffer,
threads, and the layers that time their stages with them (the serving
engine, the tune session and the measured cost backend)."""

import threading

import numpy as np
import pytest

from repro.utils import spans


@pytest.fixture
def fresh():
    spans.reset()
    yield
    spans.reset()


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def _inside(child, parent):
    return parent.start <= child.start <= child.end <= parent.end


def test_nesting_parent_and_root_ids(fresh):
    with spans.span("tune.workload", workload="w") as root:
        with spans.span("tune.setup") as setup:
            pass
        with spans.span("tune.measure") as measure:
            with spans.span("measure.funnel") as funnel:
                root.attrs["late"] = 1
    with spans.span("tune.workload") as other:
        pass
    recs = {r.id: r for r in spans.snapshot()}
    assert [r.name for r in spans.snapshot()] == [
        "tune.setup", "measure.funnel", "tune.measure", "tune.workload", "tune.workload",
    ]  # each appended as it closed
    assert recs[root.id].parent is None and recs[root.id].root == root.id
    assert recs[setup.id].parent == root.id and recs[measure.id].parent == root.id
    assert recs[funnel.id].parent == measure.id
    assert {recs[i].root for i in (setup.id, measure.id, funnel.id)} == {root.id}
    assert recs[other.id].root == other.id != root.id
    assert recs[root.id].attrs == {"workload": "w", "late": 1}
    assert _inside(recs[funnel.id], recs[measure.id])
    assert recs[setup.id].seconds == pytest.approx(setup.seconds)
    assert spans.snapshot()[0].thread == threading.get_ident()


def test_a_span_closes_on_an_exception(fresh):
    with pytest.raises(ValueError):
        with spans.span("measure.compile"):
            raise ValueError("refused")
    with spans.span("measure.timed") as sp:
        pass
    (bad, ok) = spans.snapshot()
    assert bad.name == "measure.compile" and bad.end >= bad.start
    assert ok.parent is None and ok.root == sp.id  # the stack unwound


def test_buffer_keeps_the_newest_records(fresh):
    n = spans.CAPACITY + 10
    for i in range(n):
        with spans.span("serve.stage", i=i):
            pass
    recs = spans.snapshot()
    assert len(recs) == spans.CAPACITY
    assert [r.attrs["i"] for r in recs[:2]] == [10, 11]
    assert recs[-1].attrs["i"] == n - 1
    spans.reset()
    assert spans.snapshot() == []


@pytest.mark.parametrize("carried", [False, True])
def test_records_from_several_threads(fresh, carried):
    """Each thread nests on its own stack; a thread started under
    ``carry`` takes the starting span's root.  No record is lost while
    eight threads write at once."""
    n_threads, per_thread = 8, 500
    together = threading.Barrier(n_threads, timeout=60)  # no ident is reused

    def work():
        together.wait()
        for _ in range(per_thread):
            with spans.span("measure.compile"):
                with spans.span("measure.load"):
                    pass
        together.wait()

    with spans.span("measure.lanes") as wave:
        target = spans.carry(work) if carried else work
        threads = [threading.Thread(target=target) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    recs = [r for r in spans.snapshot() if r.name != "measure.lanes"]
    assert len(recs) == 2 * n_threads * per_thread
    assert len({r.id for r in recs}) == len(recs)
    assert len({r.thread for r in recs}) == n_threads
    by_id = {r.id: r for r in recs}
    for r in recs:
        if r.name == "measure.load":
            parent = by_id[r.parent]
            assert parent.name == "measure.compile" and parent.thread == r.thread
            assert r.root == parent.root
        else:
            assert r.parent is None  # nothing open on its own thread
            assert (r.root == wave.id) if carried else (r.root == r.id)


def test_serve_generate_spans_time_the_stats():
    import jax

    from repro.configs.registry import get_arch
    from repro.launch.serve import ServeEngine
    from repro.models.api import Model

    cfg = get_arch("yi-6b").reduced()
    params = Model(cfg).init_params(jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_batch=2, max_len=24,
                         prompt_buckets=[16], gen_buckets=[4])
    prompts = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    spans.reset()
    engine.generate(prompts, gen_tokens=4)
    recs = spans.snapshot()
    by = _by_name(recs)
    (call,) = by["serve.generate"]
    assert call.attrs == {"batch": 2, "prompt_bucket": 16, "gen_bucket": 4}
    (pre,) = by["serve.prefill"]
    (dec,) = by["serve.decode"]
    assert engine.stats["prefill_s"][-1] == pre.seconds
    assert engine.stats["decode_s"][-1] == dec.seconds
    ids = {r.id: r for r in recs}
    assert {r.root for r in recs} == {call.id}
    for r in recs:
        if r.parent is not None:
            assert _inside(r, ids[r.parent]), r.name
    children = {p: sorted(r.name for r in recs if r.parent == ids_p)
                for p, ids_p in (("prefill", pre.id), ("decode", dec.id))}
    assert children == {
        "prefill": ["serve.launch", "serve.stage", "serve.wait"],
        "decode": ["serve.fetch", "serve.launch", "serve.wait"],
    }


@pytest.mark.parametrize("executor", ["sim", "thread"])
def test_tune_workload_spans_cover_the_search(executor):
    from repro.core import Budget, TuningSession, Workload
    from repro.core.cost.measured import XLATimedCost
    from repro.core.executor import make_executor
    from repro.core.measure import MeasureStats

    wl = Workload("gemm", (256, 256, 256), dtype="float32", label="w")
    stats = MeasureStats()
    spans.reset()
    with make_executor(executor) as ex:
        TuningSession(
            cost_factory=lambda sp: XLATimedCost(sp, n_repeats=2), verbose=False,
        ).tune_workload(wl, "g-bfs", Budget(max_trials=6), stats=stats, executor=ex)
    recs = spans.snapshot()
    by = _by_name(recs)
    (root,) = by["tune.workload"]
    assert root.attrs == {"workload": "w"}
    assert {r.root for r in recs} == {root.id}  # lane threads carry it
    assert stats.n_compiles == len(by["measure.compile"]) > 0
    assert stats.compile_s == pytest.approx(sum(r.seconds for r in by["measure.compile"]))
    assert len(by["measure.timed"]) == len(by["measure.warmup"]) == stats.n_compiles
    assert stats.static_s == 0.0 and "measure.static" not in by  # analyze="off"
    staged = sorted((r.start, r.end) for r in recs if r.parent == root.id)
    covered, end = 0.0, root.start
    for a, b in staged:
        covered += max(0.0, b - max(a, end))
        end = max(end, b)
    assert covered >= 0.95 * root.seconds
