"""Lane executors (thread/process) and the crash-safe shared journal:
real-concurrency waves, per-lane timeout/crash isolation, O_APPEND
multi-process journal appends, strict-JSON cost encoding, and sibling
reload merging."""

import itertools
import json
import math
import multiprocessing
import time

import pytest

from repro.core import (
    AnalyticalTPUCost,
    Budget,
    GBFSTuner,
    GemmConfigSpace,
    MeasureEngine,
    ProcessExecutor,
    SimulatedExecutor,
    SleepingCost,
    ThreadExecutor,
    TrialJournal,
    make_executor,
    workload_key,
)
from repro.core.config_space import TilingState
from repro.core.cost.base import backend_from_spec


def _strict_loads(line):
    """json.loads that rejects the non-standard Infinity/NaN literals."""
    def _reject(const):
        raise AssertionError(f"non-strict JSON constant in journal: {const}")

    return json.loads(line, parse_constant=_reject)


@pytest.fixture(scope="module")
def space():
    return GemmConfigSpace(256, 256, 256)


@pytest.fixture(scope="module")
def states(space):
    return [space.initial_state()] + space.neighbors(space.initial_state())[:3]


# -- worker-spec protocol ------------------------------------------------------


def test_backend_spec_round_trip(space):
    cost = AnalyticalTPUCost(space, n_repeats=2, noise_sigma=0.1, seed=5)
    rebuilt = backend_from_spec(cost.worker_spec())
    for s in itertools.islice(space.enumerate(), 20):
        assert rebuilt.cost(s) == cost.cost(s)
    assert rebuilt.measure_fingerprint() == cost.measure_fingerprint()


def test_sleeping_backend_spec_round_trip(space):
    sl = SleepingCost(AnalyticalTPUCost(space), delay_s=0.0)
    rebuilt = backend_from_spec(sl.worker_spec())
    s = space.initial_state()
    assert rebuilt.cost(s) == sl.cost(s)


def test_unshippable_backend_refused(space):
    guarded = GemmConfigSpace(256, 256, 256, extra_constraint=lambda s: True)
    cost = AnalyticalTPUCost(guarded)
    assert cost.worker_spec() is None
    ex = ProcessExecutor()
    try:
        with pytest.raises(ValueError, match="worker_spec"):
            ex.run_wave(cost, [guarded.initial_state()])
    finally:
        ex.close()


@pytest.mark.slow
def test_cold_start_excluded_from_lane_timeout(space, states):
    """Worker start-up (interpreter + imports, easily seconds) must not
    eat into the per-lane measurement timeout: a tight timeout with
    cold workers still measures fine because _ensure_workers blocks
    until workers are ready."""
    sl = SleepingCost(AnalyticalTPUCost(space), delay_s=0.05)
    with ProcessExecutor(timeout_s=1.0) as ex:  # no warm_up on purpose
        eng = MeasureEngine(sl, n_workers=4, executor=ex)
        out = eng.measure_wave(states)
    assert all(o.error is None for o in out), [o.error for o in out]
    assert all(o.lane_s < 1.0 for o in out)  # lane wall is the job, not spawn


def test_tune_workload_rejects_engine_executor_conflict(tmp_path):
    from repro.core import GemmWorkload, TuningSession

    space = GemmConfigSpace(64, 64, 64)
    session = TuningSession(verbose=False)
    cost = AnalyticalTPUCost(space, n_repeats=1)
    engine = MeasureEngine(cost)
    with pytest.raises(ValueError, match="conflicts"):
        session.tune_workload(
            GemmWorkload(64, 64, 64), "g-bfs", Budget(max_trials=3),
            engine=engine, executor=SimulatedExecutor(),
        )


def test_make_executor_names():
    for name, cls_name in [("sim", "SimulatedExecutor"), ("thread", "ThreadExecutor"),
                           ("process", "ProcessExecutor")]:
        ex = make_executor(name)
        assert type(ex).__name__ == cls_name and ex.name == name
        ex.close()
    with pytest.raises(ValueError):
        make_executor("rpc")


# -- thread lanes --------------------------------------------------------------


def test_thread_executor_value_parity_and_overlap(space, states):
    """Thread lanes return the exact costs the simulated path returns,
    and genuinely overlap sleeps."""
    cost = AnalyticalTPUCost(space, n_repeats=2, noise_sigma=0.1, seed=3)
    sim = MeasureEngine(cost, n_workers=4).measure_wave(states)
    sl = SleepingCost(AnalyticalTPUCost(space, n_repeats=2, noise_sigma=0.1, seed=3),
                      delay_s=0.15)
    with ThreadExecutor() as ex:
        eng = MeasureEngine(sl, n_workers=4, executor=ex)
        t0 = time.perf_counter()
        out = eng.measure_wave(states)
        wall = time.perf_counter() - t0
    assert [o.cost for o in out] == [o.cost for o in sim]
    assert wall < len(states) * 0.15  # overlapped, not serial
    assert all(o.lane_s >= 0.15 for o in out)  # measured wall, not modeled


def test_thread_executor_isolates_raises_and_timeouts(space, states):
    bad = SleepingCost(
        AnalyticalTPUCost(space), delay_s=0.02,
        raise_keys=[states[1].key()], hang_keys=[states[2].key()], hang_s=30.0,
    )
    with ThreadExecutor(timeout_s=0.5) as ex:  # executor owns the kill timeout
        eng = MeasureEngine(bad, n_workers=4, executor=ex)
        out = eng.measure_wave([states[0], states[1], states[2]])
    assert out[0].error is None and math.isfinite(out[0].cost)
    assert math.isinf(out[1].cost) and "RuntimeError" in out[1].error
    assert math.isinf(out[2].cost) and "timeout" in out[2].error
    assert eng.stats.n_failures == 2


# -- process lanes -------------------------------------------------------------


@pytest.mark.slow
def test_process_executor_value_parity(space, states):
    cost = AnalyticalTPUCost(space, n_repeats=2, noise_sigma=0.1, seed=3)
    ref = [cost.cost(s) for s in states]
    with ProcessExecutor() as ex:
        eng = MeasureEngine(cost, n_workers=4, executor=ex)
        out = eng.measure_wave(states)
    assert [o.cost for o in out] == ref


@pytest.mark.slow
def test_process_executor_crash_and_timeout_isolation(tmp_path, space, states):
    """A worker hard-death (os._exit) or hang costs one inf trial — the
    session survives and the next wave measures normally on respawned
    workers — and executor failures are never journaled as infeasible
    configs."""
    bad = SleepingCost(
        AnalyticalTPUCost(space), delay_s=0.02,
        exit_keys=[states[1].key()], hang_keys=[states[2].key()], hang_s=30.0,
    )
    jpath = str(tmp_path / "crash.jsonl")
    wkey = workload_key(space.m, space.k, space.n, "bfloat16", "crashy")
    with ProcessExecutor(timeout_s=1.0) as ex:  # executor owns the kill timeout
        ex.warm_up(3)
        journal = TrialJournal(jpath)
        eng = MeasureEngine(bad, n_workers=3, executor=ex,
                            journal=journal, workload_key=wkey)
        out = eng.measure_wave(states[:3])
        assert out[0].error is None and math.isfinite(out[0].cost)
        assert math.isinf(out[1].cost) and "crash" in out[1].error
        assert math.isinf(out[2].cost) and "timeout" in out[2].error
        assert eng.stats.n_failures == 2
        # the genuine measurement is journaled; the crash/timeout are not
        assert journal.get(eng.journal_key, states[0].key()) is not None
        assert journal.get(eng.journal_key, states[1].key()) is None
        assert journal.get(eng.journal_key, states[2].key()) is None
        # next wave measures normally on respawned workers
        again = eng.measure_wave([states[3]])
        assert again[0].error is None and math.isfinite(again[0].cost)
        journal.close()


@pytest.mark.slow
def test_process_wave_concurrency_with_shared_journal(tmp_path, space, states):
    """Acceptance: a ProcessExecutor wave shows real wall-clock
    concurrency (N-state wave < N x single-state wall) while two engines
    append to one journal file without corrupting it."""
    delay = 0.25
    sl = SleepingCost(AnalyticalTPUCost(space), delay_s=delay)
    jpath = str(tmp_path / "shared.jsonl")
    key_a = workload_key(space.m, space.k, space.n, "bfloat16", "wave-a")
    key_b = workload_key(space.m, space.k, space.n, "bfloat16", "wave-b")
    with ProcessExecutor() as ex:
        ex.warm_up(len(states))
        journal_a = TrialJournal(jpath)
        journal_b = TrialJournal(jpath)  # second handle on the same file
        eng_a = MeasureEngine(sl, n_workers=4, executor=ex,
                              journal=journal_a, workload_key=key_a)
        eng_b = MeasureEngine(sl, n_workers=4, executor=ex,
                              journal=journal_b, workload_key=key_b)
        # serial baseline: one single-state wave at a time, warmed lanes
        t0 = time.perf_counter()
        eng_a.measure_wave([states[0]])
        single_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        wave = eng_a.measure_wave(states[1:])  # 3 fresh states, one wave
        wave_wall = time.perf_counter() - t0
        n = len(states) - 1
        assert wave_wall < n * single_wall, (
            f"no real concurrency: {n}-state wave {wave_wall:.2f}s vs "
            f"{n} x {single_wall:.2f}s serial"
        )
        assert all(o.error is None for o in wave)
        eng_b.measure_wave(states)  # interleaved appends from engine B
        journal_a.close()
        journal_b.close()
    # the shared file holds every row from both engines, all strict JSON
    merged = TrialJournal(jpath)
    jkey_a = f"{key_a}?{sl.measure_fingerprint()}"
    jkey_b = f"{key_b}?{sl.measure_fingerprint()}"
    assert merged.n_trials(jkey_a) == len(states)
    assert merged.n_trials(jkey_b) == len(states)
    with open(jpath) as f:
        raw = f.read()
    assert raw.endswith("\n")
    rows = [_strict_loads(line) for line in raw.splitlines()]
    assert len(rows) == 2 * len(states)
    # sibling visibility without re-opening: reload() merges B's rows
    journal_a2 = TrialJournal(jpath)
    assert journal_a2.get(jkey_b, states[0].key()) is not None


@pytest.mark.slow
def test_gbfs_search_identical_through_process_lanes(tmp_path):
    """End-to-end: the same G-BFS search through process lanes visits the
    same states at the same costs as the simulated engine (values never
    depend on the executor), and journals them identically."""
    space = GemmConfigSpace(128, 128, 128)
    budget = Budget(max_trials=40)

    def run(executor, jpath):
        cost = AnalyticalTPUCost(space, n_repeats=2, noise_sigma=0.1, seed=3)
        journal = TrialJournal(jpath)
        eng = MeasureEngine(
            cost, n_workers=4, executor=executor, journal=journal,
            workload_key=workload_key(space.m, space.k, space.n, "bfloat16", cost.name),
        )
        res = GBFSTuner(space, cost, seed=7).tune(budget, engine=eng)
        journal.close()
        return res

    sim = run(None, str(tmp_path / "sim.jsonl"))
    with ProcessExecutor() as ex:
        ex.warm_up(4)
        proc = run(ex, str(tmp_path / "proc.jsonl"))
    assert [t.state.key() for t in proc.trials] == [t.state.key() for t in sim.trials]
    assert [t.cost for t in proc.trials] == [t.cost for t in sim.trials]
    assert proc.best_cost == sim.best_cost
    assert proc.executor == "process" and sim.executor == "sim"
    j_sim = TrialJournal(str(tmp_path / "sim.jsonl"))
    j_proc = TrialJournal(str(tmp_path / "proc.jsonl"))
    assert len(j_sim) == len(j_proc) == 40


# -- journal: strict JSON, O_APPEND concurrency, reload ------------------------


def _journal_writer(path, wid, n_rows):
    """Child-process body for the concurrent-append stress test."""
    from repro.core.config_space import GemmConfigSpace
    from repro.core.records import TrialJournal

    space = GemmConfigSpace(64, 64, 64)
    stream = itertools.islice(space.enumerate(), n_rows)
    with TrialJournal(path) as j:
        for i, s in enumerate(stream):
            # mix finite and failed costs so both encodings hit the file
            cost = math.inf if i % 7 == 0 else 1e-4 * (wid + 1) * (i + 1)
            j.record(f"gemm/m64k64n64/bfloat16/writer{wid}", s, cost)


@pytest.mark.slow
def test_concurrent_multiprocess_appends_no_torn_rows(tmp_path):
    """N processes hammering one journal path: every row survives,
    nothing interleaves, everything is strict JSON."""
    jpath = str(tmp_path / "stress.jsonl")
    n_procs, n_rows = 4, 120
    ctx = multiprocessing.get_context(
        "forkserver" if "forkserver" in multiprocessing.get_all_start_methods()
        else "spawn"
    )
    procs = [
        ctx.Process(target=_journal_writer, args=(jpath, wid, n_rows))
        for wid in range(n_procs)
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=120)
        assert p.exitcode == 0
    with open(jpath) as f:
        raw = f.read()
    lines = raw.splitlines()
    assert len(lines) == n_procs * n_rows
    rows = [_strict_loads(line) for line in lines]
    by_writer = {}
    for row in rows:
        by_writer.setdefault(row["w"], set()).add(row["k"])
    assert all(len(keys) == n_rows for keys in by_writer.values())
    j = TrialJournal(jpath)
    assert len(j) == n_procs * n_rows
    assert len(list(j.workloads())) == n_procs
    assert all(j.n_trials(w) == n_rows for w in j.workloads())


def test_journal_inf_costs_are_strict_json(tmp_path):
    jpath = str(tmp_path / "inf.jsonl")
    space = GemmConfigSpace(4096, 4096, 4096)
    bad = TilingState((1, 1, 1, 4096), (1, 4096), (1, 4096, 1, 1))
    good = space.initial_state()
    wkey = "gemm/m4096k4096n4096/bfloat16/analytical_tpu_v5e"
    with TrialJournal(jpath) as j:
        j.record(wkey, bad, math.inf)
        j.record(wkey, good, 3.25e-3)
    with open(jpath) as f:
        rows = [_strict_loads(line) for line in f.read().splitlines()]
    assert rows[0]["c"] is None and rows[0]["fail"] is True
    assert rows[1]["c"] == 3.25e-3 and "fail" not in rows[1]
    j2 = TrialJournal(jpath)
    assert math.isinf(j2.get(wkey, bad.key()))
    assert j2.get(wkey, good.key()) == 3.25e-3
    # inf rows never become the warm-start best
    best = j2.best_state(wkey)
    assert best is not None and best[0].key() == good.key()


def test_journal_reads_legacy_infinity_rows(tmp_path):
    """Rows written by the pre-strict format (bare Infinity literal)
    still load."""
    jpath = str(tmp_path / "legacy.jsonl")
    with open(jpath, "w") as f:
        f.write('{"w": "wk", "k": "64,1,1,1|64,1|64,1,1,1", '
                '"s": [[64,1,1,1],[64,1],[64,1,1,1]], "c": Infinity}\n')
        f.write('{"w": "wk", "k": "32,2,1,1|64,1|64,1,1,1", '
                '"s": [[32,2,1,1],[64,1],[64,1,1,1]], "c": 0.5}\n')
    j = TrialJournal(jpath)
    assert math.isinf(j.get("wk", "64,1,1,1|64,1|64,1,1,1"))
    assert j.get("wk", "32,2,1,1|64,1|64,1,1,1") == 0.5


def test_journal_reload_merges_sibling_rows_and_skips_torn_tail(tmp_path):
    jpath = str(tmp_path / "j.jsonl")
    space = GemmConfigSpace(64, 64, 64)
    s0, s1 = list(itertools.islice(space.enumerate(), 2))
    j_writer = TrialJournal(jpath)
    j_reader = TrialJournal(jpath)
    j_writer.record("w", s0, 1.5)
    assert j_reader.get("w", s0.key()) is None  # not yet merged
    assert j_reader.reload() == 1
    assert j_reader.get("w", s0.key()) == 1.5
    assert j_writer.reload() == 0  # own rows dedup to nothing new
    # a torn tail (no newline) is not consumed ...
    with open(jpath, "a") as f:
        f.write('{"w":"w","k":"')
    assert j_reader.reload() == 0
    # ... until a surviving writer completes the line
    with open(jpath, "a") as f:
        f.write(f'{s1.key()}","s":{json.dumps(s1.as_lists())},"c":2.5}}\n')
    assert j_reader.reload() == 1
    assert j_reader.get("w", s1.key()) == 2.5
    j_writer.close()
    j_reader.close()


def test_journal_context_manager_closes_and_reopens(tmp_path):
    jpath = str(tmp_path / "cm.jsonl")
    space = GemmConfigSpace(64, 64, 64)
    s0, s1 = list(itertools.islice(space.enumerate(), 2))
    with TrialJournal(jpath) as j:
        j.record("w", s0, 1.0)
    assert j._fd is None  # handle released on exit
    j.record("w", s1, 2.0)  # lazily reopens
    j.close()
    assert len(TrialJournal(jpath)) == 2


def test_process_lanes_refuse_a_device_backend_on_tpu(monkeypatch):
    """One process per chip: workers run JAX on the CPU, so a backend
    that times on the device is refused when this process holds a TPU."""
    import jax

    from repro.core.cost.measured import XLATimedCost

    cost = XLATimedCost(GemmConfigSpace(64, 64, 64), n_repeats=1)
    ex = ProcessExecutor()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        with pytest.raises(ValueError, match="holds the TPU"):
            ex.run_wave(cost, [cost.space.initial_state()])
        with pytest.raises(ValueError, match="holds the TPU"):
            ex.warm_up(1, backend=cost)
        assert not ex._workers  # refused before any worker started
    finally:
        ex.close()
