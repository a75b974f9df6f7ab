"""XLATimedCost hot path: persistent executable cache (memory LRU +
on-disk layer), batch dedup, process-shippable worker spec, and the
compile-stat attribution the engine folds into MeasureStats."""

import math

import pytest

from repro.core import (
    GemmConfigSpace,
    MeasureEngine,
    ProcessExecutor,
)
from repro.core.cost.base import backend_from_spec
from repro.core.cost.measured import ExecutableCache, XLATimedCost


@pytest.fixture(scope="module")
def space():
    return GemmConfigSpace(64, 64, 64)


@pytest.fixture(scope="module")
def states(space):
    return [space.initial_state()] + space.neighbors(space.initial_state())[:2]


def test_worker_spec_refused_with_extra_constraint():
    guarded = GemmConfigSpace(64, 64, 64, extra_constraint=lambda s: True)
    assert XLATimedCost(guarded, n_repeats=1).worker_spec() is None


def test_content_key_covers_dims_dtype_state_and_version(space):
    s = space.initial_state()
    k1 = ExecutableCache.content_key(space, "float32", s)
    assert k1 == ExecutableCache.content_key(space, "float32", s)  # stable
    assert k1 != ExecutableCache.content_key(space, "float64", s)
    other = GemmConfigSpace(128, 128, 128)
    assert k1 != ExecutableCache.content_key(other, "float32", other.initial_state())


@pytest.mark.slow
def test_worker_spec_round_trip(tmp_path, space, states):
    cost = XLATimedCost(space, n_repeats=1, seed=4,
                        cache_dir=str(tmp_path / "xc"))
    spec = cost.worker_spec()
    assert spec is not None
    rebuilt = backend_from_spec(spec)
    assert rebuilt.measure_fingerprint() == cost.measure_fingerprint()
    assert rebuilt.cache.cache_dir == cost.cache.cache_dir
    # every worker rebuilt from the spec shares one timing-gate lock file
    assert rebuilt.timing_lock_path == cost.timing_lock_path
    c = rebuilt.cost(states[0])
    assert 0 < c < 10


@pytest.mark.slow
def test_batch_cost_times_each_unique_state_once(space, states):
    cost = XLATimedCost(space, n_repeats=1)
    s0, s1 = states[0], states[1]
    out = cost.batch_cost([s0, s1, s0, s0])
    stats = cost.compile_stats()
    assert stats["compiles"] == 2  # two unique states, two builds
    assert stats["n_timed"] == 2  # duplicates fanned out, never re-timed
    assert out[0] == out[2] == out[3]
    assert all(map(math.isfinite, out))


@pytest.mark.slow
def test_persistent_cache_warm_restart_zero_compiles(tmp_path, space, states):
    """A second 'session' (fresh backend, same cache dir) is served
    entirely by the on-disk layer — cold-start compilation is paid once
    ever, not once per session — and the engine attributes it."""
    cdir = str(tmp_path / "xc")
    eng1 = MeasureEngine(XLATimedCost(space, n_repeats=1, cache_dir=cdir),
                         n_workers=1)
    for s in states:
        eng1.measure_wave([s])
    assert eng1.stats.n_compiles == len(states)
    assert eng1.stats.compile_cache_hit_rate() == 0.0

    eng2 = MeasureEngine(XLATimedCost(space, n_repeats=1, cache_dir=cdir),
                         n_workers=1)
    out = [eng2.measure_wave([s])[0] for s in states]
    assert eng2.stats.n_compiles == 0
    assert eng2.stats.n_compile_disk_hits == len(states)
    assert eng2.stats.compile_cache_hit_rate() == 1.0
    assert all(math.isfinite(o.cost) and o.cost > 0 for o in out)


@pytest.mark.slow
def test_lru_cap_bounds_memory_and_counts_evictions(space, states):
    """capacity=1 with no disk layer: revisiting an evicted state pays a
    recompile, and the eviction counters expose it."""
    cost = XLATimedCost(space, n_repeats=1, cache_capacity=1)
    s0, s1 = states[0], states[1]
    for s in (s0, s1, s0):
        cost.cost(s)
    stats = cost.compile_stats()
    assert stats["evictions"] >= 2
    assert stats["compiles"] == 3  # s0 recompiled after eviction
    assert len(cost.cache) <= 1


@pytest.mark.slow
def test_lru_eviction_with_disk_layer_rehydrates_without_compile(
    tmp_path, space, states
):
    cost = XLATimedCost(space, n_repeats=1, cache_capacity=1,
                        cache_dir=str(tmp_path / "xc"))
    s0, s1 = states[0], states[1]
    for s in (s0, s1, s0):
        cost.cost(s)
    stats = cost.compile_stats()
    assert stats["compiles"] == 2  # evicted s0 came back from disk
    assert stats["disk_hits"] == 1


@pytest.mark.slow
def test_sim_vs_process_value_parity(tmp_path, space, states):
    """Process lanes time the same programs the in-process path times:
    finite costs for the same states, compile-cache attribution shipped
    back across the process boundary, and the shared disk cache means
    the workers never recompile what the parent already built."""
    cdir = str(tmp_path / "xc")
    sim_cost = XLATimedCost(space, n_repeats=1, cache_dir=cdir)
    sim_eng = MeasureEngine(sim_cost, n_workers=len(states))
    sim_out = sim_eng.measure_wave(states)
    assert all(math.isfinite(o.cost) and o.cost > 0 for o in sim_out)

    proc_cost = XLATimedCost(space, n_repeats=1, cache_dir=cdir)
    with ProcessExecutor() as ex:
        ex.warm_up(2)
        eng = MeasureEngine(proc_cost, n_workers=2, executor=ex)
        proc_out = []
        for i in range(0, len(states), 2):
            proc_out.extend(eng.measure_wave(states[i : i + 2]))
    assert [o.state.key() for o in proc_out] == [o.state.key() for o in sim_out]
    assert all(o.error is None for o in proc_out)
    assert all(math.isfinite(o.cost) and o.cost > 0 for o in proc_out)
    # worker-side compile deltas made it back: all disk hits, no compiles
    assert eng.stats.n_compiles == 0
    assert eng.stats.n_compile_disk_hits == len(states)
    assert eng.stats.compile_cache_hit_rate() == 1.0


def test_vmem_guard_is_inf_without_compiling():
    big = GemmConfigSpace(4096, 4096, 4096)
    cost = XLATimedCost(big, n_repeats=1)
    from repro.core.config_space import TilingState

    bad = TilingState((1, 1, 1, 4096), (1, 4096), (1, 4096, 1, 1))
    assert math.isinf(cost.cost(bad))
    assert cost.compile_stats()["compiles"] == 0


def test_device_kind_scopes_keys_and_namespace(space, monkeypatch):
    """What one device measured or compiled never answers on another."""
    import repro.core.cost.measured as measured

    s = space.initial_state()
    cpu_key = ExecutableCache.content_key(space, "float32", s)
    cpu = XLATimedCost(space, n_repeats=1)
    assert cpu.name == "xla_cpu_timed"
    monkeypatch.setattr(measured, "device_kind", lambda: "TPU v5 lite")
    assert ExecutableCache.content_key(space, "float32", s) != cpu_key
    tpu = XLATimedCost(space, n_repeats=1)
    assert tpu.name == "xla_tpu_v5_lite_timed"
    assert tpu.measure_fingerprint() != cpu.measure_fingerprint()


def test_measured_search_starts_at_the_heuristic_blocks():
    """From the untiled s0 a measured trial loops once per element; a
    measured backend's search starts where dispatch runs without a
    record, the analytical oracle's keeps the paper's s0."""
    from repro.core import Budget, TuningSession, Workload
    from repro.core.ops import heuristic_state

    wl = Workload("gemm", (512, 1024, 512), dtype="float32")
    space = wl.space()
    measured = TuningSession(
        cost_factory=lambda sp: XLATimedCost(sp, n_repeats=1), verbose=False
    ).tune_workload(wl, "g-bfs", Budget(max_trials=1))
    assert measured.trials[0].state == heuristic_state(space)
    assert measured.trials[0].state.grid == (2, 2, 2)  # blocks (256, 512, 256)
    oracle = TuningSession(verbose=False).tune_workload(
        wl, "g-bfs", Budget(max_trials=1)
    )
    assert oracle.trials[0].state == space.initial_state()


def test_compile_cache_helper(monkeypatch):
    """The CLIs keep JAX's compilation cache at one fixed path in the
    checkout unless JAX_COMPILATION_CACHE_DIR names one or the cache is
    off; nothing here turns the real cache on."""
    import types

    import jax

    from repro.utils import device

    calls = []
    fake = types.SimpleNamespace(
        jax_enable_compilation_cache=True,
        update=lambda *a: calls.append(a),
    )
    monkeypatch.setattr(jax, "config", fake)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    device.enable_compile_cache()
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fake.jax_enable_compilation_cache = False
    device.enable_compile_cache()
    assert calls == []
    fake.jax_enable_compilation_cache = True
    device.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", str(device.COMPILE_CACHE_DIR))]
    assert device.COMPILE_CACHE_DIR.name == ".jax_cache"
    assert (device.COMPILE_CACHE_DIR.parent / "src" / "repro").is_dir()
