"""Measurement lane executors — how a wave of candidate states actually
runs.

PR 1 gave :class:`~repro.core.measure.MeasureEngine` ``n_workers``
*simulated* lanes: the search clock compresses by the wave critical
path, but the backend work itself still runs in the calling thread.
This module makes the lane a pluggable boundary, the way TVM's tuners
ship measurement batches to an RPC/executor pool:

* :class:`SimulatedExecutor` — the PR-1 semantics, bit for bit: a
  single-miss wave takes the backend's scalar ``cost`` path, a
  multi-miss wave takes ``batch_cost``, nothing leaves the calling
  thread, and lane occupancy is *modeled* (overhead + capped runtime).
  This is the default and keeps every ``--workers 1`` parity guarantee.
* :class:`ThreadExecutor` — each lane is a thread running
  ``backend.cost``; real wall-clock overlap for backends that release
  the GIL (XLA compile/execute, sleeps).  A lane that raises is an
  ``inf``-cost outcome; a lane that exceeds the timeout is abandoned
  (the thread cannot be killed — it keeps running detached, which is
  why crash-grade isolation needs processes).

Real executors own their **kill timeout** (``timeout_s``, default 60 s):
it bounds how long a lane may *really* run before being abandoned or
killed.  This is deliberately distinct from ``MeasureEngine.timeout_s``,
which is the simulated clock's AutoTVM-style *charging cap* — a slow
config charges at most that much search clock, it is never killed for
it.  Conflating the two would kill every legitimately slow real
measurement (an XLA compile easily outlives a 4 s charging cap).
* :class:`ProcessExecutor` — each lane is a persistent worker *process*
  fed ``(backend_spec, state)`` jobs over a pipe.  The backend is
  rebuilt worker-side from ``CostBackend.worker_spec()`` and cached
  per spec, so per-job cost is one pipe round-trip.  A worker that
  raises reports the error and lives on; a worker that dies (segfault,
  ``os._exit``, OOM-kill) or blows the per-lane timeout is reaped and
  respawned, and its lane resolves to ``inf`` — a backend crash can no
  longer take down the tuning session.  Workers run JAX on the CPU: a
  chip belongs to one process, and the parent may hold it, so a backend
  that measures on the device is refused on process lanes when the
  parent's backend is a TPU.

Executors with ``real_time = True`` report *measured* per-lane wall
seconds; the engine charges those to the search clock instead of the
simulated occupancy model, so benchmark speedups separate clock
compression (simulated) from genuine parallel measurement (real).
"""

from __future__ import annotations

import abc
import dataclasses
import math
import multiprocessing
import os
import sys
import time
from typing import Optional, Sequence

from .cost.base import CostBackend, backend_from_spec
from .space import State

__all__ = [
    "LaneExecutor",
    "LaneResult",
    "SimulatedExecutor",
    "ThreadExecutor",
    "ProcessExecutor",
    "EXECUTORS",
    "make_executor",
]


@dataclasses.dataclass
class LaneResult:
    """What one measurement lane hands back for one state."""

    cost: float
    wall_s: float = 0.0  # measured lane wall time (0 under simulation)
    error: Optional[str] = None  # crash / timeout / raised-exception note
    #: build-cache counter delta this job incurred worker-side (process
    #: lanes only — in-process executors let the engine read the backend
    #: directly); see ``CostBackend.compile_stats``.
    compile: Optional[dict] = None
    #: failure taxonomy (see ``repro.core.fault``): ``"crash"`` /
    #: ``"timeout"`` / ``"spawn"`` are transient (retry-able), ``"raise"``
    #: is permanent.  ``None`` on success; executors that only set
    #: ``error`` are classified by the engine via ``classify_error``.
    kind: Optional[str] = None


class LaneExecutor(abc.ABC):
    """Runs the cache-miss portion of one measurement wave."""

    name: str = "base"
    #: True when ``LaneResult.wall_s`` is measured wall-clock the engine
    #: should charge, False when occupancy must come from the clock model.
    real_time: bool = False

    @abc.abstractmethod
    def run_wave(
        self,
        backend: CostBackend,
        states: Sequence[State],
        timeout_s: Optional[float] = None,
    ) -> list[LaneResult]:
        """Measure ``states`` (one per lane); results align with input."""

    def close(self) -> None:
        """Release lanes (threads/processes). Idempotent."""

    def __enter__(self) -> "LaneExecutor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class SimulatedExecutor(LaneExecutor):
    """The historical in-thread path: scalar ``cost`` for single-miss
    waves (n_workers=1 parity), ``batch_cost`` otherwise.  A backend
    exception is isolated per lane as a ``kind="raise"`` result rather
    than unwinding the whole tuning session — the batched path falls
    back to per-state scalar calls to attribute the raise (legal because
    ``batch_cost(states)[i] == cost(states[i])`` by contract)."""

    name = "sim"
    real_time = False

    def _lane(self, backend, s) -> LaneResult:
        try:
            return LaneResult(cost=backend.cost(s))
        except BaseException as e:  # noqa: BLE001 — lane isolation
            return LaneResult(
                cost=math.inf, error=f"{type(e).__name__}: {e}", kind="raise"
            )

    def run_wave(self, backend, states, timeout_s=None):
        if len(states) == 1:
            return [self._lane(backend, states[0])]
        try:
            costs = list(backend.batch_cost(states))
        except BaseException:  # noqa: BLE001 — re-run per lane to attribute
            return [self._lane(backend, s) for s in states]
        return [LaneResult(cost=c) for c in costs]


class ThreadExecutor(LaneExecutor):
    """One daemon thread per lane (waves are measurement-bound, so
    per-wave thread spawn is noise).  Real overlap only where the
    backend drops the GIL; a timed-out lane is abandoned — daemon
    threads mean an abandoned lane can never block interpreter
    shutdown the way a ThreadPoolExecutor's atexit join would."""

    name = "thread"
    real_time = True

    def __init__(self, timeout_s: Optional[float] = 60.0):
        self.timeout_s = timeout_s  # kill timeout; None = never abandon

    def run_wave(self, backend, states, timeout_s=None):
        import threading

        timeout = timeout_s if timeout_s is not None else self.timeout_s
        box: list[Optional[LaneResult]] = [None] * len(states)

        def lane(i: int, s: State) -> None:
            t0 = time.perf_counter()
            try:
                c = backend.cost(s)
                box[i] = LaneResult(cost=c, wall_s=time.perf_counter() - t0)
            except BaseException as e:  # noqa: BLE001 — lane isolation
                box[i] = LaneResult(
                    cost=math.inf,
                    wall_s=time.perf_counter() - t0,
                    error=f"{type(e).__name__}: {e}",
                    kind="raise",
                )

        threads = [
            threading.Thread(
                target=lane, args=(i, s), daemon=True, name=f"measure-lane-{i}"
            )
            for i, s in enumerate(states)
        ]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        results: list[LaneResult] = []
        for i, t in enumerate(threads):
            remaining = (
                None
                if timeout is None
                else max(0.0, t_start + timeout - time.perf_counter())
            )
            t.join(remaining)
            if t.is_alive():  # abandoned: its eventual box write is dropped
                results.append(
                    LaneResult(
                        cost=math.inf,
                        wall_s=time.perf_counter() - t_start,
                        error=f"lane timeout after {timeout:g}s",
                        kind="timeout",
                    )
                )
            else:
                results.append(box[i])
        return results


def _worker_main(conn) -> None:
    """Measurement worker loop: rebuild backends from specs (cached per
    spec — so a backend's warm executable cache survives across jobs),
    measure one state per job, report ``("ok", cost, wall, compile_delta)``
    or ``("err", message)``.  ``compile_delta`` is the job's increment of
    ``backend.compile_stats()`` (None for backends without a build step)
    so the engine can attribute compile-cache hits across the process
    boundary.  Runs until the sentinel ``None`` or parent death.

    JAX runs on the CPU here: a worker that tried the TPU would find it
    held by the parent, and fall back to the CPU without a word."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:  # imported with the start method's preload
        sys.modules["jax"].config.update("jax_platforms", "cpu")
    backends: dict = {}
    while True:
        try:
            job = conn.recv()
        except (EOFError, OSError):
            return
        if job is None:
            return
        if job == "ping":  # liveness probe (see ProcessExecutor.warm_up)
            conn.send("pong")
            continue
        if job[0] == "prewarm":
            # build the backend ahead of the first measurement so lane
            # wall-clocks never include the worker's jax import + backend
            # construction (see ProcessExecutor.warm_up(backend=...))
            try:
                key = repr(job[1])
                if key not in backends:
                    backends[key] = backend_from_spec(job[1])
            except BaseException:  # noqa: BLE001 — surface it on the real job
                pass
            conn.send("prewarmed")
            continue
        spec, state_lists = job
        backend, before = None, None
        try:
            key = repr(spec)
            backend = backends.get(key)
            if backend is None:
                backend = backends[key] = backend_from_spec(spec)
            before = backend.compile_stats()
            t0 = time.perf_counter()
            # the state class is op-specific: the rebuilt backend's space
            # owns the deserialization (operator-agnostic lane protocol)
            cost = backend.cost(backend.space.state_from_lists(state_lists))
            wall = time.perf_counter() - t0
            conn.send(("ok", cost, wall, _compile_delta(backend, before)))
        except BaseException as e:  # noqa: BLE001 — the worker must survive
            try:
                # compile work paid before the failure still gets
                # attributed (a raised measurement is not free)
                conn.send(
                    ("err", f"{type(e).__name__}: {e}",
                     _compile_delta(backend, before))
                )
            except (BrokenPipeError, OSError):
                return


def _compile_delta(backend, before) -> Optional[dict]:
    """Increment of ``backend.compile_stats()`` since ``before`` (None
    for backends without a build step or when stats are unreadable)."""
    if backend is None or before is None:
        return None
    try:
        after = backend.compile_stats()
        if after is None:
            return None
        return {k: after[k] - before.get(k, 0) for k in after}
    except Exception:  # noqa: BLE001 — attribution must never kill a job
        return None


class _Worker:
    """One lane: a persistent process plus its duplex pipe."""

    def __init__(self, ctx):
        self.conn, child = ctx.Pipe()
        self.proc = ctx.Process(target=_worker_main, args=(child,), daemon=True)
        self.proc.start()
        child.close()  # parent keeps only its end

    def alive(self) -> bool:
        return self.proc.is_alive()

    def kill(self) -> None:
        # idempotent: a lane may be killed at timeout AND reaped again
        # by the next wave's _ensure_workers
        try:
            self.proc.terminate()
            self.proc.join(timeout=2.0)
        except (ValueError, OSError):
            pass
        try:
            self.conn.close()
        except OSError:
            pass

    def stop(self) -> None:
        """Graceful: sentinel, short join, then terminate."""
        try:
            self.conn.send(None)
        except (BrokenPipeError, OSError):
            pass
        self.proc.join(timeout=2.0)
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(timeout=2.0)
        self.conn.close()


class ProcessExecutor(LaneExecutor):
    """Persistent worker-process lanes with per-lane timeouts and crash
    isolation (TVM's measure-worker pattern, pipes instead of RPC).

    Requires ``backend.worker_spec()`` — the backend is rebuilt inside
    each worker, never pickled.  ``mp_context`` defaults to
    ``forkserver`` where available (workers fork from a clean server
    process: no ``__main__`` re-import, and safe once JAX/XLA threads
    exist in the parent — which plain ``fork`` is not), falling back to
    ``spawn`` elsewhere.
    """

    name = "process"
    real_time = True

    def __init__(
        self,
        timeout_s: Optional[float] = 60.0,
        mp_context: Optional[str] = None,
        spawn_timeout_s: float = 120.0,
        max_respawns: int = 3,
        respawn_backoff_s: float = 0.05,
    ):
        self.timeout_s = timeout_s  # per-lane kill timeout; None = wait forever
        self.spawn_timeout_s = spawn_timeout_s
        # per-lane-slot respawn budget: after ``max_respawns`` worker
        # deaths a slot stops burning processes and degrades to the
        # in-thread (ThreadExecutor) path for the rest of the run — a
        # deterministic crasher must not respawn forever, once per wave
        self.max_respawns = max(0, int(max_respawns))
        self.respawn_backoff_s = respawn_backoff_s
        if mp_context is None:
            methods = multiprocessing.get_all_start_methods()
            mp_context = "forkserver" if "forkserver" in methods else "spawn"
        self._ctx = multiprocessing.get_context(mp_context)
        # positional lane slots: slot i keeps its respawn count across
        # worker generations (None = never spawned, or degraded)
        self._workers: list[Optional[_Worker]] = []
        self._respawns: list[int] = []
        self._degraded: set[int] = set()
        self.n_respawns = 0  # lifetime worker respawns (all slots)
        self.n_spare_adoptions = 0  # deaths absorbed by a warm spare

    def fault_stats(self) -> dict:
        """Lifetime hardening counters; the engine snapshot-diffs these
        per wave into :class:`~repro.core.measure.MeasureStats`."""
        return {
            "n_respawns": self.n_respawns,
            "n_degraded_lanes": len(self._degraded),
            "n_spare_adoptions": self.n_spare_adoptions,
        }

    def _ensure_workers(self, n: int) -> None:
        """Reap dead workers and (re)spawn slots up to ``n``, blocking
        until fresh ones answer a liveness ping — interpreter start-up
        and repro imports must never count against a lane's measurement
        timeout.  Each observed worker death consumes one respawn from
        its slot's budget, with exponential backoff between respawns;
        a slot whose budget is exhausted is degraded (logged once) and
        served in-thread by ``run_wave`` from then on."""
        while len(self._workers) < n:
            self._workers.append(None)
            self._respawns.append(0)
        fresh: list[_Worker] = []
        for i in range(n):
            if i in self._degraded:
                continue
            w = self._workers[i]
            if w is not None and w.alive():
                continue
            if w is not None:
                # an observed death: reap it and charge the slot budget
                w.kill()
                self._workers[i] = None
                self._respawns[i] += 1
                self.n_respawns += 1
                if self._respawns[i] > self.max_respawns:
                    self._degraded.add(i)
                    print(
                        f"[executor] lane {i}: worker died "
                        f"{self._respawns[i]} times (respawn budget "
                        f"{self.max_respawns} exhausted); degrading to "
                        "in-thread measurement for the rest of the run"
                    )
                    continue
                # hot-spare adoption: ``warm_up(n_lanes + spares)`` parks
                # warm workers beyond the wave; a dead lane adopts one
                # instantly instead of paying a cold interpreter start-up
                # on the respawn path (the death still charges the budget)
                for j in range(n, len(self._workers)):
                    cand = self._workers[j]
                    if j not in self._degraded and cand is not None and cand.alive():
                        self._workers[i] = cand
                        self._workers[j] = None
                        self.n_spare_adoptions += 1
                        break
                if self._workers[i] is not None:
                    continue
                if self.respawn_backoff_s > 0:
                    time.sleep(
                        self.respawn_backoff_s * (2.0 ** (self._respawns[i] - 1))
                    )
            self._workers[i] = w2 = _Worker(self._ctx)
            fresh.append(w2)
        for w in fresh:
            try:
                w.conn.send("ping")
            except (BrokenPipeError, OSError):
                pass
        deadline = time.perf_counter() + self.spawn_timeout_s
        for w in fresh:
            try:
                if w.conn.poll(max(0.0, deadline - time.perf_counter())):
                    w.conn.recv()
            except (EOFError, OSError):
                pass  # dead at birth: run_wave resolves its lane to inf

    @staticmethod
    def _refuse_device_backend(backend) -> None:
        """Workers run JAX on the CPU, so a backend that times programs
        on JAX's device would time the CPU there while this process holds
        the TPU."""
        if getattr(backend, "measured", False):
            import jax

            if jax.default_backend() == "tpu":
                raise ValueError(
                    f"backend {backend.name!r} times programs on the JAX "
                    "device, and this process holds the TPU: process lanes "
                    "run JAX on the CPU (one process per chip), so they "
                    "would time the CPU; use the sim or thread executor"
                )

    def run_wave(self, backend, states, timeout_s=None):
        import threading

        self._refuse_device_backend(backend)
        spec = backend.worker_spec()
        if spec is None:
            raise ValueError(
                f"backend {backend.name!r} has no worker_spec(); "
                "ProcessExecutor needs a process-shippable backend recipe "
                "(use ThreadExecutor or SimulatedExecutor instead)"
            )
        timeout = timeout_s if timeout_s is not None else self.timeout_s
        self._ensure_workers(len(states))
        results: list[Optional[LaneResult]] = [None] * len(states)

        # degraded slots run the ThreadExecutor path on the engine-side
        # backend, overlapping the process lanes dispatched below
        def deg_lane(box: list, s: State, t0: float) -> None:
            try:
                c = backend.cost(s)
                box[0] = LaneResult(cost=c, wall_s=time.perf_counter() - t0)
            except BaseException as e:  # noqa: BLE001 — lane isolation
                box[0] = LaneResult(
                    cost=math.inf,
                    wall_s=time.perf_counter() - t0,
                    error=f"{type(e).__name__}: {e}",
                    kind="raise",
                )

        deg: dict[int, tuple] = {}
        for i, s in enumerate(states):
            if i in self._degraded:
                box: list = [None]
                t0 = time.perf_counter()
                th = threading.Thread(
                    target=deg_lane, args=(box, s, t0), daemon=True,
                    name=f"degraded-lane-{i}",
                )
                th.start()
                deg[i] = (th, box, t0)
        sent_t: list[float] = [0.0] * len(states)
        dead_on_send: set[int] = set()
        for i, s in enumerate(states):
            if i in deg:
                continue
            w = self._workers[i]
            if w is None:
                dead_on_send.add(i)
                sent_t[i] = time.perf_counter()
                continue
            try:
                w.conn.send((spec, s.as_lists()))
            except (BrokenPipeError, OSError):
                dead_on_send.add(i)
            sent_t[i] = time.perf_counter()
        for i in range(len(states)):
            if i in deg:
                continue
            w = self._workers[i]
            if i in dead_on_send:
                if w is not None:
                    w.kill()
                results[i] = LaneResult(
                    cost=math.inf,
                    error="worker died before dispatch",
                    kind="spawn",
                )
                continue
            remaining = (
                None
                if timeout is None
                else max(0.0, sent_t[i] + timeout - time.perf_counter())
            )
            try:
                if not w.conn.poll(remaining):
                    w.kill()
                    results[i] = LaneResult(
                        cost=math.inf,
                        wall_s=time.perf_counter() - sent_t[i],
                        error=f"lane timeout after {timeout:g}s (worker killed)",
                        kind="timeout",
                    )
                    continue
                msg = w.conn.recv()
            except (EOFError, OSError):
                w.kill()
                results[i] = LaneResult(
                    cost=math.inf,
                    wall_s=time.perf_counter() - sent_t[i],
                    error="worker crashed mid-measurement",
                    kind="crash",
                )
                continue
            if msg[0] == "ok":
                results[i] = LaneResult(
                    cost=msg[1],
                    wall_s=msg[2],
                    compile=msg[3] if len(msg) > 3 else None,
                )
            else:
                results[i] = LaneResult(
                    cost=math.inf,
                    wall_s=time.perf_counter() - sent_t[i],
                    error=msg[1],
                    kind="raise",
                    compile=msg[2] if len(msg) > 2 else None,
                )
        for i, (th, box, t0) in deg.items():
            remaining = (
                None
                if timeout is None
                else max(0.0, t0 + timeout - time.perf_counter())
            )
            th.join(remaining)
            if th.is_alive():  # abandoned, same as ThreadExecutor
                results[i] = LaneResult(
                    cost=math.inf,
                    wall_s=time.perf_counter() - t0,
                    error=f"lane timeout after {timeout:g}s (degraded lane)",
                    kind="timeout",
                )
            else:
                results[i] = box[0]
        return results

    def warm_up(self, n_lanes: int, backend=None) -> None:
        """Pre-spawn ``n_lanes`` ready workers so not even the *first*
        wave's wall-clock includes process start-up (``run_wave`` already
        excludes start-up from lane timeouts via ``_ensure_workers``).

        With ``backend``, each worker also pre-builds the backend from
        its ``worker_spec()`` — the worker-side jax import, backend
        construction, and persistent-cache open all happen here instead
        of inside the first measurement wave.  Spawning more lanes than
        the wave width parks warm spares that dead lanes adopt instantly
        (see ``_ensure_workers``)."""
        if backend is not None:
            self._refuse_device_backend(backend)
        self._ensure_workers(n_lanes)
        if backend is None:
            return
        spec = backend.worker_spec()
        if spec is None:
            return
        warmed: list[_Worker] = []
        for w in self._workers[:n_lanes]:
            if w is None or not w.alive():
                continue
            try:
                w.conn.send(("prewarm", spec))
                warmed.append(w)
            except (BrokenPipeError, OSError):
                pass
        deadline = time.perf_counter() + self.spawn_timeout_s
        for w in warmed:
            try:
                if w.conn.poll(max(0.0, deadline - time.perf_counter())):
                    w.conn.recv()
            except (EOFError, OSError):
                pass  # dead during prewarm: run_wave resolves it later

    def close(self) -> None:
        workers, self._workers = self._workers, []
        self._respawns = []
        for w in workers:
            if w is None:
                continue
            if w.alive():
                w.stop()
            else:
                w.kill()


EXECUTORS = {
    "sim": SimulatedExecutor,
    "thread": ThreadExecutor,
    "process": ProcessExecutor,
}


def make_executor(name: str, **kwargs) -> LaneExecutor:
    """Build a lane executor by CLI name (``sim``/``thread``/``process``)."""
    try:
        cls = EXECUTORS[name]
    except KeyError:
        raise ValueError(f"unknown executor {name!r}; pick from {sorted(EXECUTORS)}")
    return cls(**kwargs)
