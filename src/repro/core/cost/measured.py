"""Measured cost backends — real wall-clock oracles.

The paper measures candidate configurations on real hardware (Titan Xp).
These backends time programs on JAX's default device:

* :class:`XLATimedCost` — realizes the *blocked loop structure* of a
  schedule as an XLA program and times it on the device (the CPU in the
  tests, the TPU on a chip host; the device kind is part of its records
  namespace, fingerprint and cache keys).  The per-op build recipe
  comes from the op registry (``repro.core.ops``): a tiled macro-grid
  matmul for ``gemm``, the blocked online-softmax loop for ``flash``.
  Different schedules genuinely run at different speeds, so the search
  problem is real; the program is an emulation of the kernel's
  schedule, not the Pallas kernel itself.

  Compilation — not timing, not search logic — dominates the trial cost
  of this backend, so it is engineered out of the hot path at every
  layer (the TVM line of work treats build/measure throughput as a
  first-class axis; see "Learning to Optimize Tensor Programs"):

  - an :class:`ExecutableCache` holds compiled programs behind an
    LRU-bounded in-memory layer and an optional **persistent on-disk
    layer** (JAX's AOT ``serialize_executable`` facility), content-keyed
    by ``(op, workload dims, dtype, state.key(), jax/jaxlib version)`` —
    a re-run, a sibling engine, or a worker process on the same host
    skips straight past compilation;
  - ``batch_cost`` compiles a batch's *unique* unbuilt candidates
    concurrently on a thread pool (XLA compilation releases the GIL) and
    times each unique configuration exactly once, fanning the result out
    to duplicates;
  - the backend is **process-shippable** (``worker_spec()``): process
    lanes rebuild it from a picklable recipe, each worker keeps its own
    warm executable cache across jobs, and the warmup+timed region is
    serialized across lanes by a :class:`_TimingGate` (thread lock
    in-process, ``flock`` across processes) so parallel lanes never
    contend for cores *while a measurement is being timed*.  Compiles
    still overlap — they are two orders of magnitude longer than the
    timed region, and serializing them would erase the parallel win.

* :class:`PallasInterpretCost` — times the op's actual Pallas kernel
  (via the registry's ``pallas_run`` binding) in ``interpret=True``
  mode.  Functionally faithful to the TPU kernel; timing reflects the
  interpreter, so this backend is for correctness-coupled search demos
  on small shapes, and is refused on a TPU backend.  Process-shippable
  via ``worker_spec()`` like the other backends.

Both are deliberately interchangeable with :class:`AnalyticalTPUCost`
behind the same :class:`CostBackend` protocol (DESIGN.md §2).
"""

from __future__ import annotations

import hashlib
import math
import os
import pickle
import tempfile
import threading
import time
from collections import OrderedDict
from typing import Optional


from repro.utils.device import device_kind
from repro.utils.spans import carry, span

from ..space import SearchSpace, State
from .base import CostBackend

__all__ = ["XLATimedCost", "PallasInterpretCost", "ExecutableCache"]


class _TimingGate:
    """Serializes the warmup+timed region of a measurement: a thread lock
    covers lanes sharing one backend object (ThreadExecutor), an
    exclusive ``flock`` on ``lock_path`` covers sibling worker processes
    (ProcessExecutor).  Held only around execution — compilation stays
    parallel."""

    def __init__(self, lock_path: Optional[str] = None):
        self.lock_path = lock_path
        self._tlock = threading.Lock()
        self._fd: Optional[int] = None

    def _flock(self, exclusive: bool) -> None:
        try:
            import fcntl
        except ImportError:  # non-POSIX: thread lock only
            return
        try:
            if self._fd is None:
                d = os.path.dirname(os.path.abspath(self.lock_path))
                os.makedirs(d, exist_ok=True)
                self._fd = os.open(self.lock_path, os.O_CREAT | os.O_RDWR, 0o644)
            fcntl.flock(self._fd, fcntl.LOCK_EX if exclusive else fcntl.LOCK_UN)
        except OSError:
            pass  # lock file unusable: measure anyway, just unserialized

    def __enter__(self) -> "_TimingGate":
        self._tlock.acquire()
        if self.lock_path is not None:
            self._flock(exclusive=True)
        return self

    def __exit__(self, *exc) -> None:
        try:
            if self.lock_path is not None and self._fd is not None:
                self._flock(exclusive=False)
        finally:
            self._tlock.release()


class ExecutableCache:
    """Two-layer compiled-program cache for :class:`XLATimedCost`.

    * **memory** — an LRU of loaded executables, bounded by ``capacity``
      so a long ``tune_arch`` run over many shapes cannot grow without
      limit;
    * **disk** (optional) — serialized executables under ``cache_dir``
      via JAX's AOT ``serialize_executable`` facility, content-keyed so
      one directory safely serves every shape/dtype/version.  Writes are
      atomic (tmp + rename), so sibling processes can share the
      directory; a corrupt or version-mismatched entry silently falls
      back to a fresh compile.

    Counters (``stats()``) feed ``MeasureStats``/``BENCH_measure.json``:
    ``compiles``, ``mem_hits``, ``disk_hits``, ``evictions``,
    ``compile_s`` (seconds spent compiling), ``n_timed`` (maintained by
    the backend: how many timed executions actually ran).
    """

    def __init__(self, capacity: int = 512, cache_dir: Optional[str] = None):
        self.capacity = max(1, int(capacity))
        self.cache_dir = cache_dir
        self._mem: OrderedDict[str, object] = OrderedDict()
        self._lock = threading.Lock()
        self.counters = {
            "compiles": 0,
            "mem_hits": 0,
            "disk_hits": 0,
            "evictions": 0,
            "compile_s": 0.0,
            "n_timed": 0,
        }

    # -- key/paths -----------------------------------------------------------
    @staticmethod
    def content_key(
        space: SearchSpace, dtype: str, state: State, flavor: str = ""
    ) -> str:
        """Content key: the compiled program is fully determined by the
        op, its workload dims, dtype, schedule state, the device kind it
        was compiled for, and the jax/jaxlib (XLA) version that produced
        it.  The op field keeps one shared
        cache directory safe across operators; ``flavor`` separates
        program families that would otherwise collide on the same
        (op, dims, state) — e.g. the interpret-mode Pallas program and
        the plain-XLA timed program of the same schedule.  The default
        "" adds nothing, so pre-flavor XLATimedCost disk caches
        survive."""
        import jax
        import jaxlib

        op = getattr(space, "op", "gemm")
        dims = "x".join(map(str, space.dims))
        # non-default space construction kwargs (e.g. flash's causal
        # flag) change the compiled program: fold them into the key.
        # Empty kwargs add nothing, so pre-registry GEMM keys survive.
        kw = getattr(space, "spec_kwargs", dict)() or {}
        extra = "".join(f"/{k}={v!r}" for k, v in sorted(kw.items()))
        fl = f"/{flavor}" if flavor else ""
        raw = (
            f"{op}/{dims}/{dtype}/{state.key()}{extra}{fl}/{device_kind()}"
            f"/jax{jax.__version__}/jaxlib{jaxlib.__version__}"
        )
        return hashlib.sha256(raw.encode()).hexdigest()[:40]

    def _path(self, ckey: str) -> str:
        return os.path.join(self.cache_dir, f"{ckey}.xlaexec")

    # -- layers --------------------------------------------------------------
    def peek(self, ckey: str) -> bool:
        """Uncounted membership probe of the memory layer (used to skip
        already-built states without charging a hit event)."""
        with self._lock:
            return ckey in self._mem

    def get_mem(self, ckey: str, count: bool = True):
        with self._lock:
            fn = self._mem.get(ckey)
            if fn is not None:
                self._mem.move_to_end(ckey)
                if count:
                    self.counters["mem_hits"] += 1
            return fn

    def count_mem_hit(self) -> None:
        with self._lock:
            self.counters["mem_hits"] += 1

    def put_mem(self, ckey: str, fn) -> None:
        with self._lock:
            self._mem[ckey] = fn
            self._mem.move_to_end(ckey)
            while len(self._mem) > self.capacity:
                self._mem.popitem(last=False)
                self.counters["evictions"] += 1

    def get_disk(self, ckey: str):
        """Deserialize a previously-persisted executable, or None."""
        if self.cache_dir is None:
            return None
        path = self._path(ckey)
        if not os.path.exists(path):
            return None
        try:
            from jax.experimental import serialize_executable

            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            fn = serialize_executable.deserialize_and_load(payload, in_tree, out_tree)
        except Exception:  # corrupt / version drift: recompile instead
            return None
        with self._lock:
            self.counters["disk_hits"] += 1
        return fn

    def put_disk(self, ckey: str, compiled) -> None:
        if self.cache_dir is None:
            return
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(compiled)
            os.makedirs(self.cache_dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.cache_dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    pickle.dump((payload, in_tree, out_tree), f)
                os.replace(tmp, self._path(ckey))  # atomic publish
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        except Exception:
            pass  # persistence is an optimization, never a failure mode

    def count_compile(self, seconds: float) -> None:
        with self._lock:
            self.counters["compiles"] += 1
            self.counters["compile_s"] += seconds

    def count_timed(self) -> None:
        with self._lock:
            self.counters["n_timed"] += 1

    def stats(self) -> dict:
        with self._lock:
            return dict(self.counters)

    def __len__(self) -> int:
        return len(self._mem)


def _xla_timed_from_spec(
    op: str, dims: list, depths: list, space_kwargs: dict,
    n_repeats: int, dtype: str, vmem_guard_bytes: int, seed: int,
    n_build_workers: int, cache_dir: Optional[str],
    cache_capacity: int, timing_lock_path: Optional[str],
) -> "XLATimedCost":
    """Worker-process factory (see ``CostBackend.worker_spec``)."""
    from ..ops import get_op

    return XLATimedCost(
        get_op(op).make_space(tuple(dims), tuple(depths), **space_kwargs),
        n_repeats=n_repeats,
        dtype=dtype,
        vmem_guard_bytes=vmem_guard_bytes,
        seed=seed,
        n_build_workers=n_build_workers,
        cache_dir=cache_dir,
        cache_capacity=cache_capacity,
        timing_lock_path=timing_lock_path,
    )


class XLATimedCost(CostBackend):
    """Times the op's XLA realization of a schedule on JAX's device; its
    ``name`` (the records namespace) carries the device kind:
    ``xla_cpu_timed`` on the CPU, ``xla_tpu_v5_lite_timed`` on a v5e."""

    measured = True

    def __init__(
        self,
        space: SearchSpace,
        n_repeats: int = 3,
        dtype: str = "float32",
        vmem_guard_bytes: int = 16 * 1024 * 1024,
        seed: int = 0,
        n_build_workers: int = 4,
        cache_dir: Optional[str] = None,
        cache_capacity: int = 512,
        timing_lock_path: Optional[str] = None,
    ):
        super().__init__(space, n_repeats)
        import jax
        import jax.numpy as jnp

        from ..ops import get_op  # lazy: the registry imports cost modules

        self._jax, self._jnp = jax, jnp
        self.device_kind = device_kind()
        self.name = f"xla_{self.device_kind.lower().replace(' ', '_')}_timed"
        self.dtype = dtype
        self.vmem_guard_bytes = vmem_guard_bytes
        self.seed = seed
        self.n_build_workers = max(1, n_build_workers)
        # the op binding supplies the operands and the per-state timed
        # program -- this backend is build-recipe-agnostic
        self._opspec = get_op(self.op)
        self._args = self._opspec.timed_operands(space, dtype, seed)
        self.cache = ExecutableCache(capacity=cache_capacity, cache_dir=cache_dir)
        if timing_lock_path is None and cache_dir is not None:
            timing_lock_path = os.path.join(cache_dir, ".timing.lock")
        self.timing_lock_path = timing_lock_path
        self._gate = _TimingGate(timing_lock_path)

    # -- build ---------------------------------------------------------------
    def _build(self, s: State):
        """Lower + AOT-compile the op's timed program for ``s`` (cold
        path) -- the traceable realization of the schedule comes from the
        op registry's ``timed_fn`` binding."""
        fn = self._opspec.timed_fn(self.space, s, self.dtype)
        with span("measure.compile") as sp:
            compiled = self._jax.jit(fn).lower(*self._args).compile()
        self.cache.count_compile(sp.seconds)
        return compiled

    def _fits_vmem(self, s: State) -> bool:
        # Honor the TPU VMEM legitimacy constraint so the searched space
        # matches what the Pallas kernel would accept on hardware.
        itemsize = self._jnp.dtype(self.dtype).itemsize
        return (
            self.space.working_set_bytes(s, itemsize) <= self.vmem_guard_bytes
        )

    def _ensure(self, s: State, count_mem_hit: bool = True):
        """Resolve the executable for ``s``: in-memory LRU, then the
        persistent disk layer, then a fresh compile (persisted for the
        next session/worker).  Disk loads and compiles are warmed with
        one untimed call before entering the memory layer.

        ``count_mem_hit=False`` suppresses the memory-layer hit counter
        for resolves whose trial already charged its cache event (the
        batch path counts exactly one event per unique trial)."""
        ckey = ExecutableCache.content_key(self.space, self.dtype, s)
        fn = self.cache.get_mem(ckey, count=count_mem_hit)
        if fn is not None:
            return fn
        with span("measure.load"):
            fn = self.cache.get_disk(ckey)
        if fn is None:
            fn = self._build(s)
            self.cache.put_disk(ckey, fn)
        # warmup: never timed, but gated — a warm run on the cores would
        # contend with a sibling lane's in-flight timed region
        with span("measure.warmup"), self._gate:
            fn(*self._args).block_until_ready()
        self.cache.put_mem(ckey, fn)
        return fn

    def _timed_mean(self, fn) -> float:
        """``n_repeats`` gated timed runs of a resolved executable; the
        gate keeps sibling lanes (threads sharing this backend, worker
        processes sharing the lock file) off the cores while a
        measurement is on the clock."""
        total = 0.0
        with span("measure.timed"):
            for _ in range(self.n_repeats):
                with self._gate:
                    t0 = time.perf_counter()
                    fn(*self._args).block_until_ready()
                    total += time.perf_counter() - t0
                self.cache.count_timed()
        return total / self.n_repeats

    def cost(self, s: State) -> float:
        # resolve once per *trial* (not per repeat): the cache counters
        # feed compile_cache_hit_rate, which must mean "fraction of
        # trials served without a fresh compile"
        if not self.space.is_legitimate(s) or not self._fits_vmem(s):
            return math.inf
        return self._timed_mean(self._ensure(s))

    def cost_once(self, s: State, repeat_idx: int) -> float:
        # kept for the CostBackend protocol; cost() bypasses it so the
        # executable resolve (and its counters) happen once per trial
        if not self._fits_vmem(s):
            return math.inf
        fn = self._ensure(s)
        with span("measure.timed"), self._gate:
            t0 = time.perf_counter()
            fn(*self._args).block_until_ready()
            dt = time.perf_counter() - t0
        self.cache.count_timed()
        return dt

    def batch_cost(self, states) -> list[float]:
        """Compile the batch's *unique* unbuilt candidates on a thread
        pool (XLA compilation releases the GIL), then time each unique
        configuration once — serially, so timing never contends for
        cores — and fan results out to duplicates.  Exactly one cache
        event is counted per unique measurable state: a mem hit for
        already-built ones, a disk hit or compile for the rest (charged
        inside the prefetch)."""
        from concurrent.futures import ThreadPoolExecutor

        states = list(states)
        todo, seen = [], set()
        for s in states:
            key = s.key()
            if (
                key not in seen
                and self.space.is_legitimate(s)
                and self._fits_vmem(s)
            ):
                seen.add(key)
                ckey = ExecutableCache.content_key(self.space, self.dtype, s)
                if self.cache.peek(ckey):
                    self.cache.count_mem_hit()  # warm trial: one event
                else:
                    todo.append(s)
        if len(todo) > 1:
            workers = min(self.n_build_workers, len(todo))
            with ThreadPoolExecutor(max_workers=workers) as ex:
                # the prefetch charges the trial's disk-hit/compile event
                ensure = carry(self._ensure)  # pool spans join this root
                for fut in [ex.submit(ensure, s, False) for s in todo]:
                    fut.result()
            todo = []
        by_key: dict[str, float] = {}
        out: list[float] = []
        single = {s.key() for s in todo}  # <2 misses: cost() charges it
        for s in states:
            key = s.key()
            if key not in by_key:
                if not self.space.is_legitimate(s) or not self._fits_vmem(s):
                    by_key[key] = math.inf
                elif key in single:
                    by_key[key] = self.cost(s)
                else:
                    by_key[key] = self._timed_mean(
                        self._ensure(s, count_mem_hit=False)
                    )
            out.append(by_key[key])
        return out

    # -- CostBackend protocol ------------------------------------------------
    def measure_fingerprint(self) -> str:
        # seed fixes the operand contents; dtype changes the program;
        # the device kind says what the seconds were measured on
        return (
            f"r{self.n_repeats}|{self.dtype}|seed{self.seed}"
            f"|{self.device_kind}" + self.space_fingerprint()
        )

    def compile_stats(self) -> Optional[dict]:
        return self.cache.stats()

    def worker_spec(self):
        space_kwargs = self.space.spec_kwargs()
        if space_kwargs is None:
            # arbitrary closures don't survive the spec round-trip;
            # refuse to ship rather than search a subtly different space
            return None
        dims = self.space.dims
        lock = self.timing_lock_path
        if lock is None:
            # all workers rebuilt from this spec must share one gate so
            # their timed regions serialize; derive a stable path from
            # the measurement identity
            digest = hashlib.sha256(
                f"{self.op}/{'x'.join(map(str, dims))}"
                f"/{self.dtype}/s{self.seed}/{os.getpid()}".encode()
            ).hexdigest()[:16]
            lock = os.path.join(
                tempfile.gettempdir(), f"repro-xla-timing-{digest}.lock"
            )
        return (
            "repro.core.cost.measured:_xla_timed_from_spec",
            {
                "op": self.op, "dims": list(dims),
                "depths": list(self.space.depths),
                "space_kwargs": space_kwargs,
                "n_repeats": self.n_repeats,
                "dtype": self.dtype,
                "vmem_guard_bytes": self.vmem_guard_bytes,
                "seed": self.seed,
                "n_build_workers": self.n_build_workers,
                "cache_dir": self.cache.cache_dir,
                "cache_capacity": self.cache.capacity,
                "timing_lock_path": lock,
            },
        )


def _pallas_interpret_from_spec(
    op: str, dims: list, depths: list, space_kwargs: dict,
    n_repeats: int, seed: int,
    cache_dir: Optional[str] = None, cache_capacity: int = 128,
) -> "PallasInterpretCost":
    """Worker-process factory (see ``CostBackend.worker_spec``)."""
    from ..ops import get_op

    return PallasInterpretCost(
        get_op(op).make_space(tuple(dims), tuple(depths), **space_kwargs),
        n_repeats=n_repeats,
        seed=seed,
        cache_dir=cache_dir,
        cache_capacity=cache_capacity,
    )


class PallasInterpretCost(CostBackend):
    """Times the op's *actual Pallas kernel* in ``interpret=True`` mode,
    via the op registry's ``pallas_run`` binding (``repro.kernels.gemm``
    for GEMM, ``repro.kernels.flash_attention`` for flash).  Process-
    shippable like the other backends: ``worker_spec()`` ships the op
    name + dims, and the worker rebuilds space and operands from the
    registry.

    Each candidate program is AOT-compiled once and resolved through the
    same two-layer :class:`ExecutableCache` that backs
    :class:`XLATimedCost` — repeats time a pre-compiled executable (one
    uncounted warm run first), so trace/lower overhead never pollutes
    the measurement and a ``cache_dir`` lets interpret-mode lanes and
    later sessions replay prior compiles from disk.  Cache entries carry
    a ``"pallas_interpret"`` flavor so they can share a directory with
    XLATimedCost programs of the same schedule without collision."""

    name = "pallas_interpret_timed"
    measured = True
    _FLAVOR = "pallas_interpret"

    def __init__(
        self,
        space: SearchSpace,
        n_repeats: int = 1,
        seed: int = 0,
        cache_dir: Optional[str] = None,
        cache_capacity: int = 128,
    ):
        super().__init__(space, n_repeats)
        import jax

        from repro.kernels import check_interpret

        from ..ops import get_op  # lazy: the registry imports cost modules

        check_interpret(True)  # timing the interpreter on a TPU is refused
        self._jax = jax
        self.seed = seed
        self._opspec = get_op(self.op)
        if self._opspec.pallas_run is None:
            raise ValueError(f"op {self.op!r} has no Pallas kernel binding")
        self._args = self._opspec.timed_operands(space, "float32", seed)
        self.cache = ExecutableCache(capacity=cache_capacity, cache_dir=cache_dir)
        self._bad: set[str] = set()  # schedules the kernel refused at trace

    def _ensure(self, s: State):
        """Resolve the interpret-mode executable for ``s``: memory LRU,
        then the persistent disk layer, then a fresh AOT compile.  Fresh
        loads get one uncounted warm run before entering the memory
        layer.  Raises ValueError when the kernel refuses the
        schedule."""
        ckey = ExecutableCache.content_key(
            self.space, "float32", s, flavor=self._FLAVOR
        )
        fn = self.cache.get_mem(ckey)
        if fn is not None:
            return fn
        with span("measure.load"):
            fn = self.cache.get_disk(ckey)
        if fn is None:
            traced = lambda *ops: self._opspec.pallas_run(
                self.space, s, ops, interpret=True
            )
            with span("measure.compile") as sp:
                fn = self._jax.jit(traced).lower(*self._args).compile()
            self.cache.count_compile(sp.seconds)
            self.cache.put_disk(ckey, fn)
        with span("measure.warmup"):
            fn(*self._args).block_until_ready()  # never timed
        self.cache.put_mem(ckey, fn)
        return fn

    def cost_once(self, s: State, repeat_idx: int) -> float:
        skey = s.key()
        if skey in self._bad:
            return math.inf
        try:
            fn = self._ensure(s)
        except ValueError:  # schedule the kernel refuses (bad blocks)
            self._bad.add(skey)
            return math.inf
        with span("measure.timed"):
            t0 = time.perf_counter()
            fn(*self._args).block_until_ready()
            dt = time.perf_counter() - t0
        self.cache.count_timed()
        return dt

    def measure_fingerprint(self) -> str:
        # "aot1": repeats time a pre-compiled executable (trace/lower
        # excluded) — values are incommensurable with pre-AOT journal
        # entries, so the fingerprint must not match them.  seed fixes
        # the operand contents.
        return (
            f"r{self.n_repeats}|aot1|seed{self.seed}"
            + self.space_fingerprint()
        )

    def compile_stats(self) -> Optional[dict]:
        return self.cache.stats()

    def worker_spec(self):
        space_kwargs = self.space.spec_kwargs()
        if space_kwargs is None:
            # constraint closures don't survive the spec round-trip
            return None
        return (
            "repro.core.cost.measured:_pallas_interpret_from_spec",
            {
                "op": self.op, "dims": list(self.space.dims),
                "depths": list(self.space.depths),
                "space_kwargs": space_kwargs,
                "n_repeats": self.n_repeats,
                "seed": self.seed,
                "cache_dir": self.cache.cache_dir,
                "cache_capacity": self.cache.capacity,
            },
        )
