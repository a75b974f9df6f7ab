"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
idle gaps and kernel time.

Device planes are named ``/device:TPU:<n>``; on each, the line ``XLA
Ops`` holds one event per operation that ran, and ``XLA Modules`` one
per executable.  Busy time is the union of the op intervals; idle gaps
are the holes in that union inside the traced window.  Host planes
(``/host:CPU``) tell what the host was doing in a gap.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

from . import spec

__all__ = ["Event", "Trace", "load", "union_ns", "find_xplane"]

_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def stat(self, key: str, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


@dataclasses.dataclass
class Trace:
    #: device plane name -> line name -> events, in time order
    device: dict
    #: host thread name -> events, in time order
    host: dict

    def lines(self, line: str) -> dict:
        return {p: lines.get(line, []) for p, lines in self.device.items()}

    def ops(self) -> dict:
        return self.lines("XLA Ops")

    def modules(self) -> dict:
        return self.lines("XLA Modules")

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        ops = self.ops()
        if not ops:
            return 0.0
        return sum(union_ns((e.start_ns, e.end_ns) for e in evs) for evs in ops.values()) / len(ops) / 1e9

    def span_s(self) -> float:
        """From the first device operation's start to the last one's end."""
        evs = [e for v in self.ops().values() for e in v]
        if not evs:
            return 0.0
        return (max(e.end_ns for e in evs) - min(e.start_ns for e in evs)) / 1e9

    def op_seconds(self) -> dict:
        """Device seconds per HLO instruction (summed over devices and
        over its runs), keyed by a short name: the instruction, its
        operation and its operand shapes.  Loops and calls, whose events
        span the ops inside them, are left out."""
        out: collections.Counter = collections.Counter()
        for evs in self.ops().values():
            for e in evs:
                name = short_name(e.name)
                if not _CONTAINER.match(name):
                    out[name] += e.dur_ns / 1e9
        return dict(out)

    def idle_gaps(self, limit: int = 10) -> list:
        """The longest holes between device operations on the first
        device, each named after the host event that covered its middle."""
        ops = self.ops()
        if not ops:
            return []
        evs = sorted(next(iter(ops.values())), key=lambda e: e.start_ns)
        gaps, end = [], None
        for e in evs:
            if end is not None and e.start_ns > end:
                gaps.append((end, e.start_ns))
            end = e.end_ns if end is None else max(end, e.end_ns)
        gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
        return [[self._host_at((a + b) / 2), (b - a) / 1e9] for a, b in gaps[:limit]]

    def _host_at(self, t: float) -> str:
        best: Optional[Event] = None
        for evs in self.host.values():
            for e in evs:
                if e.start_ns <= t <= e.end_ns and e.dur_ns > 0:
                    if best is None or e.dur_ns < best.dur_ns:
                        best = e
        return best.name if best is not None else "(no host event)"


def short_name(hlo: str) -> str:
    """``%gemm_pallas.3 custom-call(8192x4096,4096x512)`` from an op
    event's HLO text."""
    head, _, rest = hlo.partition(" = ")
    op = re.search(r"\s([a-z][a-z0-9\-]*)\(", rest)
    if op is None:
        return head
    args = _ARGS_END.split(rest[op.end():], 1)[0]
    shapes = ",".join("x".join(m.group(1).split(",")) for m in _OPERAND.finditer(args))
    return f"{head} {op.group(1)}({shapes})"


def union_ns(intervals: Iterable[tuple]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line) -> list:
    out = []
    for ev in line.events:
        try:
            stats = tuple((k, v) for k, v in ev.stats)
        except (TypeError, ValueError):
            stats = ()
        out.append(Event(ev.name, float(ev.start_ns), float(ev.duration_ns), stats))
    out.sort(key=lambda e: e.start_ns)
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, {}
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            device[plane.name] = {line.name: _events(line) for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host[f"{plane.name}/{line.name}"] = _events(line)
    return Trace(device, host)


def describe(path: str, top: int = 40) -> dict:
    """A summary of every plane and line, for reading a trace by hand."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = _events(line)
            by = collections.defaultdict(lambda: [0, 0.0, None])
            for e in evs:
                r = by[e.name]
                r[0] += 1
                r[1] += e.dur_ns / 1e9
                if r[2] is None:
                    r[2] = [[k, str(v)[:300]] for k, v in e.stats]
            names = sorted(by.items(), key=lambda kv: -kv[1][1])[:top]
            lines[line.name] = {
                "n": len(evs),
                "first_ns": evs[0].start_ns if evs else None,
                "last_ns": evs[-1].end_ns if evs else None,
                "top": [[n, c, s, st] for n, (c, s, st) in names],
            }
        out[plane.name] = lines
    return out


# -- kernels ------------------------------------------------------------------

#: the HLO instruction of each Pallas kernel is named after its
#: ``pallas_call`` (``%gemm_pallas.3 = bf16[...] custom-call(...)``), whose
#: name each kernel kind's file in ``launches/`` gives
_KERNELS = {
    kind: re.compile(rf"^%{re.escape(name)}(?:\.\d+)? = .*custom-call\(")
    for name, kind in spec.launch_kinds().items()
}
_OPERAND = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
#: ops whose events enclose other ops' events
_CONTAINER = re.compile(r"^\S+ (while|conditional|call)\(")
#: where an instruction's operand list ends and its attributes begin
_ARGS_END = re.compile(r"\),\s*[a-z_]+=")


def _kernel(kind: str):
    if kind not in _KERNELS:
        spec.load_launch(kind)  # raises, naming the file to add
    return _KERNELS[kind]


def operand_shapes(event: Event) -> list:
    """The operand shapes in an op event's HLO text."""
    args = _ARGS_END.split(event.name.split("custom-call(", 1)[-1], 1)[0]
    return [tuple(int(d) for d in m.group(1).split(",") if d) for m in _OPERAND.finditer(args)]


def launch_dims(kind: str, event: Event) -> Optional[tuple]:
    """The census dims of one kernel event, read from its operands by the
    kind's file as the census reads them from the jaxpr's: a GEMM's
    ``(m, k, n)``, also with a scalar-prefetched layer index first;
    flash's ``(b, heads, kv_heads, s, hd)``."""
    return spec.load_launch(kind).dims(operand_shapes(event))


def kernel_events(tr: Trace, kind: str) -> list:
    pat = _kernel(kind)
    return [e for evs in tr.ops().values() for e in evs if pat.match(e.name)]


def kernel_launches(tr: Trace, kind: str) -> int:
    return len(kernel_events(tr, kind))


def kernel_seconds(tr: Trace, kind: str) -> float:
    return sum(e.dur_ns for e in kernel_events(tr, kind)) / 1e9


def module_kernels(tr: Trace, kind: str) -> list:
    """``(module event, [its kernel events])`` for every executable run on
    every device: a kernel belongs to the run whose interval holds its start."""
    pat = _kernel(kind)
    out = []
    for plane, lines in tr.device.items():
        mods = lines.get("XLA Modules", [])
        ops = [e for e in lines.get("XLA Ops", []) if pat.match(e.name)]
        i = 0
        for m in mods:
            while i < len(ops) and ops[i].start_ns < m.start_ns:
                i += 1
            inside = []
            while i < len(ops) and ops[i].start_ns <= m.end_ns:
                inside.append(ops[i])
                i += 1
            out.append((m, inside))
    return out
