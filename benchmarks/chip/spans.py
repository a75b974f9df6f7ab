"""Reductions of the program's own spans (``repro.utils.spans``) that the
span metric files share.

The records come from ``repro.utils.spans.snapshot()``, taken in the
process that ran the cell once the run returned (a test hands its
own under ``run["spans"]``), and are clipped to the run's window: the
search of the tune cell (``traced[0]`` + ``window_s``), the calls of a
serving cell (``calls[0]["t0"]`` + ``window_s``), both on
``time.perf_counter``.  A program without spans gives no records, and
every reader then returns None.

A share is the union of the named spans' intervals over the window, so
compiles that overlap on the build pool count once.  A span's self time
is its interval less its children's (the tuner's proposals are the self
time of ``tune.workload``).

The profiler writes a program span to the host plane of a trace only if
the span opened and closed while it ran, so the spans still open when a
trace stops (the tuned workload, its ``tune.measure``) are missing
there, and ``trace.load`` keeps the events of one Python thread only.
The idle attribution therefore maps every record onto the trace's clock
(``clock_offset_ns``).
"""

from __future__ import annotations

import bisect
import collections
import re
import statistics

import numpy as np

from .harness import log

__all__ = [
    "PROGRAM", "records", "window", "merge", "total", "intersect", "subtract",
    "span_share", "serve_host_share", "clock_offset_ns", "idle_by_span", "idle_unattributed",
]

#: the program's span names; the Python tracer's events ("measured.py:346
#: _ensure", "$builtins isinstance") never match
PROGRAM = re.compile(r"(serve|tune|measure)\.[a-z_]+")

#: how far a trace event's start may lie from its record's under the prior
_TOL_NS = 5e6


def records(run) -> list:
    """The run's span records (``run["spans"]`` where a test put them),
    or [] from a program without spans."""
    if "spans" in run:
        return list(run["spans"])
    try:
        from repro.utils import spans
    except ImportError:
        return []
    return spans.snapshot()


def window(run):
    """``(start, end)`` of the run's window on ``time.perf_counter``."""
    if not run.get("window_s"):
        return None
    if run.get("calls"):
        t0 = run["calls"][0]["t0"]
    elif run.get("traced"):
        t0 = run["traced"][0]
    else:
        return None
    return t0, t0 + run["window_s"]


# -- intervals ----------------------------------------------------------------

def merge(intervals) -> list:
    """The union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def total(merged) -> float:
    return sum(b - a for a, b in merged)


def intersect(x, y) -> list:
    """Intersection of two merged lists."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(x, y) -> list:
    """``x`` less ``y``, both merged."""
    out = []
    for a, b in x:
        for c, d in y:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def _self_intervals(recs, name: str) -> list:
    children = collections.defaultdict(list)
    for r in recs:
        if r.parent is not None:
            children[r.parent].append((r.start, r.end))
    out = []
    for r in recs:
        if r.name == name:
            out += subtract([(r.start, r.end)], merge(children[r.id]))
    return out


def span_share(run, names, self_of: str = "") -> float | None:
    """Percent of the window that the union of the ``names`` spans (and
    the self time of the ``self_of`` spans) covers."""
    recs, win = records(run), window(run)
    if not recs or win is None:
        return None
    iv = [(r.start, r.end) for r in recs if r.name in names]
    if self_of:
        iv += _self_intervals(recs, self_of)
    covered = intersect(merge(iv), [win])
    return 100.0 * total(covered) / (win[1] - win[0])


def serve_host_share(run) -> float | None:
    """Percent of a serving window in which the engine's host work held a
    call: staging the prompts (``serve.stage``), launching the prefill
    and decode executables (``serve.launch``) and fetching the tokens
    (``serve.fetch``); its waits on the device (``serve.wait``) are left
    out."""
    return span_share(run, {"serve.stage", "serve.launch", "serve.fetch"})


# -- the idle device against the program's spans -------------------------------

def _program_events(trace) -> list:
    evs = [e for line in trace.host.values() for e in line if PROGRAM.fullmatch(e.name)]
    return sorted(evs, key=lambda e: e.start_ns)


def clock_offset_ns(events, recs, prior_ns: float) -> float:
    """The trace's clock less ``time.perf_counter``'s, in ns.  A trace's
    clock reads zero when the profiler's session starts, a fraction of a
    millisecond after the run reads ``traced[0]``, so ``prior_ns`` =
    ``-traced[0]`` in ns is right to about that.  Each program-span event
    of the trace with a record of its name starting within 5 ms of it,
    under the prior, refines it: the median of those pairs' differences.
    (``trace.load`` keeps one host line per thread name, and every Python
    thread bears the process's name, so the trace holds the program spans
    of one thread at most: the other threads' are known from the records
    alone.)"""
    starts: dict = collections.defaultdict(list)
    for r in recs:
        starts[r.name].append(r.start * 1e9)
    for v in starts.values():
        v.sort()
    diffs = []
    for e in events:
        xs = starts.get(e.name)
        if not xs:
            continue
        want = e.start_ns - prior_ns
        k = bisect.bisect_left(xs, want)
        near = min((want - xs[i] for i in (k - 1, k) if 0 <= i < len(xs)), key=abs)
        if abs(near) <= _TOL_NS:
            diffs.append(near)
    return prior_ns + (statistics.median(diffs) if diffs else 0.0)


def idle_by_span(run):
    """The traced window's device-idle time (the window, as far as the
    trace's events reach, less the union of the first device's
    operations) split by the innermost program span over it, each record
    mapped onto the trace's clock.  ``({span name: s}, s under no span,
    idle s)``, or None where the run holds nothing to read."""
    trace, traced, recs = run.get("trace"), run.get("traced"), records(run)
    if trace is None or traced is None or not recs or not trace.ops():
        return None
    events = _program_events(trace)
    prior = -traced[0] * 1e9
    offset = clock_offset_ns(events, recs, prior)
    log(f"trace clock: {len(events)} program-span events in the trace move the "
        f"session-start prior by {(offset - prior) / 1e3:.1f} us")
    ops = next(iter(trace.ops().values()))
    # the window as far as the trace saw it: the profiler starts after
    # the run's clock does
    seen = [e for line in trace.host.values() for e in line] + ops
    lo = max(traced[0] * 1e9 + offset, min(e.start_ns for e in seen))
    hi = min(traced[1] * 1e9 + offset, max(e.end_ns for e in seen))
    idle = subtract([(lo, hi)], merge((e.start_ns, e.end_ns) for e in ops))
    if total(idle) <= 0:
        return None
    mapped = [(r.start * 1e9 + offset, r.end * 1e9 + offset, r.name) for r in recs]
    # cut the window at every boundary; each piece is idle or not, and
    # belongs to the innermost span over it (outermost written first)
    cuts = np.unique(np.array(
        [t for a, b in idle for t in (a, b)] + [t for a, b, _ in mapped for t in (a, b)]))
    mid = (cuts[:-1] + cuts[1:]) / 2
    k = np.searchsorted(np.array([a for a, _ in idle]), mid, side="right") - 1
    is_idle = (k >= 0) & (mid < np.array([b for _, b in idle])[np.maximum(k, 0)])
    names = sorted({n for _, _, n in mapped})
    owner = np.full(mid.shape, -1)
    for a, b, name in sorted(mapped, key=lambda m: m[0] - m[1]):
        owner[np.searchsorted(cuts, a):np.searchsorted(cuts, b)] = names.index(name)
    length = np.diff(cuts) * is_idle / 1e9
    by_name = {n: float(length[owner == i].sum()) for i, n in enumerate(names)}
    return by_name, float(length[owner < 0].sum()), total(idle) / 1e9


def idle_unattributed(run) -> float | None:
    """Percent of the traced window's device-idle time that no program
    span covers (``idle_by_span``); logs the split."""
    split = idle_by_span(run)
    if split is None:
        return None
    by_name, none_s, idle_s = split
    log("idle by innermost span (s): " + ", ".join(
        f"{n} {v:.4f}" for n, v in sorted(by_name.items(), key=lambda kv: -kv[1]) if v)
        + f"; none {none_s:.4f} of {idle_s:.4f}")
    return 100.0 * none_s / idle_s
